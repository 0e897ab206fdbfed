"""Stage split of a traced serving window, from what the program marks
itself: its ``pixie.*`` host spans (``TraceAnnotation``s in
``serving/server.py``, with their arguments) and the ``pixie.*``
``jax.named_scope`` stages of the serving step (``core/service.py``,
``core/walk.py``), on the profiler's clock.

``trace.py`` reduces the benchmark's own ``pb.*`` spans and the device's op
and module events; this module adds, from the same ``.xplane.pb``:

* each device op's stage: the innermost ``pixie.*`` component of the op's
  name stack (``vmap(pixie.walk)/while/body/pixie.walk.hop/...`` is
  ``pixie.walk.hop``), read from the ``tf_op`` stat of the op event's
  metadata.  A loop op has none: it takes the stage enclosing those of
  its body's ops, and an op with no stage inside or out the stage of the
  innermost op around it;
* device time per stage: every op's own time (its span less the ops that
  run inside it, so a loop op and its body are never counted twice),
  clipped to the window and summed by stage; ``unscoped`` is the rest of
  the window's module time;
* idle gaps named by the span (``pb.*`` or ``pixie.*``) that is the
  innermost one over most of the gap;
* the counters ``ServerStats`` keeps (batch fill, walk steps taken and
  budgeted, batches on the batch-native walk loop) over the window, from
  snapshots before and after it.

``run.measure`` fills ``run.Run.stages`` in every traced window.  The metric
readers ``walk_hop_ms``, ``count_ms``, ``topk_ms``, ``rank_ms``,
``dispatch_form_ms``, ``batch_fill`` and ``steps_taken_share`` read it and
return None where a run has none.

    python3 -m pixiebench.stages --workload <cell> --seed <n> --seconds <s>

sets the cell up as ``run.py`` does, drives one traced window
(``run.measure``) and prints one JSON object: the stage readers' metrics,
the cell's per-layer metrics and ``completed_rps`` of the traced window,
the breakdown with stages, and the counters.  It does not check the
answers; ``run.py`` does.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from pixiebench import registry, run, trace

SPAN_PREFIX = "pixie."
UNSCOPED = "unscoped"
COUNTERS = ("lanes_filled", "lanes_dispatched", "steps_taken",
            "steps_budgeted", "batches_batch_native")
_SCOPE = re.compile(r"pixie\.[A-Za-z0-9_.]*[A-Za-z0-9_]")

Op = Tuple[str, int, int, Optional[str]]        # (name, start, dur, stage)
Span = Tuple[str, int, int, Dict]               # (name, start, dur, args)


class Stages(NamedTuple):
    device_ns: Dict[str, float]   # stage -> own op time in window, per device
    op_stage: Dict[str, str]      # op name -> its stage
    spans: List[Span]             # pixie.* host spans
    counters: Dict[str, int]      # ServerStats deltas over the window


def scope_of(name_stack: Optional[str]) -> Optional[str]:
    """The innermost ``pixie.*`` stage of a name stack (None if none)."""
    found = _SCOPE.findall(name_stack or "")
    return found[-1] if found else None


def _xspace_class():
    """A message class for the parts of tsl's ``xplane.proto`` read here.
    ``ProfileData`` gives an event's own stats but not those of its
    metadata, where a device op's name stack (``tf_op``) is kept; fields
    left out here are skipped on the wire."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory

    t = descriptor_pb2.FieldDescriptorProto
    i64, u64, f64, text = (t.TYPE_INT64, t.TYPE_UINT64, t.TYPE_DOUBLE,
                           t.TYPE_STRING)
    schema = {  # message: [(field, number, type, repeated)]
        "XSpace": [("planes", 1, "XPlane", True)],
        "XPlane": [("name", 2, text, False), ("lines", 3, "XLine", True),
                   ("event_metadata", 4, "EventMetadataEntry", True),
                   ("stat_metadata", 5, "StatMetadataEntry", True)],
        "EventMetadataEntry": [("key", 1, i64, False),
                               ("value", 2, "XEventMetadata", False)],
        "StatMetadataEntry": [("key", 1, i64, False),
                              ("value", 2, "XStatMetadata", False)],
        "XLine": [("name", 2, text, False), ("timestamp_ns", 3, i64, False),
                  ("events", 4, "XEvent", True)],
        "XEvent": [("metadata_id", 1, i64, False),
                   ("offset_ps", 2, i64, False),
                   ("duration_ps", 3, i64, False),
                   ("stats", 4, "XStat", True)],
        "XStat": [("metadata_id", 1, i64, False),
                  ("double_value", 2, f64, False),
                  ("uint64_value", 3, u64, False),
                  ("int64_value", 4, i64, False),
                  ("str_value", 5, text, False),
                  ("ref_value", 7, u64, False)],
        "XEventMetadata": [("name", 2, text, False),
                           ("stats", 5, "XStat", True)],
        "XStatMetadata": [("name", 2, text, False)],
    }
    proto = descriptor_pb2.FileDescriptorProto(
        name="pixiebench_xplane.proto", package="pixiebench_xplane",
        syntax="proto3")
    for msg, fields in schema.items():
        m = proto.message_type.add(name=msg)
        if msg == "XStat":
            m.oneof_decl.add(name="value")
        for name, number, typ, repeated in fields:
            f = m.field.add(name=name, number=number, label=(
                t.LABEL_REPEATED if repeated else t.LABEL_OPTIONAL))
            if isinstance(typ, str):
                f.type = t.TYPE_MESSAGE
                f.type_name = f".pixiebench_xplane.{typ}"
            else:
                f.type = typ
            if msg == "XStat" and number > 1:
                f.oneof_index = 0
    pool = descriptor_pool.DescriptorPool()
    pool.Add(proto)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("pixiebench_xplane.XSpace"))


def _stat_values(stats, names: Dict[int, str]) -> Dict:
    out = {}
    for st in stats:
        which = st.WhichOneof("value")
        if which == "ref_value":
            out[names.get(st.metadata_id, "")] = names.get(st.ref_value, "")
        elif which is not None:
            out[names.get(st.metadata_id, "")] = getattr(st, which)
    return out


def read_xspace(path: str):
    """Yield ``(plane, line, event name, start_ns, duration_ns, stats)``
    for every event of a trace file, the event's stats merged over those
    of its metadata and named (string references resolved)."""
    with open(path, "rb") as f:
        space = _xspace_class().FromString(f.read())
    for plane in space.planes:
        names = {e.key: e.value.name for e in plane.stat_metadata}
        meta = {e.key: (e.value.name, _stat_values(e.value.stats, names))
                for e in plane.event_metadata}
        for line in plane.lines:
            for ev in line.events:
                name, stats = meta.get(ev.metadata_id, ("", {}))
                yield (plane.name, line.name, name,
                       int(line.timestamp_ns + ev.offset_ps / 1000),
                       int(ev.duration_ps / 1000),
                       dict(stats, **_stat_values(ev.stats, names)))


def load(path: str) -> Tuple[Dict[str, List[Op]], List[Span]]:
    """Device ops with their own stages (from ``tf_op``, the op's
    ``op_name`` metadata), per ``/device:TPU:n`` plane, and the ``pixie.*``
    host spans with their arguments, of one trace file."""
    ops: Dict[str, List[Op]] = {}
    spans: List[Span] = []
    for plane, line, name, start, dur, stats in read_xspace(path):
        if trace._DEVICE_PLANE.match(plane):
            if line == trace.OPS_LINE:
                ops.setdefault(plane, []).append(
                    (name, start, dur, scope_of(stats.get("tf_op"))))
        elif plane.startswith("/host:") and name.startswith(SPAN_PREFIX):
            spans.append((name, start, dur, stats))
    return ops, spans


def _enclosing_stage(inner: Sequence[Tuple[str, int]]) -> Optional[str]:
    """The stage that encloses those of the ops that fill 90% of a loop
    body's time (``pixie.walk`` for ``pixie.walk.hop`` and
    ``pixie.walk.count``), or None; ``inner`` is ``(stage, duration)`` of
    the ops directly inside the loop."""
    by_stage: Dict[str, int] = {}
    for st, d in inner:
        by_stage[st] = by_stage.get(st, 0) + d
    kept, filled = [], 0
    for st, d in sorted(by_stage.items(), key=lambda kv: -kv[1]):
        if filled >= 0.9 * sum(by_stage.values()):
            break
        kept.append(st.split("."))
        filled += d
    common = []
    for level in zip(*kept):
        if len(set(level)) > 1:
            break
        common.append(level[0])
    return ".".join(common) if len(common) > 1 else None


def own_time(ops: Sequence[Op], lo: int, hi: int
             ) -> Tuple[Dict[str, float], Dict[str, str]]:
    """``(ns per stage, stage per op name)`` of one device's ops in
    [lo, hi).  An op's own time is its span less the spans of the ops
    directly inside it.  An op without a stage (a loop op carries no name
    stack) takes the stage enclosing most of its body's
    (``_enclosing_stage``), else that of the innermost op around it; the
    first instance of a name decides the name's stage."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    parent: List[Optional[int]] = [None] * len(ops)
    inner: List[List[int]] = [[] for _ in ops]
    open_: List[int] = []               # ops around the current one
    for i in order:
        while open_ and ops[open_[-1]][1] + ops[open_[-1]][2] <= ops[i][1]:
            open_.pop()
        if open_:
            parent[i] = open_[-1]
            inner[open_[-1]].append(i)
        open_.append(i)
    stage = [op[3] for op in ops]
    for i in reversed(order):           # from the ops inside
        if stage[i] is None:
            stage[i] = _enclosing_stage(
                [(stage[j], ops[j][2]) for j in inner[i] if stage[j]])
    for i in order:                     # from the op around
        if stage[i] is None and parent[i] is not None:
            stage[i] = stage[parent[i]]
    ns: Dict[str, float] = {}
    op_stage: Dict[str, str] = {}
    for i in order:
        name, s, d, _ = ops[i]
        if stage[i] is None:
            continue
        op_stage.setdefault(name, stage[i])
        own = min(s + d, hi) - max(s, lo)
        for j in inner[i]:
            _, sj, dj, _ = ops[j]
            own -= max(0, min(sj + dj, s + d, hi) - max(sj, lo))
        if own > 0:
            ns[stage[i]] = ns.get(stage[i], 0.0) + own
    return ns, op_stage


def reduce(ops: Dict[str, List[Op]], summary: trace.Summary,
           spans: List[Span], counters: Dict[str, int]) -> Stages:
    """Own device time per stage in ``summary``'s window, averaged over
    the devices (``ops`` holds the planes ``summary`` read); ``unscoped``
    is the rest of their module time."""
    lo, hi = summary.window
    device_ns: Dict[str, float] = {}
    op_stage: Dict[str, str] = {}
    n = max(len(ops), 1)
    for plane in sorted(ops):
        ns, names = own_time(ops[plane], lo, hi)
        for k, v in ns.items():
            device_ns[k] = device_ns.get(k, 0.0) + v / n
        for k, v in names.items():
            op_stage.setdefault(k, v)
    device_ns[UNSCOPED] = max(
        0.0, summary.module_ns - sum(device_ns.values()))
    return Stages(device_ns, op_stage, spans, counters)


def name_gap(gap: trace.Interval, spans: Sequence[Tuple]) -> str:
    """The span that covers most of ``gap`` as the innermost span there:
    each instant of the gap goes to the shortest span around it (``idle``
    when no span covers any of it)."""
    lo, hi = gap
    near = [(max(s, lo), min(s + d, hi), d, name)
            for name, s, d, *_ in spans if min(s + d, hi) > max(s, lo)]
    cuts = sorted({t for a, b, _, _ in near for t in (a, b)})
    owned: Dict[str, int] = {}
    for a, b in zip(cuts, cuts[1:]):
        around = [sp for sp in near if sp[0] <= a and b <= sp[1]]
        if around:
            name = min(around, key=lambda sp: sp[2])[3]
            owned[name] = owned.get(name, 0) + b - a
    return max(owned, key=owned.get) if owned else "idle"


def breakdown(summary: trace.Summary, st: Stages, top: int = 10) -> Dict:
    """``trace.breakdown`` with each device op's stage and idle gaps named
    by ``name_gap``, and seconds per stage."""
    ops = sorted(summary.op_ns.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(trace.device_gaps(summary),
                  key=lambda g: g[0] - g[1])[:top]
    spans = list(summary.host_spans) + list(st.spans)
    return {
        "device_ops": [
            [f"{name.split(' = ', 1)[0]} [{st.op_stage.get(name, UNSCOPED)}]",
             ns / 1e9] for name, ns in ops],
        "idle_gaps": [[name_gap(g, spans), (g[1] - g[0]) / 1e9]
                      for g in idle],
        "device_stages": [[k, v / 1e9] for k, v in sorted(
            st.device_ns.items(), key=lambda kv: -kv[1])[:top]],
    }


# -- what the metric readers share -------------------------------------------

def device_ms_per_batch(run_, stage: str) -> Optional[float]:
    """Own device time of ``stage`` in the window, per batch dispatched."""
    st = getattr(run_, "stages", None)
    if st is None or not run_.batches or stage not in st.device_ns:
        return None
    return st.device_ns[stage] / run_.batches / 1e6


def span_ms_per_batch(run_, name: str) -> Optional[float]:
    """Host time of the ``name`` spans inside the window, per batch."""
    st = getattr(run_, "stages", None)
    if st is None or run_.summary is None or not run_.batches:
        return None
    lo, hi = run_.summary.window
    parts = [min(s + d, hi) - max(s, lo) for n, s, d, _ in st.spans
             if n == name]
    if not parts:
        return None
    return sum(p for p in parts if p > 0) / run_.batches / 1e6


def counter_share(run_, part: str, whole: str) -> Optional[float]:
    """100 x the window's ``part`` over its ``whole`` counter."""
    st = getattr(run_, "stages", None)
    if st is None or not st.counters.get(whole):
        return None
    return 100.0 * st.counters[part] / st.counters[whole]


# -- the command ---------------------------------------------------------------

STAGE_METRICS = ("walk_hop_ms", "count_ms", "topk_ms", "rank_ms",
                 "dispatch_form_ms", "batch_fill", "steps_taken_share")


def counters(server) -> Dict[str, int]:
    """The ``ServerStats`` counters the readers use."""
    return {k: getattr(server.stats, k) for k in COUNTERS}


def run_stages(cell: Dict, config: Dict, traffic: Dict, seed: int,
               seconds: float, metrics: List[Dict]) -> Dict:
    """Set up, drive one traced window and reduce it; returns the result
    object ``main`` prints."""
    from pixiebench import loadgen

    c = run.set_up(cell, config, traffic, seed)
    reqs = loadgen.schedule(traffic, seed, seconds, c.offsets)
    setup_s = run.process_age_s()
    w = run.measure(c, reqs, trace=True)
    r = run.window_run(reqs, w, seconds, setup_s)
    names = ["completed_rps"] + [m["name"] for m in metrics] + list(
        STAGE_METRICS)
    values = {}
    for name in dict.fromkeys(names):
        v = registry.metric_reader(name)(r)
        if v is not None:
            values[name] = v
    return {
        "attempted": len(reqs), "failed": int(w.rec["failed"].sum()),
        "metrics": values, "counters": w.stages.counters,
        "device": {"kind": c.devices[0].device_kind,
                   "busy_s": w.summary.busy_s,
                   "window_s": w.summary.window_s},
        "breakdown": breakdown(w.summary, w.stages),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    bench = registry.load_benchmark()
    cell = registry.cell(bench, args.workload)
    sys.path.insert(0, str(registry.ROOT / "src"))
    try:
        out = run_stages(cell, registry.config(bench, cell["config"]),
                         registry.traffic(cell["traffic"]), args.seed,
                         args.seconds,
                         registry.metrics_for(bench, cell["name"], True))
    except run.NoAccelerator as e:
        run.log(f"pixiebench.stages: {e}")
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
