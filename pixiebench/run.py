"""One run of one benchmark cell on the chip it is started on.

    python3 -m pixiebench.run --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up enables JAX's persistent compilation cache at ``<checkout>/.jax_cache``,
refuses to go on without a TPU (or with fewer chips than the cell asks
for), builds the configuration's graph on the device from the seed (and,
where the configuration has a ``ranker`` section, the stage-2 ranker's
parameters: ``rankgen.py``), constructs ``PixieServer`` with only what the
configuration states (graph, walk settings, query width, ranker; batch
size, buckets, ``max_wait_ms`` and the walk engine stay at the program's
defaults) and warms up the serving shapes.  The window then drives the
server open-loop for ``--seconds``: at each request's due time the client
builds its query (``service.build_query`` for action histories), calls
``submit(..., now=due)`` (on a ranked configuration with ``scenario=`` the
head the mix names) and ``pump(now=wall)``; whenever batches are in flight
it collects them with ``harvest()``.  A request's latency runs from its due
time to the return of the ``harvest`` that delivered it, so a stall counts
against every request behind it.  After the window every request is
drained; then the device's peak memory is read, the server is freed, and a
sample of the answers is checked against the plain reference
(``check.py``).  With ``--trace 1`` the window is profiled, and the trace
is reduced to device time per stage, the program's spans and its counters
over the window (``stages.py``), which the per-layer readers read.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``check``: each number compared with its limit.
The same numbers end standard error.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from contextlib import nullcontext
from typing import Dict, List, NamedTuple, Optional

import numpy as np

from pixiebench import registry

ROOT = registry.ROOT
WARMUP_BATCHES = 3
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/backend_compile_duration")


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def process_age_s() -> float:
    """Seconds since this process started."""
    import psutil

    return time.time() - psutil.Process().create_time()


def enable_compile_cache() -> None:
    """The program's cache rule (``JAX_COMPILATION_CACHE_DIR``, else
    ``<checkout>/.jax_cache``), with every program cached however small
    or quick to compile, so a warm run compiles nothing."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable

    enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def require_tpu(chips: int):
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoAccelerator(f"needs a TPU; JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoAccelerator(f"needs {chips} chips; JAX found {len(devices)}")
    return devices[:chips]


def peaks_for(kind: str) -> Dict:
    with open(registry.BENCH_DIR / "peaks.json") as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} has no entry in peaks.json")
    return table["devices"][kind]


class CompileCounter:
    """Counts traces and backend compiles JAX reports while ``on``."""

    def __init__(self):
        import jax

        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event, duration, **_):
        if self.on and event in _COMPILE_EVENTS:
            self.count += 1


@dataclasses.dataclass
class Run:
    """What the metric readers get.  Times are seconds from the window's
    start unless named otherwise."""

    seconds: float
    setup_s: float
    due: np.ndarray
    submitted: np.ndarray
    done: np.ndarray           # NaN: never answered
    wait_ms: np.ndarray        # the server's QueryResult.wait_ms
    failed: np.ndarray         # refused or never answered
    pumps: List               # (start, end, batches) of pumps that ran
    batches: int
    summary: object = None     # trace.Summary of a --trace 1 run
    trace_t0_ns: int = 0       # trace clock at the window's start
    stages: object = None      # stages.Stages of a --trace 1 run

    def latency_ms(self) -> np.ndarray:
        lat = (self.done - self.due) * 1e3
        return np.where(self.failed | ~np.isfinite(lat), np.inf, lat)

    def request_intervals_ns(self):
        end = np.where(np.isfinite(self.done), self.done,
                       self.summary.window_s if self.summary else self.seconds)
        t = lambda s: int(self.trace_t0_ns + s * 1e9)
        return [(t(a), t(b)) for a, b in zip(self.due, end)]


def _query(req, n_slots: int, service):
    """(pins, weights) the client submits for one request."""
    if req.actions is None:
        return [int(p) for p in req.pins], [1.0]
    history = [service.UserAction(pin=int(p), action=a, age_hours=float(h))
               for p, a, h in zip(req.pins, req.actions, req.ages_h)]
    pins, weights = service.build_query(history, n_slots=n_slots)
    keep = pins >= 0
    return pins[keep].tolist(), weights[keep].tolist()


def _sleep_until(t: float) -> None:
    left = t - time.perf_counter()
    if left > 0.002:
        time.sleep(left - 0.001)
    while time.perf_counter() < t:
        pass


def _head(scenario: Optional[int]) -> Dict:
    """``submit``'s head argument: none on a retrieval-only server."""
    return {} if scenario is None else {"scenario": scenario}


def drive(c: "Cell", reqs, t0: float, span) -> Dict:
    """The open loop: submit each request at its due time, pump, harvest.
    Returns the per-request records (seconds from ``t0``)."""
    from repro.core import service

    server, n_slots, head = c.server, c.config["n_slots"], _head(c.scenario)
    n = len(reqs)
    due_abs = t0 + np.array([r.due_s for r in reqs])
    rec = {
        "submitted": np.full(n, np.nan), "done": np.full(n, np.nan),
        "wait_ms": np.full(n, np.nan), "failed": np.zeros(n, bool),
        "sent": [None] * n, "answers": {}, "pumps": [], "batches": 0,
    }
    index_of = {}
    outstanding, i = 0, 0
    while True:
        now = time.perf_counter()
        while i < n and due_abs[i] <= now:
            with span("pb.build_query"):
                pins, weights = _query(reqs[i], n_slots, service)
            with span("pb.submit"):
                rid = server.submit(pins, weights, user_feat=reqs[i].feat,
                                    now=float(due_abs[i]), **head)
            rec["submitted"][i] = time.perf_counter() - t0
            if rid is None:
                rec["failed"][i] = True
            else:
                index_of[rid] = i
                rec["sent"][i] = (rid, pins, weights)
            i += 1
            now = time.perf_counter()
        if server.pending():
            start = time.perf_counter()
            with span("pb.pump"):
                k = server.pump(now=start)
            if k:
                rec["pumps"].append((start - t0, time.perf_counter() - t0, k))
                rec["batches"] += k
                outstanding += k
        if outstanding:
            with span("pb.harvest"):
                results = server.harvest()
            t = time.perf_counter() - t0
            for q in results:
                j = index_of.pop(q.req_id)
                rec["done"][j] = t
                rec["wait_ms"][j] = q.wait_ms
                rec["answers"][j] = (np.asarray(q.scores), np.asarray(q.ids))
            outstanding = 0
            continue
        nxt = min(due_abs[i] if i < n else np.inf,
                  server.next_deadline() or np.inf)
        if not np.isfinite(nxt):
            break
        with span("pb.wait"):
            _sleep_until(nxt)
    rec["failed"] |= ~np.isfinite(rec["done"])
    return rec


def warm_up(server, reqs, n_slots: int, batch_size: int,
            scenario: Optional[int] = None) -> None:
    """Serve full and partial batches through the window's own calls."""
    from repro.core import service

    at = 0
    for size in [batch_size] * (WARMUP_BATCHES - 1) + [1]:
        for r in reqs[at:at + size]:
            pins, weights = _query(r, n_slots, service)
            server.submit(pins, weights, user_feat=r.feat, **_head(scenario))
        at += size
        server.pump(now=time.perf_counter() + 1.0)
        for q in server.harvest():
            np.asarray(q.scores)


@dataclasses.dataclass
class Cell:
    """A cell set up for its window: graph, server and the request mix."""

    cell: Dict
    config: Dict
    traffic: Dict
    seed: int
    devices: list
    spec: object
    graph: object
    server: object
    server_seed: int
    offsets: np.ndarray
    scenario: Optional[int] = None   # the mix's head, on a ranked server


def head_of(config: Dict, traffic: Dict) -> Optional[str]:
    """The ranker head the mix names, or None for a retrieval-only cell.
    A configuration with a ``ranker`` section serves only a mix that names
    its ``scenario``, and a mix that names one only a ranked configuration:
    no head is ever taken by default."""
    head = traffic.get("scenario")
    if "ranker" in config and head is None:
        raise ValueError("the configuration states a ranker, but the "
                         "traffic mix names no scenario (ranker head)")
    if "ranker" not in config and head is not None:
        raise ValueError(f"the traffic mix names scenario {head!r}, but the "
                         "configuration states no ranker")
    return head


def set_up(cell: Dict, config: Dict, traffic: Dict, seed: int) -> Cell:
    """Everything before the window: chip check, cache, graph, ranker
    parameters, server, warm-up."""
    from pixiebench import graphgen, loadgen, rankgen
    from repro.core.walk import WalkConfig
    from repro.serving.server import PixieServer

    head = head_of(config, traffic)
    devices = require_tpu(cell["chips"])
    peaks_for(devices[0].device_kind)
    enable_compile_cache()
    spec = graphgen.spec_from_config(config["graph"])
    graph, _, gstats = graphgen.generate(spec, seed)
    log(f"graph: {json.dumps(gstats)}")
    server_seed = seed % (2**31 - 1)
    kw = {}
    if head is not None:
        from repro.serving import ranker as ranker_lib

        rcfg = ranker_lib.RankerConfig(
            n_items=spec.n_pins,
            **dict(config["ranker"],
                   scenarios=tuple(config["ranker"]["scenarios"])))
        kw["ranker"] = ranker_lib.RankRequest(
            rankgen.ranker_params(server_seed, spec.n_pins,
                                  config["ranker"]), rcfg)
    server = PixieServer(graph, WalkConfig(**config["walk"]),
                         n_slots=config["n_slots"], seed=server_seed, **kw)
    scenario = None if head is None else server.ranker.cfg.scenario_id(head)
    offsets = np.asarray(graph.p2b.offsets)
    warm = loadgen.warmup_requests(
        traffic, seed, WARMUP_BATCHES * server.batch_size, offsets)
    warm_up(server, warm, config["n_slots"], server.batch_size, scenario)
    return Cell(cell, config, traffic, seed, devices, spec, graph, server,
                server_seed, offsets, scenario)


class Window(NamedTuple):
    """What ``measure`` returns; ``summary`` and ``stages`` only when
    traced."""

    rec: Dict
    summary: object            # trace.Summary
    stages: object             # stages.Stages
    compiles: int              # compilations inside the window


def measure(c: Cell, reqs, trace: bool) -> Window:
    """Drive one window.  Traced, the profile is reduced to a
    ``trace.Summary`` and the stage split (``stages.reduce``, with the
    server's counters over the window) of the cell's own devices."""
    import jax

    from pixiebench import stages
    from pixiebench import trace as trace_lib

    counter = CompileCounter()
    log_dir = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="pixiebench-trace-")
        before = stages.counters(c.server)
        trace_lib.start(log_dir)
        span = jax.profiler.TraceAnnotation
    else:
        span = lambda _name: nullcontext()
    gc.collect()
    gc.disable()
    try:
        counter.on = True
        with span("pb.window"):
            t0 = time.perf_counter()
            rec = drive(c, reqs, t0, span)
        counter.on = False
    finally:
        gc.enable()
    summary = st = None
    if trace:
        jax.profiler.stop_trace()
        after = stages.counters(c.server)
        try:
            path = trace_lib.find_xplane(log_dir)
            devices_ev, host_ev = trace_lib.load(path)
            ops, spans = stages.load(path)
        finally:
            shutil.rmtree(log_dir, ignore_errors=True)
        mine = lambda plane: int(plane.rsplit(":", 1)[1]) < len(c.devices)
        summary = trace_lib.reduce(
            {k: v for k, v in devices_ev.items() if mine(k)}, host_ev)
        st = stages.reduce({k: v for k, v in ops.items() if mine(k)},
                           summary, spans,
                           {k: after[k] - before[k] for k in after})
    late = (rec["submitted"] - np.array([r.due_s for r in reqs])) * 1e3
    log(f"window: requests={len(reqs)} batches={rec['batches']} "
        f"compiles_in_window={counter.count} generator_late_ms: "
        f"p50={np.nanpercentile(late, 50):.3f} "
        f"p95={np.nanpercentile(late, 95):.3f} max={np.nanmax(late):.3f}")
    return Window(rec, summary, st, counter.count)


def window_run(reqs, w: Window, seconds: float, setup_s: float) -> Run:
    """What the metric readers read of one window."""
    return Run(seconds=seconds, setup_s=setup_s,
               due=np.array([r.due_s for r in reqs]),
               submitted=w.rec["submitted"], done=w.rec["done"],
               wait_ms=w.rec["wait_ms"], failed=w.rec["failed"],
               pumps=w.rec["pumps"], batches=w.rec["batches"],
               summary=w.summary,
               trace_t0_ns=w.summary.window[0] if w.summary else 0,
               stages=w.stages)


def release_and_sample(c: Cell, rec: Dict, reqs) -> tuple:
    """Copy the graph (and a ranked cell's ranker: ``reference.Stage2``) to
    the host, free the program's state, and pick the answers to check.
    Returns ``(host graph, [check.Served], Stage2 or None)``."""
    import jax

    from pixiebench import check, reference

    g = c.graph
    arrays = {
        "p2b_offsets": g.p2b.offsets, "p2b_targets": g.p2b.targets,
        "p2b_feat_bounds": g.p2b.feat_bounds,
        "b2p_offsets": g.b2p.offsets, "b2p_targets": g.b2p.targets,
        "b2p_feat_bounds": g.b2p.feat_bounds,
    }
    hg = reference.host_graph(arrays, c.spec.n_pins, c.spec.max_pin_degree)
    st2 = None
    if c.scenario is not None:
        st2 = reference.stage2(jax.device_get(c.server.ranker.params),
                               c.config["ranker"], c.traffic["scenario"])
    del g, arrays
    c.graph = c.server = None
    gc.collect()
    ccfg = c.config["check"]
    n_slots = c.config["n_slots"]
    widths = np.array([len(s[1]) if s else 0 for s in rec["sent"]])
    picked = check.sample(widths, ~rec["failed"], c.seed, ccfg["sample"],
                          ccfg["widest"])
    served = []
    for j in picked:
        rid, pins, weights = rec["sent"][j]
        qp = np.full(n_slots, -1, np.int32)
        qw = np.zeros(n_slots, np.float32)
        qp[:len(pins)] = pins
        qw[:len(pins)] = weights
        scores, ids = rec["answers"][j]
        served.append(check.Served(rid, qp, qw, reqs[j].feat, scores, ids))
    return hg, served, st2


def free_and_check(c: Cell, rec: Dict, reqs) -> tuple:
    """Free the program's state, then compare a sample of the answers with
    the plain reference.  Returns ``(correct, numbers)``."""
    from pixiebench import check

    chunk_steps = c.server.cfg.chunk_steps
    hg, served, st2 = release_and_sample(c, rec, reqs)
    t_check = time.perf_counter()
    gaps = check.gaps(hg, served, c.server_seed, c.config["walk"],
                      chunk_steps, st2)
    widest = float(gaps.max()) if gaps.size else float("nan")
    log(f"check: {len(served)} {'ranked ' if st2 else ''}answers in "
        f"{time.perf_counter() - t_check:.1f} s; widest gap {widest:.3g}; "
        f"chunk_steps={chunk_steps}")
    return check.judge(gaps, int(rec["failed"].sum()), c.config["check"],
                       ranked=st2 is not None)


def run_cell(cell: Dict, config: Dict, traffic: Dict, seed: int,
             seconds: float, trace: bool, metrics: List[Dict]) -> Dict:
    """Set up, drive and check one run; returns the result object."""
    from pixiebench import loadgen, stages

    c = set_up(cell, config, traffic, seed)
    reqs = loadgen.schedule(traffic, seed, seconds, c.offsets)
    setup_s = process_age_s()
    w = measure(c, reqs, trace)
    rec, summary = w.rec, w.summary
    run = window_run(reqs, w, seconds, setup_s)
    values = {}
    for m in metrics:
        v = registry.metric_reader(m["name"])(run)
        if v is not None and np.isfinite(v):  # a lost request: see check
            values[m["name"]] = {"value": v, "unit": m["unit"]}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in c.devices)
    device = {"platform": c.devices[0].platform,
              "kind": c.devices[0].device_kind, "count": len(c.devices),
              "memory_peak_bytes": peak}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
    correct, numbers = free_and_check(c, rec, reqs)
    out = {
        "correct": bool(correct),
        "attempted": len(reqs),
        "failed": int(rec["failed"].sum()),
        "metrics": values,
        "device": device,
    }
    if summary is not None:
        out["breakdown"] = stages.breakdown(summary, w.stages)
    out["check"] = numbers
    return out


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    bench = registry.load_benchmark()
    cell = registry.cell(bench, args.workload)
    config = registry.config(bench, cell["config"])
    traffic = registry.traffic(cell["traffic"])
    metrics = registry.metrics_for(bench, cell["name"], bool(args.trace))
    sys.path.insert(0, str(ROOT / "src"))
    try:
        out = run_cell(cell, config, traffic, args.seed, args.seconds,
                       bool(args.trace), metrics)
    except NoAccelerator as e:
        log(f"pixiebench: {e}")
        return 3
    for name, c in out["check"].items():
        log(f"check {name}: {c['value']} limit {c['limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
