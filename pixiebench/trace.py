"""Reduction of a profiler trace to the numbers the metrics read.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes, through
``jax.profiler.ProfileData``, into plain event lists; ``reduce`` turns
those into a ``Summary``.  Tests build the lists by hand.

Device planes are the ``/device:TPU:<n>`` planes; on each, the ``XLA Ops``
line holds one event per device operation and the ``XLA Modules`` line one
per executed program.  Host spans are the benchmark's own
``TraceAnnotation``s, named ``pb.<what>``; the ``pb.window`` span marks the
traced window, and its start ties the host clock of the benchmark's
records to the trace's clock.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, NamedTuple, Sequence, Tuple

Event = Tuple[str, int, int]            # (name, start_ns, duration_ns)
Interval = Tuple[int, int]              # [start_ns, end_ns)

WINDOW_SPAN = "pb.window"
SPAN_PREFIX = "pb."
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Summary(NamedTuple):
    window: Interval                    # the traced window, trace clock
    n_devices: int
    busy: List[List[Interval]]          # per device, merged, clipped
    module_ns: float                    # module time in window, per device
    op_ns: Dict[str, float]             # per op name, per device
    host_spans: List[Event]             # pb.* spans other than the window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        if not self.n_devices:
            return 0.0
        tot = sum(span_ns(b) for b in self.busy)
        return tot / self.n_devices / 1e9


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    """Union of intervals, sorted and disjoint."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def span_ns(intervals: Sequence[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """The parts of [lo, hi) that no interval of ``busy`` covers."""
    out, at = [], lo
    for s, e in merge(clip(busy, lo, hi)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def intersect_ns(a: Sequence[Interval], b: Sequence[Interval]) -> int:
    """Length of the intersection of two unions of intervals."""
    a, b = merge(a), merge(b)
    i = j = tot = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def reduce(devices: Dict[str, Dict[str, List[Event]]],
           host: List[Event]) -> Summary:
    """``devices`` maps a plane name to ``{"ops": [...], "modules": [...]}``;
    ``host`` lists the host spans.  Raises when the window span is absent,
    when there is no device plane, or when a device ran no program inside
    the window: a trace read wrongly must not pass for an idle device."""
    wins = [e for e in host if e[0] == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    if not devices:
        raise ValueError("no device plane in the trace")
    _, ws, wd = max(wins, key=lambda e: e[2])
    lo, hi = ws, ws + wd
    busy, op_ns, mod = [], {}, 0.0
    n = len(devices)
    for name in sorted(devices):
        ev = devices[name]
        busy.append(merge(clip([(s, s + d) for _, s, d in ev["ops"]], lo, hi)))
        for op, s, d in ev["ops"]:
            part = min(s + d, hi) - max(s, lo)
            if part > 0:
                op_ns[op] = op_ns.get(op, 0.0) + part / n
        in_window = span_ns(clip([(s, s + d) for _, s, d in ev["modules"]],
                                 lo, hi))
        if not in_window:
            raise ValueError(f"{name}: no {MODULES_LINE} event inside the "
                             "traced window")
        mod += in_window / n
    spans = [e for e in host
             if e[0].startswith(SPAN_PREFIX) and e[0] != WINDOW_SPAN]
    return Summary((lo, hi), n, busy, mod, op_ns, spans)


def device_gaps(summary: Summary) -> List[Interval]:
    """Idle stretches of the window (on the first device)."""
    lo, hi = summary.window
    return gaps(summary.busy[0] if summary.busy else [], lo, hi)


def name_gap(gap: Interval, spans: Sequence[Event]) -> str:
    """The host span that covers most of ``gap`` (``idle`` when none)."""
    best, best_ns = "idle", 0
    by_name: Dict[str, List[Interval]] = {}
    for name, s, d in spans:
        by_name.setdefault(name, []).append((s, s + d))
    for name, iv in by_name.items():
        ov = intersect_ns([gap], iv)
        if ov > best_ns:
            best, best_ns = name, ov
    return best


def breakdown(summary: Summary, top: int = 10) -> Dict[str, list]:
    """The device operations that took most time, each named by its HLO
    instruction (the text before `` = ``), and the longest idle gaps, named
    by what the host was doing; seconds."""
    ops = sorted(summary.op_ns.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(device_gaps(summary), key=lambda g: g[0] - g[1])[:top]
    return {
        "device_ops": [[name.split(" = ", 1)[0], ns / 1e9]
                       for name, ns in ops],
        "idle_gaps": [[name_gap(g, summary.host_spans), (g[1] - g[0]) / 1e9]
                      for g in idle],
    }


def start(log_dir: str) -> None:
    """Start the profiler with host spans and device events but without
    the Python function tracer, whose per-call events would slow the
    client it is meant to watch."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str) -> Tuple[Dict[str, Dict[str, List[Event]]], List[Event]]:
    """Device op/module events and host ``pb.*`` spans of one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            ev = {}
            for line in plane.lines:
                which = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if which:
                    ev[which] = [(e.name, int(e.start_ns), int(e.duration_ns))
                                 for e in line.events]
            if len(ev) != 2:
                raise ValueError(f"{plane.name} lacks its {OPS_LINE!r} or "
                                 f"{MODULES_LINE!r} line")
            devices[plane.name] = ev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    (e.name, int(e.start_ns), int(e.duration_ns))
                    for e in line.events if e.name.startswith(SPAN_PREFIX)
                )
    return devices, host
