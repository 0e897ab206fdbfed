"""Knee sweep: one cell's open loop at a series of offered rates.

    python3 -m pixiebench.sweep --workload <cell> --seed <n> --seconds <s> \
        --rates 1,2,4,8 [--refine 2] [--trace-dir DIR] [--check]

Sets the cell up once, then drives one window per rate, in the order
given, with the cell's traffic mix at that rate, and prints per rate:
completed requests per second, latency p50/p95, mean batch fill, how late
the generator ran, and the backlog's growth (the least-squares slope of
latency against due time, times the window: how much later the window's
last requests finished than its first).  The ladder stops at the first rate not sustained
(``sustained``); ``--refine`` more windows then halve the interval between
the last sustained rate and that one.  The knee is the highest rate
sustained.  ``--trace-dir`` takes one traced window at 0.8 x the knee and
writes what the trace holds (planes, lines, first events, the reduced
summary) there; ``--check`` ends with the correctness check of the last
window.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

import numpy as np

from pixiebench import registry, run


def window_stats(rate: float, seconds: float, reqs, rec) -> dict:
    due = np.array([r.due_s for r in reqs])
    lat = (rec["done"] - due) * 1e3
    n = len(reqs)
    ok = np.isfinite(lat)
    late = (rec["submitted"] - due) * 1e3
    return {
        "rate": rate,
        "completed_rps": float(np.sum(ok)) / max(seconds,
                                                   float(np.nanmax(rec["done"]))),
        "p50_ms": float(np.nanpercentile(lat, 50)),
        "p95_ms": float(np.nanpercentile(lat, 95)),
        "batch_fill": n / max(rec["batches"], 1),
        "late_p95_ms": float(np.nanpercentile(late, 95)),
        "min_ms": float(np.nanmin(lat)),
        "growth_ms": float(np.polyfit(due[ok], lat[ok], 1)[0] * seconds),
        "failed": int(rec["failed"].sum()),
    }


def sustained(st: dict) -> bool:
    """A rate is sustained when every request was answered and latency grew
    through the window by less than the fastest request took (about one
    serving step): the queue did not build up."""
    return st["failed"] == 0 and st["growth_ms"] < st["min_ms"]


def dump_trace(c, reqs, out_dir: str) -> None:
    """One traced window; writes the trace's planes and lines, the first
    events of each line, and the reduced summary to ``out_dir``."""
    import jax
    from jax.profiler import ProfileData

    from pixiebench import trace as trace_lib

    log_dir = tempfile.mkdtemp(prefix="pixiebench-sweep-trace-")
    trace_lib.start(log_dir)
    with jax.profiler.TraceAnnotation("pb.window"):
        t0 = run.time.perf_counter()
        rec = run.drive(c, reqs, t0, jax.profiler.TraceAnnotation)
    jax.profiler.stop_trace()
    path = trace_lib.find_xplane(log_dir)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "trace_structure.txt"), "w") as f:
        for plane in ProfileData.from_file(path).planes:
            lines = list(plane.lines)
            f.write(f"PLANE {plane.name} lines={len(lines)}\n")
            for line in lines:
                ev = list(line.events)
                f.write(f"  LINE {line.name!r} events={len(ev)}\n")
                for e in ev[:6]:
                    f.write(f"    {e.name!r} start_ns={e.start_ns} "
                            f"dur_ns={e.duration_ns}\n")
    try:
        s = trace_lib.reduce(*trace_lib.load(path))
        summary = {"window_s": s.window_s, "busy_s": s.busy_s,
                   "module_s": s.module_ns / 1e9, "batches": rec["batches"],
                   "breakdown": trace_lib.breakdown(s),
                   "first_host_spans": s.host_spans[:20]}
    except ValueError as e:
        summary = {"error": str(e)}
    with open(os.path.join(out_dir, "trace_summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    if os.path.getsize(path) < 24 << 20:
        shutil.copy(path, os.path.join(out_dir, "window.xplane.pb"))
    shutil.rmtree(log_dir, ignore_errors=True)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--trace-dir", default=None)
    p.add_argument("--refine", type=int, default=2)
    p.add_argument("--check", action="store_true")
    args = p.parse_args(argv)
    sys.path.insert(0, str(registry.ROOT / "src"))
    from pixiebench import loadgen

    bench = registry.load_benchmark()
    cell = registry.cell(bench, args.workload)
    config = registry.config(bench, cell["config"])
    traffic = registry.traffic(cell["traffic"])
    c = run.set_up(cell, config, traffic, args.seed)
    print(json.dumps({"setup_s": run.process_age_s()}), flush=True)
    rates = [float(r) for r in args.rates.split(",")]

    def window(rate: float, i: int):
        reqs = loadgen.schedule(dict(traffic, rate_rps=rate), args.seed + i,
                                args.seconds, c.offsets)
        w = run.measure(c, reqs, trace=False)
        rec = w.rec
        st = window_stats(rate, args.seconds, reqs, rec)
        st["compiles_in_window"] = w.compiles
        st["sustained"] = sustained(st)
        print(json.dumps(st), flush=True)
        return st, reqs, rec

    ok_rate, bad_rate, i = None, None, 0
    for rate in rates:
        st, reqs, rec = window(rate, i)
        i += 1
        if not st["sustained"]:
            bad_rate = rate
            break
        ok_rate = rate
    for _ in range(args.refine if ok_rate and bad_rate else 0):
        mid = round((ok_rate + bad_rate) / 2, 2)
        st, reqs, rec = window(mid, i)
        i += 1
        if st["sustained"]:
            ok_rate = mid
        else:
            bad_rate = mid
    print(json.dumps({"knee": ok_rate, "first_unsustained": bad_rate}),
          flush=True)
    if args.trace_dir and ok_rate:
        t_reqs = loadgen.schedule(dict(traffic, rate_rps=0.8 * ok_rate),
                                  args.seed + i, args.seconds, c.offsets)
        dump_trace(c, t_reqs, args.trace_dir)
    if args.check:
        ok, numbers = run.free_and_check(c, rec, reqs)
        print(json.dumps({"correct": ok, "check": numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
