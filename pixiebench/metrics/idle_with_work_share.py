"""Share of the traced window in which some request was queued or in
flight (due time to result) and no operation ran on the device."""

from pixiebench import trace


def read(run):
    s = run.summary
    if s is None or not s.busy:
        return None
    lo, hi = s.window
    work = trace.clip(run.request_intervals_ns(), lo, hi)
    idle = trace.gaps(s.busy[0], lo, hi)
    return 100.0 * trace.intersect_ns(work, idle) / (hi - lo)
