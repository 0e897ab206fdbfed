"""Requests answered, over the time from the window's start until the last
answer came back (or the window's end, if that is later): every request
sent in the window is waited for, so all of the work counts, over all of
the time it took."""

import numpy as np


def read(run):
    ok = ~run.failed & np.isfinite(run.done)
    span = max(run.seconds, float(np.max(run.done[ok], initial=0.0)))
    return float(np.sum(ok)) / span
