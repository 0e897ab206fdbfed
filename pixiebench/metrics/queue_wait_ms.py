"""Median queue wait the server reports per request (``QueryResult.wait_ms``:
due time, passed to ``submit`` as ``now``, to dispatch)."""

import numpy as np


def read(run):
    w = run.wait_ms[np.isfinite(run.wait_ms)]
    return float(np.median(w)) if w.size else None
