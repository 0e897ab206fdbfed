"""Median request latency: due time to result, over every request sent in
the window; a request that failed or never returned counts as infinite.  Nearest rank, so the number is one request's latency."""

import numpy as np


def read(run):
    return float(np.percentile(run.latency_ms(), 50, method="inverted_cdf"))
