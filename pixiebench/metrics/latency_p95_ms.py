"""95th percentile of request latency: due time to result, over every
request sent in the window; a request that failed or never returned counts
as infinite.  Nearest rank, so the number is one request's latency."""

import numpy as np


def read(run):
    return float(np.percentile(run.latency_ms(), 95, method="inverted_cdf"))
