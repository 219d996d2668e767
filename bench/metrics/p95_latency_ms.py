"""p95_latency_ms: 95th percentile of the latency of every request due in
the window, each timed from its due time to the return of the step that
answered it; a request with no answer counts as infinitely late."""

import numpy as np


def read(ctx):
    lat = getattr(ctx, "latencies_s", None)
    if lat is None or len(lat) == 0:
        return None
    return float(np.percentile(lat, 95, method="higher")) * 1e3
