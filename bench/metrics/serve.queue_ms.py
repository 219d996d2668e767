"""serve.queue_ms: mean over the answered requests of (start of the step
that admitted it - its due time), on the benchmark's clock."""

import numpy as np


def read(ctx):
    waits = getattr(ctx, "queue_waits_s", None)
    if waits is None or not np.isfinite(waits).any():
        return None
    return float(np.nanmean(waits)) * 1e3
