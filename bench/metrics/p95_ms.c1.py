"""95th percentile latency of the window's requests, client side (ms).

Closed loop: from each send to its answer; recorded, not judged."""
import numpy as np


def read(ctx):
    return float(np.percentile(ctx.latencies_ms(), 95))
