"""95th percentile latency of the window's requests, client side (ms), read
in the traced run: recorded, not judged.

Open loop: from each request's scheduled send to its answer."""
import numpy as np


def read(ctx):
    return float(np.percentile(ctx.latencies_ms(), 95))
