"""Median latency of the window's requests, client side (ms)."""
import numpy as np


def read(ctx):
    return float(np.percentile(ctx.latencies_ms(), 50))
