"""95th percentile of request latency, from issue to read results on the
host, over every request of the window (host clock)."""
import numpy as np


def read(ctx):
    return float(np.percentile(ctx.latencies_s, 95)) * 1e3
