"""95th percentile of the latency of every request in the window, ms."""
import numpy as np


def read(ctx):
    w = ctx.window
    if w is None or not w.latencies:
        return None
    return 1e3 * float(np.percentile(np.asarray(w.latencies), 95))
