"""95th percentile of the latencies of all calls in the window, each from
its issue to ``block_until_ready`` on the whole result (linear
interpolation between order statistics)."""
import numpy as np


def read(run):
    return float(np.percentile(np.asarray(run.window.latencies) * 1e3, 95))
