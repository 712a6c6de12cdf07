"""95th percentile of the latency of the window's compile requests, on the
host clock (linear interpolation between order statistics)."""
import numpy as np


def value(ctx):
    return float(np.percentile([r["latency_s"] for r in ctx.served], 95))
