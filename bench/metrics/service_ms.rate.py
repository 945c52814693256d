"""Median wall time of a pump that served a batch: release to every handle
of the batch filled."""
import numpy as np


def read(ctx):
    pumps = ctx.window.get("pumps")
    if not pumps:
        return None
    return float(np.median([p["t_done"] - p["t_pump"] for p in pumps])) * 1e3
