"""Median per-query latency, from the query's due time to the end of the
pump that answered it, over every query due in the window."""
import numpy as np


def read(ctx):
    lat = ctx.window.get("latency_s")
    return float(np.percentile(lat, 50)) * 1e3 if lat is not None and lat.size else None
