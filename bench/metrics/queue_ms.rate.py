"""99th percentile of the wait from a query's due time to the release of the
batch it rode in (the pump that served it began)."""
import numpy as np


def read(ctx):
    q = ctx.window.get("queue_s")
    return float(np.percentile(q, 99)) * 1e3 if q is not None and q.size else None
