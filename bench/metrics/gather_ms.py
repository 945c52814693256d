"""Device time per traced batch of the row gather, on the busiest chip: the
lookup ops whose name path passes the gather kernel's jitted entry.  None
where no such op ran (a program without the row gather)."""
from bench.trace import union_ns

ENTRY = "embedding_bag_gm_gather"


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.traced:
        return None
    per_chip = [
        union_ns([(o.start, o.end) for o in t.chip_ops(c, "lookup") if ENTRY in o.path],
                 t.lo, t.hi)
        for c in range(t.n_chips)
    ]
    if max(per_chip) <= 0:
        return None
    return max(per_chip) / len(ctx.traced) * 1e-6
