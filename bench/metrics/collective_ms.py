"""Device time per batch of collectives (all-to-all, all-gather, all-reduce,
reduce-scatter, collective-permute), on the busiest chip."""


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.traced or t.n_chips < 2:
        return None
    return max(t.busy_ns(c, "collective") for c in range(t.n_chips)) / len(ctx.traced) * 1e-6
