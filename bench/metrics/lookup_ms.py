"""Device time per batch of the lookup kernels, on the busiest chip."""


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.traced:
        return None
    per_chip = [t.busy_ns(c, "lookup") for c in range(t.n_chips)]
    if max(per_chip) <= 0:
        return None
    return max(per_chip) / len(ctx.traced) * 1e-6
