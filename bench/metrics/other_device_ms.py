"""Device busy time per batch that is neither lookup kernel nor collective
(the tower, the executor's preparation around the kernel), on the busiest
chip."""


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.traced or not t.ops:
        return None
    chip = max(range(t.n_chips), key=t.busy_ns)
    rest = t.busy_ns(chip) - t.busy_ns(chip, "lookup") - t.busy_ns(chip, "collective")
    return rest / len(ctx.traced) * 1e-6
