"""Share of the traced window in which no device operation ran, averaged over
the chips: 1 - busy / window."""


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0 or not t.ops:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
