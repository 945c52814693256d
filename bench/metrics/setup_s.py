"""Process start to the first measured query: imports, traffic pool, weights,
build, compile (or cache hit), warm-up."""


def read(ctx):
    return ctx.setup_s
