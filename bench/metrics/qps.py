"""Queries served by the path under test over the whole measured window."""


def read(ctx):
    return ctx.window["qps"] if ctx.window["loop"] == "closed" else None
