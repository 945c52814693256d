"""Wall time per batch of the harness's calls into the server (submit and
pump) during which no device ran an operation."""


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.traced:
        return None
    spans = t.spans_named("bench.submit") + t.spans_named("bench.pump")
    if not spans:
        return None
    wall = sum(e - s for s, e in spans)
    return (wall - t.busy_within_ns(spans)) / len(ctx.traced) * 1e-6
