"""The whole step's share of the chips' peak: served queries per second times
the least time one query needs (its tower FLOPs at peak FLOP/s, or its
lookup bytes at peak bandwidth, whichever is longer), over the chips."""


def read(ctx):
    if ctx.window["loop"] != "closed" or not ctx.traced or ctx.peaks is None:
        return None
    m, cfg = ctx.cell.model, ctx.cell.cfg
    f_q = m.query_flops(cfg) + m.lookup_flops(cfg, ctx.batch) / ctx.batch
    b_q = sum(m.lookup_bytes(cfg, b["distinct"], ctx.batch) for b in ctx.traced) / len(ctx.traced) / ctx.batch
    t_q = max(f_q / ctx.peaks["flops_per_s"], b_q / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * ctx.window["qps"] * t_q / ctx.chips
