"""The least time the chips could take for a batch's lookups (the bytes they
must move at peak bandwidth; their adds at peak FLOP/s are far less), as a
share of the lookup kernels' device time on the busiest chip."""


def read(ctx):
    t = ctx.trace
    if t is None or not ctx.traced or ctx.peaks is None:
        return None
    busy = max(t.busy_ns(c, "lookup") for c in range(t.n_chips))
    if busy <= 0:
        return None
    m, cfg = ctx.cell.model, ctx.cell.cfg
    need_s = 0.0
    for b in ctx.traced:
        by_bytes = m.lookup_bytes(cfg, b["distinct"], ctx.batch) / ctx.peaks["hbm_bytes_per_s"]
        by_flops = m.lookup_flops(cfg, ctx.batch) / ctx.peaks["flops_per_s"]
        need_s += max(by_bytes, by_flops) / ctx.chips
    return 100.0 * need_s / (busy * 1e-9)
