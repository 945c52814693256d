"""The least time the chips could take to move the bytes that lookups into
the tables too large to replicate must move, as a share of ``gather_ms``.

Too large to replicate: a table whose float32 bytes exceed one chip's HBM
(``hbm_bytes`` of ``bench/peaks.json``) over the cell's chips; the bytes are
the kind's ``gather_bytes`` (u_t·E·4 + B·seq_t·4 + B·E·4 per such table), at
chips x peak bandwidth.  The row gather serves at least those tables (the
planner row-shards them, and their shards are beyond what the fused
kernel's schedule holds), and its time covers whatever else it serves, so
the share can only read low, never over 100%."""
from pathlib import Path

from bench.harness import load_module

_gather_ms = load_module(Path(__file__).with_name("gather_ms.py"))


def read(ctx):
    ms = _gather_ms.read(ctx)
    if ms is None or ctx.peaks is None:
        return None
    m, cfg = ctx.cell.model, ctx.cell.cfg
    need = sum(
        m.gather_bytes(cfg, b["distinct"], ctx.batch, ctx.peaks["hbm_bytes"], ctx.chips)
        for b in ctx.traced
    ) / len(ctx.traced)
    return 100.0 * need / (ctx.chips * ctx.peaks["hbm_bytes_per_s"]) / (ms * 1e-3)
