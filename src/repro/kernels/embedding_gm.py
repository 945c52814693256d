"""GM strategy: row-at-a-time lookup streamed from global memory (HBM).

Paper §II-B: "Read one row at a time (with double buffering) either from the
off-chip memory (GM) or from the persistent buffer (L1) to the shared memory,
followed by pooling this row in an accumulation buffer."

TPU realization: the Pallas grid iterates over (query, lookup) pairs and the
*table's BlockSpec index_map is driven by the scalar-prefetched indices* — so
each grid step DMAs exactly the one indexed row HBM→VMEM, and the Pallas
pipeline double-buffers the row fetches automatically (the row for step
``(b, j+1)`` is in flight while step ``(b, j)`` accumulates).  The output
block for query ``b`` stays resident in VMEM across the ``s`` accumulation
steps (consecutive grid steps map to the same output block).  Table and
output carry a unit middle axis so that a one-row block spans the full last
two dims, as the TPU's (8, 128) block rule requires.  That reshape lays
the table out again in ``(1, 128)`` tiles, a 128-lane line per row: a copy
of 8x the bytes of an E=16 f32 table (512 MB for a 64 MB table, a compile
for a v5e), so this kernel suits tables of a few MB, which is what the
symmetric group's GM tables are.

:func:`embedding_bag_gm_gather` is the same data flow for chunks far larger
than a chip could lay out that way: the row gather of the fused executor
(``core/partition.py``).  Its buffer is lane-dense, ``(L, sub, 128)``: rows
back to back, ``128 // E`` to a 128-lane line, ``sub`` lines (one native
tile) to a block, so HBM holds the rows' logical bytes.  Each lookup DMAs
the block that holds its row into a ring of VMEM slots, with up to
``_INFLIGHT`` fetches in flight; the row's line is read out of its slot,
the other rows' lanes are masked, and one GEMM against a 0/1 fold matrix
moves the row's ``E`` lanes to the front (an exact copy at HIGHEST).
Lookups with row ``-1`` fetch nothing and give exact zeros.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat


def _gm_kernel(idx_ref, row_ref, out_ref):
    """Accumulate one streamed row into the per-query output block."""
    del idx_ref  # consumed by the index_map
    j = pl.program_id(1)
    row = row_ref[...].astype(jnp.float32)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = row

    @pl.when(j > 0)
    def _acc():
        out_ref[...] += row


@functools.partial(jax.jit, static_argnames=("interpret",))
def embedding_bag_gm(
    table: jax.Array,
    indices: jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    """GM-strategy pooled lookup. table (m, E), indices (B, s) -> (B, E) f32."""
    m, e = table.shape
    b, s = indices.shape
    flat_idx = indices.reshape(-1).astype(jnp.int32)

    out = pl.pallas_call(
        _gm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, s),
            in_specs=[
                # one row per grid step; the row number comes from the
                # prefetched indices -> pipelined, double-buffered row DMA.
                pl.BlockSpec(
                    (1, 1, e), lambda bi, j, idx: (idx[bi * s + j], 0, 0)
                ),
            ],
            out_specs=pl.BlockSpec((1, 1, e), lambda bi, j, idx: (bi, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, 1, e), jnp.float32),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(flat_idx, table.reshape(m, 1, e))
    return out.reshape(b, e)


_LANES = 128
_INFLIGHT = 64  # block fetches in flight per core
_TILE = 2048  # lookups per grid step (their rows sit in SMEM)


def _gather_kernel(ridx_ref, lines_ref, out_ref, ring, sel, sems, *, e, sub, tile):
    """Fetch the row of each of ``tile`` lookups and write it to ``out``."""
    rpl = _LANES // e  # rows per 128-lane line
    group = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1) // e

    def fetch(j, r):
        slot = j % _INFLIGHT
        return pltpu.make_async_copy(
            lines_ref.at[r // (rpl * sub)], ring.at[slot], sems.at[slot]
        )

    def step(j, carry):
        # lookup j - _INFLIGHT: its fetch is done, copy its row out
        @pl.when(j >= _INFLIGHT)
        def _take():
            k = j - _INFLIGHT
            r = ridx_ref[k]

            @pl.when(r >= 0)
            def _hit():
                fetch(k, r).wait()
                line = ring[k % _INFLIGHT, pl.ds((r // rpl) % sub, 1), :]
                sel[pl.ds(k, 1), :] = jnp.where(
                    group == r % rpl, line.astype(jnp.float32), 0.0
                )

            @pl.when(r < 0)
            def _miss():
                sel[pl.ds(k, 1), :] = jnp.zeros((1, _LANES), jnp.float32)

        # lookup j: start its fetch into the slot lookup k just freed
        @pl.when(j < tile)
        def _start():
            r = ridx_ref[j]

            @pl.when(r >= 0)
            def _go():
                fetch(j, r).start()

        return carry

    jax.lax.fori_loop(0, tile + _INFLIGHT, step, 0)
    fold = (
        jax.lax.broadcasted_iota(jnp.int32, (_LANES, e), 0) % e
        == jax.lax.broadcasted_iota(jnp.int32, (_LANES, e), 1)
    ).astype(jnp.float32)
    out_ref[...] = jnp.dot(
        sel[...], fold, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("e", "interpret"))
def embedding_bag_gm_gather(
    lines: jax.Array,
    ridx: jax.Array,
    *,
    e: int,
    interpret: bool = False,
) -> jax.Array:
    """Row-gather pooled lookup: ``lines`` (L, sub, 128) lane-dense buffer,
    ``ridx`` (G, B, s) buffer rows (-1 = none) -> (G, B, E) f32, each bag
    the sum of its ``s`` rows."""
    _, sub, lanes = lines.shape
    if lanes != _LANES or _LANES % e:
        raise ValueError(f"lines must be (L, sub, {_LANES}) with E dividing it")
    n = ridx.size
    tile = min(_TILE, -(-n // 8) * 8)
    n_pad = -(-n // tile) * tile
    flat = jnp.pad(
        ridx.reshape(-1).astype(jnp.int32), (0, n_pad - n), constant_values=-1
    )
    rows = pl.pallas_call(
        functools.partial(_gather_kernel, e=e, sub=sub, tile=tile),
        grid=(n_pad // tile,),
        in_specs=[
            pl.BlockSpec((tile,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pl.ANY),  # stays in HBM
        ],
        out_specs=pl.BlockSpec((tile, e), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, e), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((_INFLIGHT, sub, _LANES), lines.dtype),
            pltpu.VMEM((tile, _LANES), jnp.float32),
            pltpu.SemaphoreType.DMA((_INFLIGHT,)),
        ],
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(flat, lines)
    return rows[:n].reshape(*ridx.shape, e).sum(axis=-2)
