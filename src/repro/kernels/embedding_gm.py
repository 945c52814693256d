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
two dims, as the TPU's (8, 128) block rule requires.
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
