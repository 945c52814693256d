"""L1 strategy: row gather from a persistently VMEM-pinned table.

Paper §II-B: the table is preloaded once into the core's fast scratchpad (1 MB
L1 on Ascend; VMEM on TPU) and every lookup is served from on-chip memory,
decoupling latency from the query distribution and saving HBM bandwidth for
the tables that cannot fit on-chip.

TPU realization: the table's BlockSpec pins the *whole* (padded) table in VMEM
(constant index_map, one buffer -> fetched once, reused across all grid
steps).  Indices arrive via scalar prefetch (SMEM) so the row addresses are
available to the scalar core for the dynamic VMEM row reads.  A pinned
``(m, 16)`` f32 table pads to 128 lanes in VMEM, so it takes 8x its logical
bytes (:func:`repro.compat.vmem_bytes`): that padded size is what the planner
budgets (``CostModel.fits_l1``) and the kernel asks the compiler for.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat

# largest pinned table (padded VMEM bytes) the L1 / L1-UB kernels accept;
# CostModel.fits_l1 budgets the planner against the same number.
PIN_VMEM_BYTES = 32 << 20


def check_pinnable(rows: int, dim: int, itemsize: int) -> int:
    """The pinned table's VMEM bytes; raises when it exceeds the kernels'
    cap, before the compiler refuses it."""
    need = compat.vmem_bytes((rows, dim), itemsize)
    if need > PIN_VMEM_BYTES:
        raise ValueError(
            f"a pinned ({rows}, {dim}) table takes {need:,} B of VMEM "
            f"(lane-padded), over the {PIN_VMEM_BYTES:,} B the L1 kernels "
            "may pin; the planner must not give this table an L1 strategy"
        )
    return need


def _l1_kernel(idx_ref, table_ref, out_ref, *, block_b: int, seq: int):
    bi = pl.program_id(0)

    def query(r, _):
        def lookup(j, acc):
            idx = idx_ref[(bi * block_b + r) * seq + j]
            return acc + table_ref[pl.ds(idx, 1), :].astype(jnp.float32)

        acc = jax.lax.fori_loop(
            0, seq, lookup, jnp.zeros((1, table_ref.shape[1]), jnp.float32)
        )
        out_ref[pl.ds(r, 1), :] = acc
        return _

    jax.lax.fori_loop(0, block_b, query, None)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def embedding_bag_l1(
    table: jax.Array,
    indices: jax.Array,
    *,
    block_b: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """L1-strategy pooled lookup. table (m, E), indices (B, s) -> (B, E) f32."""
    m, e = table.shape
    b, s = indices.shape
    pinned = check_pinnable(m, e, table.dtype.itemsize)
    block_b = min(block_b, b)
    pad_b = (-b) % block_b
    if pad_b:
        # padded queries look up row 0 and are discarded afterwards.
        indices = jnp.pad(indices, ((0, pad_b), (0, 0)))
    bp = b + pad_b
    flat_idx = indices.reshape(-1).astype(jnp.int32)

    kernel = functools.partial(_l1_kernel, block_b=block_b, seq=s)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bp // block_b,),
            in_specs=[
                # whole table pinned in VMEM for the kernel's lifetime; the
                # index never changes, so one buffer is enough.
                pl.BlockSpec(
                    (m, e), lambda bi, idx: (0, 0),
                    pipeline_mode=pl.Buffered(1),
                ),
            ],
            out_specs=pl.BlockSpec((block_b, e), lambda bi, idx: (bi, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((bp, e), jnp.float32),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary",),
            vmem_bytes=pinned + 2 * compat.vmem_bytes((block_b, e)),
        ),
        interpret=interpret,
    )(flat_idx, table)
    return out[:b]
