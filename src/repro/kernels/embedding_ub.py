"""GM-UB / L1-UB strategies: vectorized conflict-free lookup on the MXU.

Paper §II-B: "Performs vectorized look-up operations after moving the table
in chunks to the shared memory" — the Ascend vector unit retrieves multiple
rows in parallel from the Unified Buffer.

TPU adaptation (DESIGN.md §2): the TPU-native conflict-free multi-row lookup
is a *one-hot matmul*.  For a batch tile of queries we build per-chunk one-hot
count rows ``counts[r, q] = #{j : idx[q, j] == chunk_offset + r}`` and compute

    pooled_tile += table_chunkᵀ @ counts         (MXU, (E x Mc) @ (Mc x Bt))

which performs lookup *and* sum-pooling in one dense GEMM whose run time is
completely independent of the index values — reproducing (and strengthening)
the paper's query-distribution robustness claim.  Indices and outputs are
laid out batch-on-lanes (``(s, B)`` and ``(E, B)``): each lookup position is
a row read, and the output tile is lane-dense.

* GM-UB: the chunk grid dimension streams the table HBM→VMEM chunk by chunk
  (double-buffered by the pipeline).
* L1-UB: the whole table is pinned in VMEM (constant index_map, one buffer)
  and the kernel sweeps it in ``_SUB_ROWS`` sub-blocks, so the one-hot stays
  ``(_SUB_ROWS, Bt)`` however large the pinned table is.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import compat
from repro.kernels.embedding_l1 import check_pinnable

_SUB_ROWS = 512  # rows of the pinned L1-UB table one one-hot GEMM covers


def tdot(a: jax.Array, b: jax.Array) -> jax.Array:
    """``aᵀ @ b`` in f32 at full precision: (K, M), (K, N) -> (M, N).

    HIGHEST keeps the MXU's f32 passes exact for one-hot operands (a
    one-hot row times finite data is an exact row copy), so every kernel
    path matches the XLA gather reference bit for bit."""
    return jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _ub_kernel(idx_ref, table_ref, out_ref, *, sub: int, n_sub: int, seq: int):
    c = pl.program_id(1)
    bt = idx_ref.shape[1]
    iota = jax.lax.broadcasted_iota(jnp.int32, (sub, 1), 0)

    def sub_block(k, acc):
        base = (c * n_sub + k) * sub
        rows = table_ref[pl.ds(pl.multiple_of(k * sub, 8), sub), :]

        def cnt(j, counts):
            # one-hot over the chunk rows of lookup position j; summing the
            # positions gives the count matrix.
            hit = iota == idx_ref[pl.ds(j, 1), :] - base  # (sub, Bt)
            return counts + hit.astype(jnp.float32)

        counts = jax.lax.fori_loop(
            0, seq, cnt, jnp.zeros((sub, bt), jnp.float32)
        )
        return acc + tdot(rows.astype(jnp.float32), counts)

    partial = jax.lax.fori_loop(
        0, n_sub, sub_block,
        jnp.zeros((table_ref.shape[1], bt), jnp.float32),
    )

    @pl.when(c == 0)
    def _init():
        out_ref[...] = partial

    @pl.when(c > 0)
    def _acc():
        out_ref[...] += partial


def _align(n: int, mult: int) -> int:
    return -(-n // mult) * mult


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_m", "persistent", "interpret")
)
def embedding_bag_ub(
    table: jax.Array,
    indices: jax.Array,
    *,
    block_b: int = 256,
    block_m: int = 512,
    persistent: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """UB-strategy pooled lookup. table (m, E), indices (B, s) -> (B, E) f32.

    ``persistent=True`` (L1-UB) pins the whole table in VMEM; otherwise
    (GM-UB) the table streams through VMEM ``block_m`` rows at a time.
    """
    m, e = table.shape
    b, s = indices.shape
    # the batch is the lane axis: one tile holds the whole batch, or a
    # multiple of 128 queries.
    block_b = b if b <= block_b else max(128, block_b // 128 * 128)
    mp = _align(m, 8)
    if persistent:
        sub = min(_SUB_ROWS, mp)
        mp = _align(mp, sub)
        block_m, n_sub = mp, mp // sub
    else:
        block_m = min(_align(block_m, 8), mp)
        mp = _align(mp, block_m)
        sub, n_sub = block_m, 1

    pad_b = (-b) % block_b
    if mp > m:
        # zero rows: junk-free contributions for the final partial chunk.
        table = jnp.pad(table, ((0, mp - m), (0, 0)))
    if pad_b:
        # padded queries hit row 0 with count s; output rows discarded below.
        indices = jnp.pad(indices, ((0, pad_b), (0, 0)))
    bp = b + pad_b
    itemsize = table.dtype.itemsize

    if persistent:
        table_vmem = check_pinnable(mp, e, itemsize)
        table_spec = pl.BlockSpec(
            (block_m, e), lambda bi, c: (0, 0), pipeline_mode=pl.Buffered(1)
        )
    else:
        table_vmem = 2 * compat.vmem_bytes((block_m, e), itemsize)
        table_spec = pl.BlockSpec((block_m, e), lambda bi, c: (c, 0))
    vmem = (
        table_vmem
        + 2 * compat.vmem_bytes((s, block_b))  # index tile
        + 4 * compat.vmem_bytes((e, block_b))  # output tile + partial
        + 3 * compat.vmem_bytes((sub, block_b))  # one-hot + counts
    )
    kernel = functools.partial(_ub_kernel, sub=sub, n_sub=n_sub, seq=s)
    out = pl.pallas_call(
        kernel,
        grid=(bp // block_b, mp // block_m),
        in_specs=[
            pl.BlockSpec((s, block_b), lambda bi, c: (0, bi)),
            table_spec,
        ],
        out_specs=pl.BlockSpec((e, block_b), lambda bi, c: (0, bi)),
        out_shape=jax.ShapeDtypeStruct((e, bp), jnp.float32),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary"), vmem_bytes=vmem,
        ),
        interpret=interpret,
    )(indices.astype(jnp.int32).T, table)
    return out[:, :b].T
