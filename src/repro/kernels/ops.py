"""Jit'd strategy dispatch for the embedding-lookup kernels.

``embedding_bag(table, indices, strategy)`` is the single entry point used by
the core library; the planner decides the strategy per table/chunk.  The
Pallas kernels compile on a TPU and run in interpret mode on the CPU test
backend (:func:`repro.compat.pallas_interpret`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import compat
from repro.core.strategies import Strategy
from repro.kernels import ref
from repro.kernels.embedding_gm import embedding_bag_gm
from repro.kernels.embedding_l1 import embedding_bag_l1
from repro.kernels.embedding_ub import embedding_bag_ub


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _bag_vjp(table, indices, strategy, interpret, block_b, block_m,
             tdtype_name, rows):
    return _bag_fwd_impl(table, indices, strategy, interpret, block_b, block_m)


def _bag_fwd_impl(table, indices, strategy, interpret, block_b, block_m):
    if strategy == Strategy.GM:
        return embedding_bag_gm(table, indices, interpret=interpret)
    if strategy == Strategy.L1:
        return embedding_bag_l1(table, indices, block_b=block_b, interpret=interpret)
    if strategy == Strategy.GM_UB:
        return embedding_bag_ub(
            table, indices, block_b=block_b, block_m=block_m,
            persistent=False, interpret=interpret,
        )
    if strategy == Strategy.L1_UB:
        return embedding_bag_ub(
            table, indices, block_b=block_b, persistent=True, interpret=interpret
        )
    raise ValueError(strategy)  # pragma: no cover


def _bag_fwd(table, indices, strategy, interpret, block_b, block_m,
             tdtype_name, rows):
    out = _bag_fwd_impl(table, indices, strategy, interpret, block_b, block_m)
    return out, indices


def _bag_bwd(strategy, interpret, block_b, block_m, tdtype_name, rows, res, g):
    # d table[r] = sum over (b, j) with idx[b,j]==r of g[b]  (scatter-add)
    indices = res
    b, s = indices.shape
    e = g.shape[-1]
    flat = indices.reshape(-1)
    gexp = jnp.repeat(g.astype(jnp.float32), s, axis=0)  # (B*s, E)
    dtable = jnp.zeros((rows, e), jnp.float32).at[flat].add(gexp)
    return dtable.astype(jnp.dtype(tdtype_name)), None


_bag_vjp.defvjp(_bag_fwd, _bag_bwd)


def embedding_bag(
    table: jax.Array,
    indices: jax.Array,
    strategy: Strategy | str | None = None,
    *,
    pooling: str = "sum",
    interpret: bool | None = None,
    block_b: int = 256,
    block_m: int = 512,
) -> jax.Array:
    """Pooled embedding lookup with an explicit data-flow strategy.

    Args:
      table: (m, E) embedding table (f32/bf16/f16).
      indices: (B, s) int32 lookup indices.
      strategy: one of Strategy.{GM, GM_UB, L1, L1_UB}; ``None`` uses the
        XLA-native gather (the vendor-compiler baseline data flow).
      pooling: "sum" (paper default) or "mean".
    Returns:
      (B, E) pooled embeddings, in the table dtype.
    """
    if strategy is None:
        return ref.embedding_bag_ref(table, indices, pooling=pooling)
    strategy = Strategy(strategy)
    if interpret is None:
        interpret = compat.pallas_interpret()

    # custom VJP: forward runs the Pallas strategy kernel, backward is the
    # standard scatter-add of pooled cotangents (trainable lookup layers).
    out = _bag_vjp(
        table, indices, strategy, interpret, block_b, block_m,
        table.dtype.name, table.shape[0],
    )

    if pooling == "mean":
        out = out / indices.shape[-1]
    elif pooling != "sum":
        raise ValueError(f"unknown pooling {pooling!r}")
    return out.astype(table.dtype)


def embedding_gather(
    table: jax.Array,
    indices: jax.Array,
    strategy: Strategy | str | None = None,
    **kw,
) -> jax.Array:
    """Pool-free row gather (s=1 bag): (m, E), (T,) -> (T, E).

    Used for LM token embeddings (the vocab-parallel / chunked case goes
    through core.partition which masks out-of-chunk rows).
    """
    if strategy is None:
        return ref.gather_ref(table, indices)
    return embedding_bag(table, indices[:, None], strategy, pooling="sum", **kw)


@functools.partial(jax.jit, static_argnames=("pooling",))
def chunk_bag(
    chunk: jax.Array,
    indices: jax.Array,
    row_offset: jax.Array,
    *,
    pooling: str = "sum",
) -> jax.Array:
    """Offset-subtract + clip + mask partial pooled lookup (paper §III-B).

    Differentiable and shard_map-friendly; the Pallas-strategy variants are
    selected above this level (the chunk is just a smaller table).
    """
    return ref.chunk_bag_ref(chunk, indices, row_offset, pooling=pooling)


def chunk_gather(
    chunk: jax.Array, indices: jax.Array, row_offset: jax.Array
) -> jax.Array:
    return ref.chunk_gather_ref(chunk, indices, row_offset)
