"""Fused multi-table (multi-slot) embedding-bag kernels.

The asymmetric executor's inner loop is "for each chunk slot: pooled lookup"
— per-slot kernel launches dominate for workloads with many small tables
(the paper's per-table launch overhead, §IV).  These kernels fuse the whole
slot sweep into ONE ``pallas_call``.

:func:`multi_embedding_bag_ragged` (default layout) is a **single streaming
pass** over the ragged packed buffer (core.partition ``layout="ragged"``):

* the host-side pack step emits a (slot, row-block, strategy) *step
  schedule* — one step per ``block_r`` rows of each chunk, grouped by the
  slot's data-flow strategy, so total grid work is proportional to ΣR_i,
  not slots x R_max;
* grid = (steps,) — the step dimension is the OUTER (and only) grid axis and
  the padded batch tile stays resident in VMEM, so each ``(block_r, E)`` row
  window of the buffer is DMA'd HBM→VMEM exactly **once per core** (not once
  per batch tile) via a scalar-prefetch-driven BlockSpec, double-buffered
  across steps by the pipeline;
* when ``B·E`` does not fit the VMEM budget the batch is chunked OUTSIDE the
  ``pallas_call`` (``lax.map`` over batch chunks); each chunk streams the
  buffer once, the minimum possible for that batch size;
* **strategy is a per-step dispatch**: UB-coded steps fold all ``s`` lookup
  positions into one conflict-free one-hot count GEMM on the MXU (run time
  independent of index values), GM/L1-coded steps pool row-at-a-time — one
  lookup position per accumulation pass — reproducing the paper's
  per-strategy data flow without any per-slot ``lax.switch``;
* **batch on lanes**: inside the kernel indices are ``(s, Bt)`` and the
  pooled output is ``(E, Bt)`` per slot, so a lookup position is one row
  read (never a dynamic column slice, which Mosaic cannot lower), one-hots
  are ``(block_r, Bt)`` and the output tile is lane-dense; the wrapper
  transposes in and out.  Every GEMM runs at HIGHEST precision, which keeps
  one-hot row copies exact on the MXU;
* out-of-window / invalid (``-1``) indices contribute exact zeros (no
  redirect row); consecutive steps of one slot accumulate into the same
  output block (``step_base == 0`` marks the first block and init-writes);
  schedule padding steps target a trash slot and init-write zeros there.

Access-reduction subsystem (DESIGN.md §6, both knobs off by default):

* **batch dedup** (``unique_cap > 0``): indices are unique-ized per slot at
  batch-prep time (sort + first-occurrence ranks, padded to the static
  ``unique_cap``); each step gathers every unique row in its window exactly
  once (one-hot ``(U, block_r) @ window`` GEMM) and scatters back to batch
  rows with the per-slot multiplicity matrix (``rows_uᵀ @ (U, B)`` GEMM) —
  per-lookup HBM row reads become per-unique-row reads.  Slots whose
  distinct-row count overflows ``unique_cap`` spill the overflow lookups to
  the cold row-at-a-time path in the same step (exact, just slower);
* **hot-row residency cache** (``cache is not None``): a ``(C, E)``
  mini-table of the core's top-access-mass rows rides a constant-index
  BlockSpec so it is DMA'd HBM→VMEM once and stays **pinned VMEM-resident
  across all steps**; lookups pre-split hot/cold by the packed remap table
  arrive as ``hidx`` cache positions and are resolved with a UB-style
  conflict-free one-hot GEMM against the resident cache on each slot's
  first step.

Kernel-path dispatch (``step_kpath``, DESIGN.md §11): the dedup'd unique-row
gather has two implementations sharing the uniq/cnt machinery —

* **onehot** (``kpath == 0``): materialize the ``(U, block_r)`` equality
  one-hot and gather via a GEMM on the MXU (dense in ``U·block_r``);
* **sparse** (``kpath == 1``): CSR-style true-sparse gather — ``uniq`` is
  already sorted ascending, so the unique rows inside a step's window are
  one contiguous run ``[lo, hi)`` (found by a binary search at batch-prep
  time); a loop over exactly that run copies each row out of the streamed
  ``(block_r, E)`` window into a ``(U, E)`` scratch, and the shared
  multiplicity GEMM (``rows_uᵀ @ cnt``) is the segment-sum scatter back to
  batch rows.  The run's scratch rows are zeroed again after the GEMM.

Both produce the same ``rows_u`` **bitwise** (a one-hot matvec against
finite data is an exact row copy: ``0·x + 1·row = row``), so the paths are
interchangeable per step; pack time emits the per-step choice from the cost
model's dense-vs-sparse crossover (``plan.meta["kernel"]``).

:func:`multi_embedding_bag_dense` is the legacy kernel over the dense
stacked-slot ``(S, R+1, E)`` layout, kept for layout comparison benchmarks
(no dedup/cache support — ragged only).

Output: (slots, B, E) pooled partials, scatter-added per table by the caller.
"""
from __future__ import annotations

import functools
import warnings

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import compat
from repro.kernels.embedding_ub import tdot

# padded-VMEM budget (bytes) for the resident batch tile, the streamed
# window and the kernel's temporaries; beyond it the batch is chunked
# outside the pallas_call (each chunk re-streams the buffer — unavoidable
# once the batch no longer fits on-chip).
_VMEM_BUDGET = 24 * 1024 * 1024


def _align8(n: int) -> int:
    return int(-(-n // 8) * 8)


def ragged_vmem_bytes(
    bb: int,
    seq: int,
    e: int,
    block_r: int,
    *,
    unique_cap: int = 0,
    cache_rows: int = 0,
) -> int:
    """Padded VMEM working set of one fused-kernel grid step with ``bb``
    resident batch rows (:func:`repro.compat.vmem_bytes` per buffer):
    double-buffered blocks plus the one-hot, count and partial temporaries.
    ``unique_cap`` adds the dedup blocks and the sparse-gather row scratch,
    ``cache_rows`` the hot-position tile, the pinned cache and its counts."""
    v = compat.vmem_bytes
    blocks = v((seq, bb)) + v((e, bb)) + v((block_r, e))  # idx, out, window
    work = 3 * v((block_r, bb)) + 3 * v((e, bb))  # one-hots, partials
    if unique_cap:
        blocks += v((unique_cap, 1)) + v((unique_cap, bb))  # uniq, cnt
        work += 2 * v((unique_cap, block_r))  # one-hot gather
        work += 3 * v((unique_cap, e))  # gathered rows (+ scratch)
    if cache_rows:
        blocks += v((seq, bb)) + v((cache_rows, e))  # hidx, cache
        work += 2 * v((cache_rows, bb))
    return 2 * blocks + work


def ragged_block_b(
    b: int,
    seq: int,
    e: int,
    block_r: int,
    *,
    block_b: int | None = None,
    vmem_budget: int = _VMEM_BUDGET,
    unique_cap: int = 0,
    cache_rows: int = 0,
) -> tuple[int, int]:
    """Resident batch-tile rows and resulting batch chunk count.

    Returns ``(block_b, n_chunks)``: the kernel keeps ``block_b`` batch rows
    resident in VMEM; ``n_chunks == 1`` means the whole (padded) batch is
    folded into the one-hot matmul and every buffer window streams once per
    core.  The auto pick is the whole batch when its padded working set
    (:func:`ragged_vmem_bytes`) fits ``vmem_budget``, else the largest
    multiple of 128 rows (one lane tile) that does.  Shared by the executor
    and the modeled-traffic accounting.
    """
    kw = dict(unique_cap=unique_cap, cache_rows=cache_rows)
    if block_b is None:
        block_b = _align8(b)
        if ragged_vmem_bytes(block_b, seq, e, block_r, **kw) > vmem_budget:
            # the working set is affine in bb over whole 128-lane tiles
            fixed = ragged_vmem_bytes(0, seq, e, block_r, **kw)
            per_tile = ragged_vmem_bytes(128, seq, e, block_r, **kw) - fixed
            block_b = 128 * max(1, (vmem_budget - fixed) // per_tile)
    block_b = min(block_b, _align8(b))
    block_b = max(8, (block_b // 8) * 8)
    n_chunks = -(-b // block_b)
    return block_b, n_chunks


# --------------------------------------------------------------------------
# ragged layout: single streaming pass, per-step strategy dispatch
# --------------------------------------------------------------------------


def _ragged_kernel(
    slot_ref, base_ref, blk_ref, strat_ref, *refs,
    block_r: int, seq: int, unique_cap: int, cache_rows: int,
    use_kpath: bool = False,
):
    del blk_ref  # consumed by the index_maps
    t = pl.program_id(0)
    base = base_ref[t]
    strat = strat_ref[t]
    refs = list(refs)
    if unique_cap or cache_rows:
        # per-step work flags (bit 0: slot has spill, bit 1: slot has
        # cache hits) — lets the kernel skip guaranteed-zero loops.
        flags = refs.pop(0)[t]
    if use_kpath:
        # gather-path selector, the step's in-window run [lo, hi) of the
        # slot's sorted unique ids, and those ids flattened (S+1)*U
        kpath = refs.pop(0)[t]
        lo = refs.pop(0)[t]
        hi = refs.pop(0)[t]
        uniq_flat_ref = refs.pop(0)
    idx_ref = refs.pop(0)  # (1, s, Bt): full lidx, or the dedup spill
    uniq_ref = refs.pop(0) if unique_cap else None  # (1, U, 1)
    cnt_ref = refs.pop(0) if unique_cap else None  # (1, U, Bt)
    hidx_ref = refs.pop(0) if cache_rows else None  # (1, s, Bt)
    cache_ref = refs.pop(0) if cache_rows else None  # (C, E)
    window_ref, out_ref = refs[:2]
    rows_ref = refs[2] if use_kpath else None  # (U, E) scratch
    bt = idx_ref.shape[-1]
    e = window_ref.shape[1]
    window = window_ref[...].astype(jnp.float32)
    iota = jax.lax.broadcasted_iota(jnp.int32, (block_r, 1), 0)

    def onehot(j):
        # (block_r, Bt): lookup position j of every batch row against the
        # window's rows; -1 / out-of-window indices match nothing.
        return (iota == idx_ref[0, pl.ds(j, 1), :] - base).astype(jnp.float32)

    def _ub_onehot():
        # UB: fold every lookup position into ONE count matrix, then a single
        # conflict-free GEMM on the MXU — run time independent of the index
        # values (the paper's vectorized UB look-up).
        counts = jax.lax.fori_loop(
            0, seq, lambda j, c: c + onehot(j),
            jnp.zeros((block_r, bt), jnp.float32),
        )
        return tdot(window, counts)

    def _gm_rowstream():
        # GM/L1: row-at-a-time pooling — one lookup position per pass through
        # the accumulation buffer (the paper's "read one row at a time ...
        # followed by pooling this row in an accumulation buffer").
        return jax.lax.fori_loop(
            0, seq, lambda j, acc: acc + tdot(window, onehot(j)),
            jnp.zeros((e, bt), jnp.float32),
        )

    if unique_cap:
        # dedup'd path (all strategies): gather each unique row in this
        # window exactly ONCE, then scatter the pooled rows back to batch
        # positions with the multiplicity matrix — per-unique-row reads
        # instead of per-lookup reads, conflict-free by construction.
        # idx_ref carries only the unique_cap overflow spill, row-streamed
        # cold alongside — but only on slots whose flag says something
        # actually spilled (the common case skips the dead loop).
        def scatter(rows_u):
            # (U, E) unique rows -> (E, Bt) pooled partial; one GEMM shared
            # by both gather paths, so they agree bit for bit.
            return tdot(rows_u, cnt_ref[0])

        def _partial_onehot():
            # dense gather: (U, block_r) equality one-hot on the MXU
            iota_r = jax.lax.broadcasted_iota(jnp.int32, (1, block_r), 1)
            equ = (uniq_ref[0] - base == iota_r).astype(jnp.float32)
            return scatter(jnp.dot(
                equ, window,
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32,
            ))

        def _partial_sparse():
            # true-sparse gather: copy just the window's unique rows
            # [lo, hi) — no (block_r, U) one-hot.  Bit-identical to the
            # one-hot path: a one-hot matvec against finite data IS an exact
            # row copy, and rows outside the run stay zero.
            first = slot_ref[t] * unique_cap

            def copy(u, c):
                r = uniq_flat_ref[first + u] - base
                rows_ref[pl.ds(u, 1), :] = window_ref[pl.ds(r, 1), :].astype(
                    jnp.float32
                )
                return c

            def clear(u, c):
                rows_ref[pl.ds(u, 1), :] = jnp.zeros((1, e), jnp.float32)
                return c

            jax.lax.fori_loop(lo, hi, copy, 0)
            out = scatter(rows_ref[...])
            jax.lax.fori_loop(lo, hi, clear, 0)
            return out

        if use_kpath:
            @pl.when(t == 0)
            def _zero_rows():
                rows_ref[...] = jnp.zeros(rows_ref.shape, jnp.float32)

            partial = jax.lax.cond(kpath == 1, _partial_sparse, _partial_onehot)
        else:
            partial = _partial_onehot()
        partial += jax.lax.cond(
            (flags & 1) > 0,
            _gm_rowstream,
            lambda: jnp.zeros((e, bt), jnp.float32),
        )
    else:
        # UB strategies (GM-UB=1, L1-UB=3) use the vectorized one-hot path.
        is_ub = (strat == 1) | (strat == 3)
        partial = jax.lax.cond(is_ub, _ub_onehot, _gm_rowstream)

    @pl.when(base == 0)
    def _init():
        out = partial
        if cache_rows:
            # hot lookups resolve against the pinned resident cache with a
            # UB-style one-hot GEMM, folded in once on the slot's first
            # step — skipped outright on slots with no cached rows.
            def _hot_fold():
                iota_c = jax.lax.broadcasted_iota(
                    jnp.int32, (cache_rows, 1), 0
                )

                def hcnt(j, c):
                    hit = iota_c == hidx_ref[0, pl.ds(j, 1), :]
                    return c + hit.astype(jnp.float32)

                counts_h = jax.lax.fori_loop(
                    0, seq, hcnt, jnp.zeros((cache_rows, bt), jnp.float32)
                )
                return tdot(cache_ref[...].astype(jnp.float32), counts_h)

            out = out + jax.lax.cond(
                (flags & 2) > 0,
                _hot_fold,
                lambda: jnp.zeros((e, bt), jnp.float32),
            )
        out_ref[0] = out

    @pl.when(base > 0)
    def _acc():
        out_ref[0] += partial


def _dedup_indices(
    lidx: jax.Array, unique_cap: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Batch-prep unique-ization of (S, B, s) chunk-local indices.

    Per slot, over all ``B·s`` lookup positions: sort, rank values by first
    occurrence, and emit

    * ``uniq``  (S, U)    — the first ``unique_cap`` distinct local ids
      (``-1`` padding),
    * ``cnt``   (S, B, U) — per-batch-row multiplicity of each unique id
      (the scatter/segment-sum matrix),
    * ``spill`` (S, B, s) — lookups whose id overflowed ``unique_cap``
      (kept verbatim for the cold row-stream path; ``-1`` elsewhere).

    ``-1`` padding indices never enter the unique set.  Exactness does not
    depend on the cap: every lookup lands in exactly one of ``cnt``/``spill``.
    """
    _, b, seq = lidx.shape
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    rows_of = jnp.arange(b * seq, dtype=jnp.int32) // seq

    def one(l: jax.Array):
        flat = l.reshape(-1)
        key = jnp.where(flat < 0, big, flat)
        order = jnp.argsort(key)
        sv = key[order]
        valid = sv < big
        first = jnp.concatenate([valid[:1], (sv[1:] != sv[:-1]) & valid[1:]])
        rank = jnp.cumsum(first.astype(jnp.int32)) - 1
        rank = jnp.where(valid, rank, unique_cap)
        # unique table: first occurrences below the cap write their value,
        # everything else lands on the dropped trash entry (always -1).
        in_cap = first & (rank < unique_cap)
        uniq = jnp.full((unique_cap + 1,), -1, jnp.int32)
        uniq = uniq.at[jnp.where(in_cap, rank, unique_cap)].set(
            jnp.where(in_cap, sv, -1).astype(jnp.int32)
        )[:unique_cap]
        # per-position rank in original order -> multiplicity scatter
        pos_rank = jnp.zeros_like(flat).at[order].set(rank)
        cnt = (
            jnp.zeros((b, unique_cap + 1), jnp.float32)
            .at[rows_of, jnp.minimum(pos_rank, unique_cap)]
            .add(jnp.where(pos_rank < unique_cap, 1.0, 0.0))[:, :unique_cap]
        )
        spill = jnp.where(
            (pos_rank >= unique_cap) & (flat >= 0), flat, -1
        ).reshape(b, seq)
        return uniq, cnt, spill

    return jax.vmap(one)(lidx)


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_r", "block_b", "vmem_budget", "interpret", "unique_cap",
    ),
)
def multi_embedding_bag_ragged(
    buffer: jax.Array,  # (T, E) ragged packed buffer, T % block_r == 0
    lidx: jax.Array,  # (S, B, s) int32 chunk-local indices, -1 = skip
    step_slot: jax.Array,  # (n_steps,) int32, S = trash slot (padding step)
    step_base: jax.Array,  # (n_steps,) int32 chunk-local block base row
    step_block: jax.Array,  # (n_steps,) int32 buffer row-block index
    step_strategy: jax.Array,  # (n_steps,) int32 strategy code of the step
    *,
    block_r: int,
    block_b: int | None = None,
    vmem_budget: int = _VMEM_BUDGET,
    interpret: bool = False,
    unique_cap: int = 0,  # > 0 arms batch dedup (static cap per slot)
    cache: jax.Array | None = None,  # (C, E) resident hot-row mini-table
    hidx: jax.Array | None = None,  # (S, B, s) int32 cache positions, -1 miss
    step_kpath: jax.Array | None = None,  # (n_steps,) 0=onehot 1=sparse
) -> jax.Array:
    """All slots' pooled lookups in one streaming pass -> (S, B, E) f32.

    ``unique_cap``/``cache``+``hidx`` arm the access-reduction subsystem
    (module docstring); with both off this is exactly the PR3 kernel.
    Callers must have already removed cache-hit lookups from ``lidx``
    (set to ``-1``) wherever ``hidx >= 0`` — the packed remap does this.
    ``step_kpath`` selects the unique-row gather implementation per step
    (0 = one-hot GEMM, 1 = true-sparse row gather) — dedup only, bitwise
    interchangeable (module docstring).
    """
    t_rows, e = buffer.shape
    s_slots, b, seq = lidx.shape
    n_steps = step_slot.shape[0]
    if t_rows % block_r:
        raise ValueError("buffer rows must be a multiple of block_r")
    if step_kpath is not None and not unique_cap:
        raise ValueError(
            "step_kpath (sparse kernel path) requires unique_cap > 0: the "
            "sparse gather rides the dedup uniq/cnt machinery"
        )
    cache_rows = 0 if cache is None else int(cache.shape[0])
    if cache_rows and hidx is None:
        raise ValueError("cache requires the hidx hot-position tensor")
    bb, n_chunks = ragged_block_b(
        b, seq, e, block_r, block_b=block_b, vmem_budget=vmem_budget,
        unique_cap=unique_cap, cache_rows=cache_rows,
    )
    pad_b = n_chunks * bb - b
    # trash slot S absorbs schedule padding steps; its indices never match.
    lidx = jnp.pad(lidx, ((0, 1), (0, pad_b), (0, 0)), constant_values=-1)
    if cache_rows:
        hidx = jnp.pad(hidx, ((0, 1), (0, pad_b), (0, 0)), constant_values=-1)
    uniq = cnt = None
    if unique_cap:
        # batch-prep dedup over the padded batch: lidx becomes the overflow
        # spill (usually all -1), uniq/cnt drive the gather/scatter GEMMs.
        uniq, cnt, lidx = _dedup_indices(lidx, unique_cap)

    use_kpath = step_kpath is not None
    kernel = functools.partial(
        _ragged_kernel, block_r=block_r, seq=seq,
        unique_cap=unique_cap, cache_rows=cache_rows, use_kpath=use_kpath,
    )
    step_slot = step_slot.astype(jnp.int32)
    step_base = step_base.astype(jnp.int32)
    prefetch = [
        step_slot,
        step_base,
        step_block.astype(jnp.int32),
        step_strategy.astype(jnp.int32),
    ]
    if unique_cap or cache_rows:
        # per-step work flags: bit 0 = the step's slot has overflow spill,
        # bit 1 = it has cache hits — the kernel skips guaranteed-zero loops.
        spill_any = (
            (lidx >= 0).any(axis=(1, 2)) if unique_cap
            else jnp.zeros(s_slots + 1, bool)
        )
        hot_any = (
            (hidx >= 0).any(axis=(1, 2)) if cache_rows
            else jnp.zeros(s_slots + 1, bool)
        )
        slot_flags = spill_any.astype(jnp.int32) + 2 * hot_any.astype(
            jnp.int32
        )
        prefetch.append(jnp.take(slot_flags, step_slot))
    if use_kpath:
        # per-step gather-path selector plus, for the sparse gather, each
        # step's run [lo, hi) of in-window ids in its slot's sorted unique
        # list (pads, -1, sort last) and the unique ids themselves.
        key = jnp.where(uniq < 0, jnp.iinfo(jnp.int32).max, uniq)

        def run_bounds(first_row):
            per_slot = jax.vmap(
                lambda k: jnp.searchsorted(k, first_row).astype(jnp.int32)
            )(key)  # (S+1, n_steps)
            return per_slot[step_slot, jnp.arange(n_steps)]

        prefetch += [
            step_kpath.astype(jnp.int32),
            run_bounds(step_base),
            run_bounds(step_base + block_r),
            uniq.reshape(-1),
        ]

    # the step's slot-indexed batch tiles are resident across the slot's
    # (consecutive) steps — refetched only on slot change; the (block_r, E)
    # buffer window is streamed HBM->VMEM exactly once per core, double-
    # buffered across steps by the pipeline; the cache block's constant
    # index_map pins it VMEM-resident for the whole grid.  The index_maps
    # take (t, *prefetch_refs) — variadic since the flags prefetch is only
    # present when the access-reduction subsystem is armed.
    slot_block = lambda t, ss, *_: (ss[t], 0, 0)  # noqa: E731
    in_specs = [pl.BlockSpec((1, seq, bb), slot_block)]
    if unique_cap:
        in_specs += [
            pl.BlockSpec((1, unique_cap, 1), slot_block),
            pl.BlockSpec((1, unique_cap, bb), slot_block),
        ]
    if cache_rows:
        in_specs += [
            pl.BlockSpec((1, seq, bb), slot_block),
            pl.BlockSpec((cache_rows, e), lambda t, ss, *_: (0, 0)),
        ]
    in_specs.append(
        pl.BlockSpec((block_r, e), lambda t, ss, sb, sk, *_: (sk[t], 0))
    )
    scratch = [pltpu.VMEM((unique_cap, e), jnp.float32)] if use_kpath else []
    vmem = ragged_vmem_bytes(
        bb, seq, e, block_r, unique_cap=unique_cap, cache_rows=cache_rows
    )

    def one_pass(tiles: dict) -> jax.Array:
        """Per-batch-chunk resident tiles -> (S+1, E, bb) pooled."""
        inputs = [tiles["lidx"]]
        if unique_cap:
            inputs += [uniq[:, :, None], tiles["cnt"]]
        if cache_rows:
            inputs += [tiles["hidx"], cache]
        inputs.append(buffer)
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(prefetch),
                grid=(n_steps,),
                in_specs=in_specs,
                out_specs=pl.BlockSpec((1, e, bb), slot_block),
                scratch_shapes=scratch,
            ),
            out_shape=jax.ShapeDtypeStruct((s_slots + 1, e, bb), jnp.float32),
            compiler_params=compat.tpu_compiler_params(
                dimension_semantics=("arbitrary",), vmem_bytes=vmem,
            ),
            interpret=interpret,
        )(*prefetch, *inputs)

    # batch on lanes: (S+1, B, ...) -> (S+1, ..., B)
    tiles = {"lidx": lidx.swapaxes(1, 2)}
    if unique_cap:
        tiles["cnt"] = cnt.swapaxes(1, 2)
    if cache_rows:
        tiles["hidx"] = hidx.swapaxes(1, 2)
    if n_chunks == 1:
        out = one_pass(tiles)
    else:
        # batch exceeds the VMEM budget: chunk it OUTSIDE the pallas_call;
        # each chunk is one full streaming pass over the buffer (the unique
        # table and the resident cache are chunk-invariant and ride along).
        def split(x):  # (S+1, d, n_chunks*bb) -> (n_chunks, S+1, d, bb)
            s1, d, _ = x.shape
            return x.reshape(s1, d, n_chunks, bb).transpose(2, 0, 1, 3)

        out = jax.lax.map(
            one_pass, {k: split(v) for k, v in tiles.items()}
        )  # (n_chunks, S+1, E, bb)
        out = out.transpose(1, 2, 0, 3).reshape(s_slots + 1, e, n_chunks * bb)
    return out[:s_slots, :, :b].swapaxes(1, 2)


# --------------------------------------------------------------------------
# dense stacked-slot layout (legacy, kept for layout comparisons)
# --------------------------------------------------------------------------


def _dense_kernel(idx_ref, chunk_ref, out_ref, *, block_b: int, seq: int, batch: int):
    si = pl.program_id(0)
    bi = pl.program_id(1)

    def query(r, _):
        def lookup(j, acc):
            idx = idx_ref[(si * batch + bi * block_b + r) * seq + j]
            row = chunk_ref[0, pl.ds(idx, 1), :]  # (1, E) of the (R+1, E) chunk
            return acc + row.astype(jnp.float32)

        acc = jax.lax.fori_loop(
            0, seq, lookup, jnp.zeros((1, chunk_ref.shape[-1]), jnp.float32)
        )
        out_ref[0, pl.ds(r, 1), :] = acc
        return _

    jax.lax.fori_loop(0, block_b, query, None)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def multi_embedding_bag_dense(
    chunks: jax.Array,  # (S, R+1, E) — slot chunk stack, trailing zero row
    lidx: jax.Array,  # (S, B, s) int32, pre-clipped local indices
    *,
    block_b: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """All slots' pooled lookups in one pallas_call -> (S, B, E) f32."""
    s_slots, rpad, e = chunks.shape
    _, b, seq = lidx.shape
    block_b = min(block_b, b)
    pad_b = (-b) % block_b
    if pad_b:
        lidx = jnp.pad(lidx, ((0, 0), (0, pad_b), (0, 0)))
    bp = b + pad_b
    flat_idx = lidx.reshape(-1).astype(jnp.int32)

    kernel = functools.partial(
        _dense_kernel, block_b=block_b, seq=seq, batch=bp
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(s_slots, bp // block_b),
            in_specs=[
                # slot chunk: fetched per slot, resident across batch tiles
                pl.BlockSpec((1, rpad, e), lambda si, bi, idx: (si, 0, 0)),
            ],
            out_specs=pl.BlockSpec(
                (1, block_b, e), lambda si, bi, idx: (si, bi, 0)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((s_slots, bp, e), jnp.float32),
        compiler_params=compat.tpu_compiler_params(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(flat_idx, chunks)
    return out[:, :b]


def multi_embedding_bag(*args, **kwargs):
    """Deprecated alias — now the RAGGED streaming entry point.

    ``multi_embedding_bag`` used to name the dense stacked-slot kernel; the
    ragged single-pass kernel is the default executor path.  Call
    :func:`multi_embedding_bag_ragged` (or ``_dense`` for the legacy layout)
    directly.
    """
    warnings.warn(
        "multi_embedding_bag now points at multi_embedding_bag_ragged (the "
        "single-pass streaming kernel); call multi_embedding_bag_ragged "
        "directly, or multi_embedding_bag_dense for the legacy dense layout.",
        DeprecationWarning,
        stacklevel=2,
    )
    return multi_embedding_bag_ragged(*args, **kwargs)
