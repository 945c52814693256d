"""SPMD execution of a placement :class:`Plan` (paper §III-B on a TPU mesh).

The paper places table *chunks* in individual cores' L1 buffers, subtracts the
chunk offset from the indices, clips them to avoid out-of-bounds accesses, and
combines partial pools with atomic inter-core accumulation.  The TPU-native
rendering (DESIGN.md §3–§4, the single-pass streaming executor):

* the per-core chunk inventory is materialized as a *ragged packed buffer*
  ``(K, R_total+1, E)`` sharded over the ``"model"`` mesh axis — every device
  holds its own (different!) chunks concatenated row-wise, plus small int32
  per-slot metadata (``slot_row_start``, ``slot_rows``, …): the asymmetric
  layout.  Memory is ``K·(ΣR_i)·E`` instead of the dense stacked-slot layout's
  ``K·S·R_max·E`` (the dense layout is kept as ``layout="dense"`` for
  comparison benchmarks);
* pack time emits a per-strategy **step schedule** (``step_slot``/
  ``step_base``/``step_block``/``step_strategy``): one step per ``block_r``
  rows of each chunk, grouped by the slot's data-flow strategy.  The default
  executor (``use_kernels="fused"``) runs ONE streaming ``pallas_call`` over
  that schedule — strategy is a per-step dispatch inside the kernel, and
  each buffer window is DMA'd HBM→VMEM once per core
  (``kernels/embedding_multi.py``).  The legacy per-slot ``lax.scan`` over
  max-alloc windows is retired (``use_kernels=True`` warns and routes here);
* inter-core accumulation is **owner-sharded** by default
  (``reduce_mode="sparse"``): each asymmetric table has one owner core; cores
  exchange only the owned-slot partial rows they actually hold
  (``lax.all_to_all``), owners sum them, and an ``all_gather`` of the owned
  buckets rebuilds the replicated output — collective volume is proportional
  to the placed slots, not K·N·B·E.  ``reduce_mode="psum"`` (the paper's
  atomic accumulation) and ``"ring"`` are kept;
* the LIF symmetric fallback group executes batch-split over the same axis and
  rejoins with an ``all_gather``; each symmetric table's kernel is fixed at
  pack time (``PackedPlan.sym_static``), so only the kernel its strategy
  names is compiled for it.

Each chunk's region in the ragged buffer is padded to a ``block_r`` multiple
with at least one zero row after the data, and the buffer carries one shared
trailing zero row; all invalid lookups (out-of-chunk, sequence padding ``-1``,
empty slots, other replicas' batch rows) are redirected to a zero row (XLA
path) or contribute exact zeros in-kernel (fused path), so no post-hoc
masking of the pooled result is needed.

The ``use_kernels`` / ``reduce_mode`` contract (single source of truth —
``partitioned_lookup``, ``PartitionedEmbeddingBag.apply``,
``forward_packed``, and the serve CLI all forward here):

* ``use_kernels="fused"`` (default) — ONE schedule-driven streaming
  ``pallas_call`` for the whole asymmetric sweep;
* ``use_kernels=False`` — the XLA gather path: identical math, no Pallas
  (the CPU-fast correctness oracle);
* ``use_kernels=True`` — deprecated spelling of the retired per-slot scan:
  warns and routes to ``"fused"``;
* ``reduce_mode`` ∈ {``"sparse"`` (default owner-sharded all_to_all +
  all_gather rejoin), ``"psum"`` (the paper's atomic accumulation),
  ``"ring"`` (collective-permute pipelined accumulation)} — all three are
  parity-identical; they differ only in collective volume/overlap.

``plan.meta`` key reference (every producer annotates the Plan it returns or
packs; all values are JSON-able):

* ``planner``      — planner name + option tags (``"asymmetric+lpt+freq"``);
* ``lif``/``fell_back`` — load-imbalance factor of the greedy load vector
  and whether the symmetric LIF fallback engaged (asymmetric planner);
* ``l1_left``      — remaining symmetric L1 budget (symmetric planner);
* ``distribution`` — per-table access-histogram summaries the plan was
  priced under (``None`` = the uniform assumption; see
  ``repro.core.planner._distribution_meta`` and DESIGN.md §5);
* ``kernel``       — the kernel-path (dense-vs-sparse gather) record
  (DESIGN.md §11), written by ``plan_asymmetric(kernel_path=)``: ``path``
  (the requested mode ``auto|onehot|sparse``), ``dedup_armed``,
  ``per_chunk`` (one record per assignment: the chosen path + modeled
  per-path microseconds), ``n_sparse``/``n_onehot``; extended by
  :func:`pack_plan` with ``packed`` (the realized schedule: resolved
  ``path``, ``sparse_chunks``/``onehot_chunks``, ``sparse_steps``);
* ``cache``        — the access-reduction subsystem record (DESIGN.md §6),
  written by ``plan_asymmetric(dedup=/cache=)`` and extended by
  :func:`pack_plan`: ``dedup`` (bool), ``unique_cap`` (static per-slot
  dedup width), ``cache_rows`` (residency-cache row budget),
  ``cache_target``/``coverage`` (requested / modeled hit fraction), and
  ``packed`` (written by :func:`pack_plan`: the realized per-core carve —
  ``cache_rows`` after padding, ``rows_per_core``);
* ``layout``       — written by :func:`pack_plan`: ``kind``,
  ``chunk_bytes``/``dense_bytes``/``bytes_vs_dense``, ``block_r``/
  ``block_b``, ``slot_window``, ``n_steps``/``n_padding_steps``,
  ``padding_frac``;
* ``rejoin``       — written by :func:`pack_plan`: ``n_owned_max``,
  ``n_send_max``, ``owned_per_core`` (owner-sharded rejoin shape);
* ``tuning``       — written by ``repro.core.autotune.autotune_block_sizes``
  (via ``bag.pack(autotune=True)`` / ``--autotune``): the full
  ``candidates`` sweep, the ``best`` pick, ``backend``/``compiled``/
  ``iters``.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import compat
from repro.core.cost_model import freq_of
from repro.core.strategies import Plan, Strategy
from repro.core.tables import TableSpec
from repro.kernels.embedding_gm import embedding_bag_gm
from repro.kernels.embedding_l1 import embedding_bag_l1
from repro.kernels.embedding_ub import embedding_bag_ub

__all__ = [
    "STRATEGY_CODE",
    "PackedPlan",
    "cache_plan_entries",
    "pack_plan",
    "partitioned_lookup",
    "place_packed",
    "vocab_parallel_embed",
]

STRATEGY_CODE: dict[Strategy, int] = {
    Strategy.GM: 0,
    Strategy.GM_UB: 1,
    Strategy.L1: 2,
    Strategy.L1_UB: 3,
}
_CODE_STRATEGY = {v: k for k, v in STRATEGY_CODE.items()}

_ROW_PAD = 8  # sublane-friendly row padding
# ragged fused-kernel row-block candidates, largest first.  Bigger blocks
# mean fewer steps, and the step schedule is scalar-prefetched into the
# core's SMEM (1 MiB on v5e); smaller ones waste fewer padding rows.
_RAGGED_BLOCK_RS = (512, 256, 128, 64)
_RAGGED_PAD_SLACK = 1.125  # padded rows allowed per data row (incl. zero row)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedPlan:
    """Array-ified Plan. ``chunk_data``/slot metadata are sharded over the
    core axis; symmetric tables and the rejoin maps are replicated (small by
    construction).

    ``layout="ragged"`` (default): ``chunk_data`` is ``(K, R_total+1, E)``
    with each core's chunks concatenated row-wise (``slot_row_start`` gives
    each slot's first row) and the ``step_*`` arrays hold the fused kernel's
    per-core (slot, row-block, strategy) schedule.  ``layout="dense"`` keeps
    the legacy stacked-slot ``(K, S, R_max+1, E)`` form (no ``step_*``
    schedule).  The ``rejoin_*`` maps drive the owner-sharded sparse rejoin:
    ``rejoin_send[c, d]`` lists the tables core ``c`` sends to owner ``d``,
    ``rejoin_bucket[d]`` lists the tables core ``d`` owns, and
    ``rejoin_owned_pos[t]`` is table ``t``'s position in its owner's bucket.
    """

    # asymmetric slots
    chunk_data: Any  # ragged: (K, R_total+1, E); dense: (K, S, R+1, E)
    slot_table: Any  # (K, S) int32, -1 = empty
    slot_offset: Any  # (K, S) int32 row offset within the source table
    slot_rows: Any  # (K, S) int32
    slot_row_start: Any  # (K, S) int32 first row in the ragged buffer
    slot_strategy: Any  # (K, S) int32
    slot_rep: Any  # (K, S) int32
    slot_nrep: Any  # (K, S) int32
    # fused-kernel step schedule (ragged layout only; (K, 0) otherwise)
    step_slot: Any  # (K, T) int32 slot id per step (S = trash slot)
    step_base: Any  # (K, T) int32 chunk-local first row of the step's block
    step_block: Any  # (K, T) int32 row-block index into the ragged buffer
    step_strategy: Any  # (K, T) int32 strategy code of the step's slot
    step_kpath: Any  # (K, T) int32 gather path per step (0 onehot, 1 sparse)
    # owner-sharded sparse rejoin maps (replicated)
    rejoin_send: Any  # (K, K, n_send) int32 table ids, -1 = none
    rejoin_owned_pos: Any  # (N,) int32 bucket position at the owner, -1
    rejoin_bucket: Any  # (K, O) int32 owned table ids, -1 pad
    # symmetric fallback group (replicated)
    sym_data: Any  # (Nsym, Msym+1, E); the tables are in sym_static
    # hot-row residency cache (ragged layout; zero-sized when off)
    cache_data: Any = None  # (K, C, E) per-core resident hot-row mini-table
    cache_remap: Any = None  # (K, T+1) int32 buffer row -> cache pos, -1 cold
    # static layout descriptors (pytree aux data)
    layout: str = "ragged"
    block_r: int = 0  # fused-kernel row-block size (ragged)
    slot_window: int = 0  # largest per-slot block_r allocation (informational)
    block_b: int = 0  # fused-kernel resident batch rows; 0 = auto
    unique_cap: int = 0  # batch-dedup width per slot; 0 = dedup off
    cache_rows: int = 0  # padded residency-cache rows; 0 = cache off
    kernel_path: str = "onehot"  # resolved gather mode; "onehot" = no sparse
    # per symmetric table (table id, rows, strategy code), static so each
    # table compiles only its own strategy's kernel
    sym_static: tuple = ()

    _ARRAY_FIELDS = (
        "chunk_data", "slot_table", "slot_offset", "slot_rows",
        "slot_row_start", "slot_strategy", "slot_rep", "slot_nrep",
        "step_slot", "step_base", "step_block", "step_strategy",
        "step_kpath",
        "rejoin_send", "rejoin_owned_pos", "rejoin_bucket",
        "sym_data", "cache_data", "cache_remap",
    )
    # replicated across the core axis (everything else is core-sharded)
    _REPLICATED_FIELDS = (
        "rejoin_send", "rejoin_owned_pos", "rejoin_bucket", "sym_data",
    )

    def tree_flatten(self):
        children = tuple(getattr(self, f) for f in self._ARRAY_FIELDS)
        aux = (
            self.layout, self.block_r, self.slot_window, self.block_b,
            self.unique_cap, self.cache_rows, self.kernel_path,
            self.sym_static,
        )
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)

    def strip_core(self, core) -> "PackedPlan":
        """Select one core's slice of every core-sharded field (replicated
        fields pass through) — the view each shard_map program executes on."""
        return dataclasses.replace(
            self,
            **{
                f: getattr(self, f)[core]
                for f in self._ARRAY_FIELDS
                if f not in self._REPLICATED_FIELDS
            },
        )

    @property
    def n_cores(self) -> int:
        return self.chunk_data.shape[0]

    @property
    def chunk_bytes(self) -> int:
        return int(np.prod(self.chunk_data.shape)) * self.chunk_data.dtype.itemsize


def _align(n: int, mult: int) -> int:
    return int(-(-n // mult) * mult)


def _default_block_r(rows: Sequence[int]) -> int:
    """Largest row-block whose per-chunk padding (each chunk plus its zero
    row rounded up to a block) keeps the buffer within
    ``_RAGGED_PAD_SLACK`` of its data rows."""
    data = sum(r + 1 for r in rows)
    for br in _RAGGED_BLOCK_RS[:-1]:
        if sum(_align(r + 1, br) for r in rows) <= _RAGGED_PAD_SLACK * data:
            return br
    return _RAGGED_BLOCK_RS[-1]


def place_packed(
    packed: "PackedPlan", mesh: jax.sharding.Mesh, axis: str = "model"
) -> "PackedPlan":
    """Put each packed array where :func:`partitioned_lookup` runs it:
    core-sharded fields split over the mesh's ``axis`` (core ``c``'s slice
    on the ``c``-th device), replicated fields on every device."""
    pspec = jax.sharding.PartitionSpec

    def put(f):
        spec = pspec() if f in PackedPlan._REPLICATED_FIELDS else pspec(axis)
        return jax.device_put(
            getattr(packed, f), jax.sharding.NamedSharding(mesh, spec)
        )

    return dataclasses.replace(
        packed,
        **{
            f: put(f)
            for f in PackedPlan._ARRAY_FIELDS
            if getattr(packed, f) is not None
        },
    )


def _rejoin_maps(
    plan: Plan, n_tables: int, k: int, mesh_shape: tuple[int, int] | None = None
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Owner-sharded rejoin maps: (owner, bucket_table, owned_pos, send_table).

    Each asymmetric table is owned by the core holding most of its rows (ties
    break to the lowest core id); ``send_table[c, d]`` lists the tables core
    ``c`` holds partials for that core ``d`` owns (deduplicated — a core
    pre-sums all its slots of one table before sending).

    ``mesh_shape=(hosts, cores_per_host)`` with ``hosts > 1`` builds the
    two-level variant (DESIGN.md §12): each table gets one owner core *per
    host that holds rows of it* (a globally row-sharded rock appears in
    every host's buckets), every core sends only to its own host's owner —
    the ``all_to_all`` payload never crosses hosts — and a table's bucket
    position is chosen to be free in ALL of its owners' buckets, so
    ``owned_pos`` keeps the flat ``(N,)`` shape with one globally
    consistent position.  The existing ``_sparse_rejoin`` scatter-add then
    sums a multi-host table's per-host partials without any executor
    change.  ``hosts == 1`` (or ``None``) is the original single-level map,
    bit for bit.
    """
    rows_by: dict[tuple[int, int], int] = {}
    for a in plan.assignments:
        key = (a.table_idx, a.core)
        rows_by[key] = rows_by.get(key, 0) + a.rows
    hosts, cph = mesh_shape if mesh_shape is not None else (1, k)
    if hosts > 1:
        # owner per (table, holding host): the in-host core with most rows.
        host_owner: dict[tuple[int, int], int] = {}
        owners_of: dict[int, list[int]] = {}
        for ti in sorted({a.table_idx for a in plan.assignments}):
            by_host: dict[int, list[int]] = {}
            for (t, c) in rows_by:
                if t == ti:
                    by_host.setdefault(c // cph, []).append(c)
            owners_of[ti] = []
            for h in sorted(by_host):
                oc = min(by_host[h], key=lambda c: (-rows_by[(ti, c)], c))
                host_owner[(ti, h)] = oc
                owners_of[ti].append(oc)
        # one globally consistent bucket position per table: the smallest
        # position free in every one of its owners' buckets (greedy in
        # table order — deterministic, and N tables keep owned_pos (N,)).
        used: dict[int, set[int]] = {c: set() for c in range(k)}
        owner = -np.ones(n_tables, np.int32)
        owned_pos = -np.ones(n_tables, np.int32)
        for ti, ocs in owners_of.items():
            p = 0
            while any(p in used[c] for c in ocs):
                p += 1
            owned_pos[ti] = p
            for c in ocs:
                used[c].add(p)
            # primary owner (reporting only): the owner on the host with
            # the most rows of the table.
            owner[ti] = max(
                ocs,
                key=lambda c: (
                    sum(r for (t, cc), r in rows_by.items()
                        if t == ti and cc // cph == c // cph),
                    -c,
                ),
            )
        o_max = max(
            1, max((max(s) + 1 for s in used.values() if s), default=0)
        )
        bucket = -np.ones((k, o_max), np.int32)
        for ti, ocs in owners_of.items():
            for c in ocs:
                bucket[c, int(owned_pos[ti])] = ti
        send_sets: dict[tuple[int, int], set[int]] = {}
        for a in plan.assignments:
            d = host_owner[(a.table_idx, a.core // cph)]
            send_sets.setdefault((a.core, d), set()).add(a.table_idx)
    else:
        owner = -np.ones(n_tables, np.int32)
        for ti in {a.table_idx for a in plan.assignments}:
            cores = [c for (t, c) in rows_by if t == ti]
            owner[ti] = min(cores, key=lambda c: (-rows_by[(ti, c)], c))
        owned: dict[int, list[int]] = {c: [] for c in range(k)}
        for ti in range(n_tables):
            if owner[ti] >= 0:
                owned[int(owner[ti])].append(ti)
        o_max = max(1, max((len(v) for v in owned.values()), default=0))
        bucket = -np.ones((k, o_max), np.int32)
        owned_pos = -np.ones(n_tables, np.int32)
        for c, lst in owned.items():
            for p, ti in enumerate(lst):
                bucket[c, p] = ti
                owned_pos[ti] = p
        send_sets = {}
        for a in plan.assignments:
            send_sets.setdefault((a.core, int(owner[a.table_idx])), set()).add(
                a.table_idx
            )
    n_send = max([1] + [len(v) for v in send_sets.values()])
    send = -np.ones((k, k, n_send), np.int32)
    for (c, d), tis in send_sets.items():
        for q, ti in enumerate(sorted(tis)):
            send[c, d, q] = ti
    return owner, bucket, owned_pos, send


def cache_plan_entries(
    plan: Plan,
    tables: Sequence[TableSpec],
    freqs,
    cache_rows: int,
) -> dict[int, list]:
    """Per-core residency-cache carve: the ``cache_rows`` rows of each core's
    **GM** chunk inventory with the highest expected hit count.

    Only GM chunks are candidates: GM is the one strategy that pays HBM per
    landing lookup, so it is the only place a resident hot row saves modeled
    (and real per-lookup) traffic — UB streams its chunk regardless and
    L1/L1-UB are already priced resident; carving their rows would burn
    cache slots for zero credited savings.  Candidates are ranked by
    per-query expected hits ``p · seq / replicas`` with deterministic tie
    order (table, then row id) — so shadow re-pack plans carve
    byte-identical caches across runs.  Returns
    ``{core: [(slot_index, assignment, global_row, weight), ...]}`` (at most
    ``cache_rows`` entries per core; within one chunk the selected rows are
    always that chunk's hottest prefix, which is what the cost/traffic
    models assume).  Shared by :func:`pack_plan` (contents) and
    ``repro.core.traffic.modeled_plan_traffic`` (hit accounting).
    """
    out: dict[int, list] = {c: [] for c in range(plan.n_cores)}
    if not cache_rows or freqs is None:
        return out
    for core, assigns in plan.per_core().items():
        cand = []
        for s_i, a in enumerate(assigns):
            f = freq_of(freqs, a.table_idx)
            if f is None or a.strategy is not Strategy.GM:
                continue
            ids = np.asarray(f.ids, np.int64)
            probs = np.asarray(f.probs, np.float64)
            sel = (ids >= a.row_offset) & (ids < a.row_offset + a.rows)
            w = probs[sel] * tables[a.table_idx].seq / max(a.replicas, 1)
            for gid, ww in zip(ids[sel].tolist(), w.tolist()):
                cand.append((-ww, a.table_idx, gid, s_i))
        cand.sort()
        out[core] = [
            (s_i, assigns[s_i], gid, -nw)
            for nw, _, gid, s_i in cand[:cache_rows]
        ]
    return out


def pack_plan(
    plan: Plan,
    tables: Sequence[TableSpec],
    table_data: Sequence[jax.Array] | None,
    *,
    dtype=jnp.float32,
    layout: str = "ragged",
    block_r: int | None = None,
    block_b: int | None = None,
    freqs=None,
    unique_cap: int | None = None,
    cache_rows: int | None = None,
    kernel_path: str | None = None,
) -> PackedPlan:
    """Materialize a Plan into the packed executor layout.

    ``table_data[i]`` is the (m_i, E) array for table i, or ``None`` for
    abstract packing (zeros; used by tests/dry-runs that only need shapes).

    ``layout="ragged"`` concatenates each core's chunks row-wise (the memory-
    proportional layout); ``layout="dense"`` pads every slot to the global
    ``max_rows`` (the legacy layout, kept for comparison).  ``block_r`` /
    ``block_b`` override the fused kernel's row-block / resident-batch sizes
    (see :mod:`repro.core.autotune` for the tuned pick).  A ``layout``
    summary (bytes, padding fraction) is recorded in ``plan.meta`` either way.

    ``unique_cap``/``cache_rows`` arm the access-reduction subsystem
    (DESIGN.md §6); ``None`` resolves each from ``plan.meta["cache"]`` (the
    planner's selection), so a ``plan_asymmetric(dedup=True, cache=True)``
    plan packs its dedup width and residency cache automatically.  The cache
    carve (top-mass rows per core + the buffer-row→cache-position remap)
    needs the access histograms: pass the same ``freqs`` the plan was priced
    under.  Ragged layout only.

    ``kernel_path`` selects the dedup'd unique-row gather implementation per
    step (DESIGN.md §11): ``"onehot"`` (the MXU one-hot GEMM), ``"sparse"``
    (the true-sparse row gather — forces every real step sparse), or
    ``"auto"`` (per-chunk from the planner's cost-modeled choice in
    ``plan.meta["kernel"]["per_chunk"]``; chunks without a record stay
    one-hot).  ``None`` resolves from ``plan.meta["kernel"]["path"]`` (the
    planner's request), defaulting to ``"onehot"``.  The sparse path rides
    the dedup uniq/cnt machinery, so it needs ``unique_cap > 0`` — under
    ``"auto"`` a dedup-off pack silently stays one-hot (the autotuner sweeps
    ``unique_cap=0`` candidates); forcing ``"sparse"`` without dedup raises.
    """
    if layout not in ("ragged", "dense"):
        raise ValueError(f"unknown layout {layout!r}")
    access_meta = plan.meta.get("cache") or {}
    if unique_cap is None:
        unique_cap = int(access_meta.get("unique_cap") or 0)
    if cache_rows is None:
        cache_rows = int(access_meta.get("cache_rows") or 0)
    if cache_rows and freqs is None:
        raise ValueError(
            "cache_rows > 0 needs the access histograms (freqs) to carve "
            "the hot-row residency cache"
        )
    if layout == "dense" and (unique_cap or cache_rows):
        raise ValueError("dedup/cache require layout='ragged'")
    kernel_meta = plan.meta.get("kernel") or {}
    if kernel_path is None:
        kernel_path = kernel_meta.get("path") or "onehot"
    if kernel_path not in ("onehot", "sparse", "auto"):
        raise ValueError(f"unknown kernel_path {kernel_path!r}")
    if kernel_path == "sparse":
        if layout == "dense":
            raise ValueError("kernel_path='sparse' requires layout='ragged'")
        if not unique_cap:
            raise ValueError(
                "kernel_path='sparse' requires batch dedup (unique_cap > 0): "
                "the sparse gather rides the dedup uniq/cnt machinery"
            )
    # per-assignment gather path: forced mode applies everywhere; "auto"
    # follows the planner's per-chunk cost-model picks (parallel to
    # plan.assignments — per_core() returns the same objects).
    path_of: dict[int, str] = {}
    if kernel_path == "sparse":
        path_of = {id(a): "sparse" for a in plan.assignments}
    elif kernel_path == "auto" and unique_cap:
        per_chunk = kernel_meta.get("per_chunk") or []
        if len(per_chunk) == len(plan.assignments):
            path_of = {
                id(a): rec.get("path", "onehot")
                for a, rec in zip(plan.assignments, per_chunk)
            }
    e = tables[0].dim
    if any(t.dim != e for t in tables):
        raise ValueError("all tables must share the embedding dim E")
    k = plan.n_cores
    per_core = plan.per_core()
    max_slots = max((len(v) for v in per_core.values()), default=0)
    max_slots = max(max_slots, 1)
    max_rows = max((a.rows for a in plan.assignments), default=1)
    max_rows_pad = _align(max_rows, _ROW_PAD)

    def tbl(i):
        if table_data is None:
            return jnp.zeros((tables[i].rows, e), dtype)
        return table_data[i].astype(dtype)

    slot_table = -np.ones((k, max_slots), np.int32)
    slot_offset = np.zeros((k, max_slots), np.int32)
    slot_rows = np.zeros((k, max_slots), np.int32)
    slot_row_start = np.zeros((k, max_slots), np.int32)
    slot_strategy = np.zeros((k, max_slots), np.int32)
    slot_rep = np.zeros((k, max_slots), np.int32)
    slot_nrep = np.ones((k, max_slots), np.int32)

    for core in range(k):
        for s_i, a in enumerate(per_core.get(core, [])):
            slot_table[core, s_i] = a.table_idx
            slot_offset[core, s_i] = a.row_offset
            slot_rows[core, s_i] = a.rows
            slot_strategy[core, s_i] = STRATEGY_CODE[a.strategy]
            slot_rep[core, s_i] = a.batch_frac[0]
            slot_nrep[core, s_i] = a.batch_frac[1]
            if a.row_offset + a.rows > tables[a.table_idx].rows:
                raise ValueError("chunk exceeds table rows")

    itemsize = jnp.dtype(dtype).itemsize
    dense_bytes = k * max_slots * (max_rows_pad + 1) * e * itemsize

    if layout == "dense":
        blocks = []
        for core in range(k):
            row = []
            assigns = per_core.get(core, [])
            for s_i in range(max_slots):
                if s_i < len(assigns):
                    a = assigns[s_i]
                    chunk = tbl(a.table_idx)[a.row_offset : a.row_offset + a.rows]
                    pad = max_rows_pad + 1 - chunk.shape[0]
                    chunk = jnp.pad(chunk, ((0, pad), (0, 0)))
                else:
                    chunk = jnp.zeros((max_rows_pad + 1, e), dtype)
                row.append(chunk)
            blocks.append(jnp.stack(row))
        chunk_arr = jnp.stack(blocks)  # (K, S, R+1, E)
        step_slot = np.zeros((k, 0), np.int32)
        step_base = np.zeros((k, 0), np.int32)
        step_block = np.zeros((k, 0), np.int32)
        step_strategy = np.zeros((k, 0), np.int32)
        step_kpath = np.zeros((k, 0), np.int32)
        cache_data = jnp.zeros((k, 0, e), dtype)
        cache_remap = jnp.zeros((k, 1), jnp.int32)
        br = 0
        slot_window = 0
        n_pad_steps = 0
    else:
        # ragged: per core, concatenate chunks row-wise; each chunk's region
        # is padded to a block_r multiple (>= 1 zero row after the data, the
        # slot's redirect target), so the fused kernel's row-blocks tile it.
        br = block_r or _default_block_r([a.rows for a in plan.assignments])
        br = max(_align(br, _ROW_PAD), _ROW_PAD)
        # per-strategy step schedule: slots grouped by strategy code (then
        # ascending size) so every strategy's steps form one contiguous run —
        # L1-resident, GM-streamed, and UB one-hot slots all execute from the
        # same (slot, row-block) schedule and the kernel dispatches per step.
        core_order: dict[int, list[int]] = {
            core: sorted(
                range(len(per_core.get(core, []))),
                key=lambda s_i: (
                    STRATEGY_CODE[per_core[core][s_i].strategy],
                    per_core[core][s_i].rows,
                    s_i,
                ),
            )
            for core in range(k)
        }
        steps: list[list[tuple[int, int, int, int, int]]] = []
        slot_window = br
        t_needed = br
        for core in range(k):
            cur = 0
            core_steps: list[tuple[int, int, int, int, int]] = []
            for s_i in core_order[core]:
                a = per_core[core][s_i]
                alloc = _align(a.rows + 1, br)
                slot_row_start[core, s_i] = cur
                code = STRATEGY_CODE[a.strategy]
                kp = 1 if path_of.get(id(a)) == "sparse" else 0
                for j in range(alloc // br):
                    core_steps.append((s_i, j * br, cur // br + j, code, kp))
                cur += alloc
                slot_window = max(slot_window, alloc)
            steps.append(core_steps)
            t_needed = max(t_needed, cur)
        # NOTE: the retired per-slot scan path used to force every core's
        # buffer to cover [row_start, row_start + slot_window) for every
        # slot; the schedule-driven kernel only ever touches real row-blocks,
        # so the buffer ends at the largest core's own total.
        t_pad = _align(t_needed, br)

        buf = np.zeros((k, t_pad + 1, e), jnp.dtype(dtype).name)
        for core in range(k):
            for s_i, a in enumerate(per_core.get(core, [])):
                start = int(slot_row_start[core, s_i])
                chunk = np.asarray(
                    tbl(a.table_idx)[a.row_offset : a.row_offset + a.rows]
                )
                buf[core, start : start + a.rows] = chunk
        chunk_arr = jnp.asarray(buf)

        if cache_rows:
            # residency-cache carve: copy each core's top-mass rows into the
            # dense mini-table and point the buffer-row remap at them; the
            # executor splits lookups hot/cold through this remap and the
            # kernel pins cache_np VMEM-resident across steps.  The planner
            # sizes the budget workload-wide, but only GM chunks are carve
            # candidates — clamp to the realized carve so zero rows are
            # never allocated or charged against the kernel's VMEM budget.
            entries = cache_plan_entries(plan, tables, freqs, cache_rows)
            realized = max((len(v) for v in entries.values()), default=0)
            cache_rows = min(cache_rows, realized)
        if cache_rows:
            cache_pad = _align(cache_rows, _ROW_PAD)
            cache_np = np.zeros((k, cache_pad, e), jnp.dtype(dtype).name)
            remap_np = -np.ones((k, t_pad + 1), np.int32)
            for core in range(k):
                # one fancy-indexed fetch per (core, table): per-row tbl()
                # round trips would be paid on every shadow re-pack.
                rows_by_table: dict[int, list[tuple[int, int]]] = {}
                for p, (s_i, a, gid, _w) in enumerate(entries[core]):
                    row = int(slot_row_start[core, s_i]) + gid - a.row_offset
                    remap_np[core, row] = p
                    rows_by_table.setdefault(a.table_idx, []).append((p, gid))
                for ti, pairs in rows_by_table.items():
                    pos = [p for p, _ in pairs]
                    gids = [g for _, g in pairs]
                    cache_np[core, pos] = np.asarray(tbl(ti)[jnp.asarray(gids)])
            cache_data = jnp.asarray(cache_np)
            cache_remap = jnp.asarray(remap_np)
            cache_rows = cache_pad
            plan.meta.setdefault("cache", {})["packed"] = {
                "cache_rows": int(cache_pad),
                "rows_per_core": [len(entries[c]) for c in range(k)],
            }
        else:
            cache_data = jnp.zeros((k, 0, e), dtype)
            cache_remap = jnp.zeros((k, t_pad + 1), jnp.int32)
            if plan.meta.get("cache", {}).get("cache_rows"):
                # requested but nothing carvable (no GM chunks hold explicit
                # hot rows) — record the empty carve so stats stay honest.
                plan.meta["cache"]["packed"] = {
                    "cache_rows": 0, "rows_per_core": [0] * k,
                }

        # uniform step count across cores (shard_map runs one program);
        # padding steps target the trash slot (id = max_slots) with base 0,
        # so they init-write zeros into a discarded output block.
        n_steps = max((len(s) for s in steps), default=0)
        n_pad_steps = sum(n_steps - len(s) for s in steps)
        step_slot = np.full((k, n_steps), max_slots, np.int32)
        step_base = np.zeros((k, n_steps), np.int32)
        step_block = np.zeros((k, n_steps), np.int32)
        step_strategy = np.zeros((k, n_steps), np.int32)
        step_kpath = np.zeros((k, n_steps), np.int32)
        for core, core_steps in enumerate(steps):
            for t, (s_i, base, blk, code, kp) in enumerate(core_steps):
                step_slot[core, t] = s_i
                step_base[core, t] = base
                step_block[core, t] = blk
                step_strategy[core, t] = code
                step_kpath[core, t] = kp

    mesh_meta = plan.meta.get("mesh") or {}
    mesh_shape = (
        int(mesh_meta.get("hosts", 1)),
        int(mesh_meta.get("cores_per_host", k)),
    )
    owner, rejoin_bucket, rejoin_owned_pos, rejoin_send = _rejoin_maps(
        plan, len(tables), k, mesh_shape=mesh_shape
    )

    ragged_bytes = int(np.prod(chunk_arr.shape)) * itemsize
    plan.meta["layout"] = {
        "kind": layout,
        "chunk_bytes": ragged_bytes,
        "dense_bytes": dense_bytes,
        "bytes_vs_dense": ragged_bytes / max(dense_bytes, 1),
        "block_r": br,
        "block_b": int(block_b or 0),
        "slot_window": slot_window,
        "n_steps": int(step_slot.shape[1]),
        "n_padding_steps": int(n_pad_steps),
        "padding_frac": 1.0
        - sum(a.rows for a in plan.assignments)
        * e * itemsize / max(ragged_bytes, 1),
    }
    cph = mesh_shape[1]
    cross_host_sends = sum(
        int((rejoin_send[c, d] >= 0).sum())
        for c in range(k)
        for d in range(k)
        if c // cph != d // cph
    )
    plan.meta["rejoin"] = {
        "n_owned_max": int(rejoin_bucket.shape[1]),
        "n_send_max": int(rejoin_send.shape[2]),
        "owned_per_core": [
            int((rejoin_bucket[c] >= 0).sum()) for c in range(k)
        ],
        "hosts": mesh_shape[0],
        # all_to_all entries whose (sender, owner) pair crosses a host
        # boundary: 0 by construction for hierarchical plans — the check
        # that the slow tier only carries the bucket all_gather.
        "cross_host_sends": cross_host_sends,
    }

    # realized gather-path schedule; a pack with zero sparse steps resolves
    # to plain "onehot" so the executor's compiled graph (and its cache key)
    # is unchanged from a pre-kernel-path pack.
    n_sparse_steps = int((step_kpath == 1).sum())
    kernel_resolved = kernel_path if n_sparse_steps else "onehot"
    n_sparse_chunks = sum(
        1 for a in plan.assignments if path_of.get(id(a)) == "sparse"
    )
    plan.meta.setdefault("kernel", {})["packed"] = {
        "path": kernel_resolved,
        "sparse_chunks": n_sparse_chunks,
        "onehot_chunks": len(plan.assignments) - n_sparse_chunks,
        "sparse_steps": n_sparse_steps,
    }

    # symmetric group
    sym_idx = list(plan.symmetric_tables)
    n_sym = len(sym_idx)
    if n_sym:
        msym = max(tables[i].rows for i in sym_idx)
        msym = _align(msym, _ROW_PAD)
        sym_blocks = []
        for i in sym_idx:
            t = tbl(i)
            sym_blocks.append(jnp.pad(t, ((0, msym + 1 - t.shape[0]), (0, 0))))
        sym_data = jnp.stack(sym_blocks)
    else:
        sym_data = jnp.zeros((0, 1, e), dtype)

    return PackedPlan(
        chunk_data=chunk_arr,
        slot_table=jnp.asarray(slot_table),
        slot_offset=jnp.asarray(slot_offset),
        slot_rows=jnp.asarray(slot_rows),
        slot_row_start=jnp.asarray(slot_row_start),
        slot_strategy=jnp.asarray(slot_strategy),
        slot_rep=jnp.asarray(slot_rep),
        slot_nrep=jnp.asarray(slot_nrep),
        step_slot=jnp.asarray(step_slot),
        step_base=jnp.asarray(step_base),
        step_block=jnp.asarray(step_block),
        step_strategy=jnp.asarray(step_strategy),
        step_kpath=jnp.asarray(step_kpath),
        rejoin_send=jnp.asarray(rejoin_send),
        rejoin_owned_pos=jnp.asarray(rejoin_owned_pos),
        rejoin_bucket=jnp.asarray(rejoin_bucket),
        sym_data=sym_data,
        cache_data=cache_data,
        cache_remap=cache_remap,
        layout=layout,
        block_r=br,
        slot_window=slot_window,
        block_b=int(block_b or 0),
        unique_cap=int(unique_cap),
        cache_rows=int(cache_rows),
        kernel_path=kernel_resolved,
        sym_static=tuple(
            (i, tables[i].rows, STRATEGY_CODE[s])
            for i, s in zip(sym_idx, plan.symmetric_strategies)
        ),
    )


# --------------------------------------------------------------------------
# strategy dispatch on one chunk (symmetric group + legacy dense layout)
# --------------------------------------------------------------------------


def _bag_with_strategy(
    chunk: jax.Array, lidx: jax.Array, strategy_code: int, use_kernels: bool
) -> jax.Array:
    """(R+1, E) chunk x (B, s) pre-clipped local indices -> (B, E) f32, with
    the kernel of the static ``strategy_code``."""
    if not use_kernels:
        # XLA gather path: identical math; strategies only differ in timing.
        return jnp.take(chunk, lidx, axis=0).astype(jnp.float32).sum(axis=1)
    interp = compat.pallas_interpret()
    strategy = _CODE_STRATEGY[strategy_code]
    if strategy is Strategy.GM:
        return embedding_bag_gm(chunk, lidx, interpret=interp)
    if strategy is Strategy.L1:
        return embedding_bag_l1(chunk, lidx, interpret=interp)
    return embedding_bag_ub(
        chunk, lidx, persistent=strategy is Strategy.L1_UB, interpret=interp
    )


# --------------------------------------------------------------------------
# per-device slot sweep
# --------------------------------------------------------------------------


def _replica_bmask(packed: PackedPlan, b: int) -> jax.Array:
    """(S, B) bool: which batch rows each slot's replica serves."""
    bpos = jnp.arange(b, dtype=jnp.int32)
    return (bpos[None, :] * packed.slot_nrep[:, None]) // b == packed.slot_rep[:, None]


def _local_asym_lookup(
    packed: PackedPlan, indices: jax.Array, *, n_tables: int, use_kernels
) -> jax.Array:
    """indices (N, B, s) -> local partial (N, B, E) f32 (pre-rejoin).

    ``use_kernels``: False = XLA gather; "fused" = ONE schedule-driven
    streaming pallas_call for the whole sweep (the default executor).
    ``True`` is the retired per-slot scan spelling — it routes to the fused
    path.
    """
    if use_kernels:
        return _fused_asym_lookup(packed, indices, n_tables=n_tables)
    if packed.layout == "dense":
        return _dense_asym_lookup(packed, indices, n_tables=n_tables)

    _, b, _ = indices.shape
    buffer = packed.chunk_data  # (T+1, E)
    zrow = buffer.shape[0] - 1  # shared trailing zero row
    bpos = jnp.arange(b, dtype=jnp.int32)

    def body(out, xs):
        ti, off, rows, start, rep, nrep = xs
        idx = jnp.take(indices, jnp.maximum(ti, 0), axis=0)  # (B, s)
        local = idx - off
        valid = (idx >= 0) & (local >= 0) & (local < rows) & (ti >= 0)
        # replica r of n serves the r-th contiguous batch 1/n-slice.
        bmask = (bpos * nrep) // b == rep
        valid = valid & bmask[:, None]
        gidx = jnp.where(valid, start + local, zrow).astype(jnp.int32)
        pooled = jnp.take(buffer, gidx, axis=0).astype(jnp.float32).sum(axis=1)
        out = out.at[jnp.maximum(ti, 0)].add(
            jnp.where(ti >= 0, pooled, jnp.zeros_like(pooled))
        )
        return out, None

    out0 = jnp.zeros((n_tables, b, buffer.shape[-1]), jnp.float32)
    xs = (
        packed.slot_table,
        packed.slot_offset,
        packed.slot_rows,
        packed.slot_row_start,
        packed.slot_rep,
        packed.slot_nrep,
    )
    out, _ = lax.scan(body, out0, xs)
    return out


def _dense_asym_lookup(
    packed: PackedPlan, indices: jax.Array, *, n_tables: int
) -> jax.Array:
    """XLA gather sweep over the legacy stacked-slot (S, R+1, E) layout."""
    _, b, _ = indices.shape
    rpad = packed.chunk_data.shape[-2] - 1  # zero row index
    e = packed.chunk_data.shape[-1]
    bpos = jnp.arange(b, dtype=jnp.int32)

    def body(out, xs):
        chunk, ti, off, rows, rep, nrep = xs
        idx = jnp.take(indices, jnp.maximum(ti, 0), axis=0)  # (B, s)
        local = idx - off
        valid = (idx >= 0) & (local >= 0) & (local < rows) & (ti >= 0)
        bmask = (bpos * nrep) // b == rep
        valid = valid & bmask[:, None]
        lidx = jnp.where(valid, local, rpad).astype(jnp.int32)
        pooled = jnp.take(chunk, lidx, axis=0).astype(jnp.float32).sum(axis=1)
        out = out.at[jnp.maximum(ti, 0)].add(
            jnp.where(ti >= 0, pooled, jnp.zeros_like(pooled))
        )
        return out, None

    out0 = jnp.zeros((n_tables, b, e), jnp.float32)
    xs = (
        packed.chunk_data,
        packed.slot_table,
        packed.slot_offset,
        packed.slot_rows,
        packed.slot_rep,
        packed.slot_nrep,
    )
    out, _ = lax.scan(body, out0, xs)
    return out


def _local_sym_lookup(
    packed: PackedPlan, idx_slice: jax.Array, *, n_tables: int, use_kernels
) -> jax.Array:
    """Symmetric fallback: idx_slice (N, B/K, s) -> (N, B/K, E) f32.

    One kernel call per symmetric table, on that table's own rows plus the
    zero row after them (``sym_data`` pads every table to the largest)."""
    _, bl, _ = idx_slice.shape
    e = packed.sym_data.shape[-1]
    out = jnp.zeros((n_tables, bl, e), jnp.float32)
    for j, (ti, rows, code) in enumerate(packed.sym_static):
        idx = idx_slice[ti]
        lidx = jnp.where((idx >= 0) & (idx < rows), idx, rows).astype(jnp.int32)
        pooled = _bag_with_strategy(
            packed.sym_data[j, : rows + 1], lidx, code, bool(use_kernels)
        )
        out = out.at[ti].add(pooled)
    return out


def _fused_local_indices(
    packed: PackedPlan, indices: jax.Array
) -> tuple[jax.Array, jax.Array | None]:
    """Vectorized slot preprocessing for the fused kernel: the (S, B, s)
    local row of each slot's lookups, with the layout's sentinel where the
    lookup is not this slot's (dense: the pad row; ragged: -1, which matches
    no row-block window), and, when the pack carries a residency cache, the
    cache position of each lookup (-1 = streamed)."""
    ti = packed.slot_table  # (S,)
    idx = jnp.take(indices, jnp.maximum(ti, 0), axis=0)  # (S, B, s)
    local = idx - packed.slot_offset[:, None, None]
    valid = (
        (idx >= 0)
        & (local >= 0)
        & (local < packed.slot_rows[:, None, None])
        & (ti >= 0)[:, None, None]
    )
    valid = valid & _replica_bmask(packed, indices.shape[1])[:, :, None]
    if packed.layout == "dense":
        rpad = packed.chunk_data.shape[-2] - 1
        return jnp.where(valid, local, rpad).astype(jnp.int32), None
    lidx = jnp.where(valid, local, -1).astype(jnp.int32)
    if not packed.cache_rows:
        return lidx, None
    # hot/cold split through the packed remap: cache-resident rows leave the
    # streaming index tensor and arrive as cache positions.
    trash = packed.cache_remap.shape[0] - 1  # remap[trash] == -1
    g = jnp.where(valid, packed.slot_row_start[:, None, None] + local, trash)
    hidx = jnp.take(packed.cache_remap, g).astype(jnp.int32)
    return jnp.where(hidx >= 0, -1, lidx), hidx


def _fused_asym_lookup(
    packed: PackedPlan, indices: jax.Array, *, n_tables: int
) -> jax.Array:
    """One schedule-driven pallas_call for all slots (kernels/embedding_multi).

    The index preparation in front of the kernel carries the
    ``lookup_prep`` name scope; the kernel call stays outside it."""
    from repro.kernels.embedding_multi import (
        multi_embedding_bag_dense,
        multi_embedding_bag_ragged,
    )

    _, b, _ = indices.shape
    e = packed.chunk_data.shape[-1]
    interp = compat.pallas_interpret()
    ti = packed.slot_table  # (S,)
    with jax.named_scope("lookup_prep"):
        lidx, hidx = _fused_local_indices(packed, indices)

    if packed.layout == "dense":
        pooled = multi_embedding_bag_dense(
            packed.chunk_data, lidx, interpret=interp
        )  # (S, B, E) f32
    elif packed.step_slot.shape[-1] == 0:
        pooled = jnp.zeros((ti.shape[0], b, e), jnp.float32)
    else:
        pooled = multi_embedding_bag_ragged(
            packed.chunk_data[:-1],  # drop the shared zero row: block_r-tiled
            lidx,
            packed.step_slot,
            packed.step_base,
            packed.step_block,
            packed.step_strategy,
            block_r=packed.block_r,
            block_b=packed.block_b or None,
            interpret=interp,
            unique_cap=packed.unique_cap,
            cache=packed.cache_data if hidx is not None else None,
            hidx=hidx,
            # kernel_path is static aux: an all-onehot pack compiles the
            # exact pre-kernel-path graph (no selector prefetch at all).
            step_kpath=(
                packed.step_kpath if packed.kernel_path != "onehot" else None
            ),
        )  # (S, B, E) f32
    out = jnp.zeros((n_tables, b, e), jnp.float32)
    return out.at[jnp.maximum(ti, 0)].add(
        jnp.where((ti >= 0)[:, None, None], pooled, 0.0)
    )


# --------------------------------------------------------------------------
# inter-core rejoin
# --------------------------------------------------------------------------


def _sparse_rejoin(local: jax.Array, packed: PackedPlan, axis: str) -> jax.Array:
    """Owner-sharded sparse rejoin of per-core partials (inside shard_map).

    ``local`` is this core's (N, B, E) partial (zeros for tables it holds no
    chunk of).  Instead of ``psum``-ing the fully dense partials (K·N·B·E
    collective bytes), each core sends only the owned-slot rows it actually
    holds to each table's owner (``all_to_all`` over the rejoin maps), the
    owner sums them (replicated/row-split slots included), and an
    ``all_gather`` of the per-owner buckets rebuilds the replicated output.
    """
    n_tables = local.shape[0]
    send_table = packed.rejoin_send  # (K, K, n_send)
    o = packed.rejoin_bucket.shape[1]
    me = lax.axis_index(axis)
    # what this core sends each owner: its partial rows for that owner's
    # tables (zeros where it holds nothing — already exact from the sweep).
    my_send = jnp.take(send_table, me, axis=0)  # (K, n_send)
    x = jnp.take(local, jnp.maximum(my_send, 0), axis=0)  # (K, n_send, B, E)
    x = jnp.where((my_send >= 0)[:, :, None, None], x, 0.0)
    r = lax.all_to_all(x, axis, split_axis=0, concat_axis=0)
    # what arrived: core j's partials for MY owned tables send_table[j, me].
    recv = jnp.take(send_table, me, axis=1)  # (K, n_send)
    pos = jnp.take(packed.rejoin_owned_pos, jnp.maximum(recv, 0))
    pos = jnp.where(recv >= 0, pos, o)  # trash bucket for -1 padding
    owned = jnp.zeros((o + 1,) + local.shape[1:], jnp.float32)
    owned = owned.at[pos.reshape(-1)].add(
        r.reshape((-1,) + local.shape[1:])
    )[:o]
    # replicate: every core needs the full (N, B, E) pooled output.
    gathered = lax.all_gather(owned, axis, axis=0, tiled=True)  # (K·O, B, E)
    bucket = packed.rejoin_bucket.reshape(-1)  # (K·O,)
    out = jnp.zeros((n_tables + 1,) + local.shape[1:], jnp.float32)
    out = out.at[jnp.where(bucket >= 0, bucket, n_tables)].add(gathered)
    return out[:n_tables]


def _ring_psum(x: jax.Array, axis: str) -> jax.Array:
    """Ring all-reduce via collective_permute; K-1 steps.

    Beyond-paper §Perf: on real hardware XLA overlaps the permute DMA of step
    t with the add of step t-1 (latency-hiding scheduler), replacing the
    blocking fused all-reduce at the tail of the slot sweep.
    """
    ksz = lax.axis_size(axis)
    if ksz == 1:
        return x
    perm = [(i, (i + 1) % ksz) for i in range(ksz)]

    def step(carry, _):
        acc, buf = carry
        buf = lax.ppermute(buf, axis, perm)
        return (acc + buf, buf), None

    (acc, _), _ = lax.scan(step, (x, x), None, length=ksz - 1)
    return acc


# --------------------------------------------------------------------------
# SPMD entry point
# --------------------------------------------------------------------------


def partitioned_lookup(
    packed: PackedPlan,
    indices: jax.Array,
    *,
    mesh: jax.sharding.Mesh,
    axis: str = "model",
    batch_axes: tuple[str, ...] = (),
    n_tables: int,
    use_kernels="fused",
    reduce_mode: str = "sparse",
) -> jax.Array:
    """Execute the plan. indices (N, B, s) int32 -> pooled (N, B, E) f32.

    ``axis`` is the "cores" mesh axis the chunks are sharded over;
    ``batch_axes`` optionally shards B over data axes (outer DP).
    ``use_kernels``: "fused" (default) = the schedule-driven streaming
    kernel; False = XLA gather; True = deprecated spelling of the retired
    per-slot scan (warns, routes to "fused" on the ragged layout).
    ``reduce_mode``: "sparse" (default, owner-sharded all_to_all/all_gather
    rejoin), "psum" (the paper's atomic accumulation), or "ring"
    (collective-permute pipelined accumulation — §Perf overlap variant).
    """
    if use_kernels is True:
        warnings.warn(
            "use_kernels=True (the per-slot lax.scan over max-alloc windows) "
            "is legacy: ragged plans now execute the schedule-driven fused "
            "kernel. Pass use_kernels='fused' (or False for the XLA path).",
            DeprecationWarning,
            stacklevel=2,
        )
    bspec = jax.sharding.PartitionSpec(None, batch_axes or None, None)

    def spmd(packed_l, idx):
        # shard_map leaves a leading size-1 core dim on the sharded arrays.
        packed_l = packed_l.strip_core(0)
        out = _local_asym_lookup(
            packed_l, idx, n_tables=n_tables, use_kernels=use_kernels
        )
        if reduce_mode == "sparse":
            out = _sparse_rejoin(out, packed_l, axis)
        elif reduce_mode == "ring":
            out = _ring_psum(out, axis)
        else:
            out = lax.psum(out, axis)
        # symmetric fallback: batch-split over the core axis.
        k = lax.axis_index(axis)
        ksz = lax.axis_size(axis)
        b = idx.shape[1]
        bl = b // ksz
        idx_slice = lax.dynamic_slice_in_dim(idx, k * bl, bl, axis=1)
        sym = _local_sym_lookup(
            packed_l, idx_slice, n_tables=n_tables, use_kernels=use_kernels
        )
        sym = lax.all_gather(sym, axis, axis=1, tiled=True)
        return out + sym

    pspec = jax.sharding.PartitionSpec
    packed_specs = PackedPlan(
        **{
            f: (
                pspec()
                if f in PackedPlan._REPLICATED_FIELDS
                else pspec(axis)
            )
            for f in PackedPlan._ARRAY_FIELDS
        },
        layout=packed.layout,
        block_r=packed.block_r,
        slot_window=packed.slot_window,
        block_b=packed.block_b,
        unique_cap=packed.unique_cap,
        cache_rows=packed.cache_rows,
        kernel_path=packed.kernel_path,
        sym_static=packed.sym_static,
    )
    fn = jax.shard_map(
        spmd,
        mesh=mesh,
        in_specs=(packed_specs, bspec),
        out_specs=jax.sharding.PartitionSpec(None, batch_axes or None, None),
        check_vma=False,
    )
    return fn(packed, indices)


# --------------------------------------------------------------------------
# vocab-parallel gather (the pool-free chunked case, for LM embeddings)
# --------------------------------------------------------------------------


def vocab_parallel_embed(
    table_shard: jax.Array,
    tokens: jax.Array,
    axis: str,
) -> jax.Array:
    """Inside shard_map: (V/K, d) local shard, (B, S) tokens -> (B, S, d).

    This is the paper's offset-subtract + clip + masked lookup + atomic
    accumulation specialized to s=1 pool-free gathers (== Megatron
    vocab-parallel embedding; see DESIGN.md §2).
    """
    vl = table_shard.shape[0]
    off = lax.axis_index(axis) * vl
    local = tokens - off
    valid = (local >= 0) & (local < vl)
    lidx = jnp.where(valid, local, 0)
    emb = jnp.take(table_shard, lidx, axis=0)
    emb = jnp.where(valid[..., None], emb, jnp.zeros_like(emb))
    return lax.psum(emb, axis)
