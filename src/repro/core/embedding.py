"""PartitionedEmbeddingBag — the public API tying planner + executor together.

Usage::

    bag = PartitionedEmbeddingBag(workload, n_cores=mesh.shape["model"],
                                  planner="asymmetric")
    params = bag.init(jax.random.PRNGKey(0))        # list of (m_i, E) tables
    packed = bag.pack(params)                       # placed per the plan
    pooled = bag.apply(packed, indices, mesh=mesh)  # (N, B, E)

``indices`` is a list of per-table (B, s_i) int arrays or the pre-stacked
(N, B, s_max) tensor with ``-1`` padding.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import planner as planner_lib
from repro.core.cost_model import CostModel, analytic_model
from repro.core.partition import PackedPlan, pack_plan, partitioned_lookup
from repro.core.strategies import Plan
from repro.core.tables import Workload


def stack_indices(indices: Sequence[jax.Array], s_max: int | None = None):
    """Per-table (B, s_i) index arrays -> (N, B, s_max) with -1 padding."""
    s_max = s_max or max(i.shape[1] for i in indices)
    padded = [
        jnp.pad(i.astype(jnp.int32), ((0, 0), (0, s_max - i.shape[1])), constant_values=-1)
        for i in indices
    ]
    return jnp.stack(padded)


@dataclasses.dataclass
class PartitionedEmbeddingBag:
    workload: Workload
    n_cores: int
    # a PLANNERS name or any callable with the planner signature
    # (workload, n_cores, model, **kwargs) -> Plan — how InferenceEngine
    # plugs registered placement policies in (DESIGN.md §7)
    planner: str | Callable[..., Plan] = "asymmetric"
    cost_model: CostModel | None = None
    dtype: jnp.dtype = jnp.float32
    planner_kwargs: dict = dataclasses.field(default_factory=dict)
    layout: str = "ragged"  # "ragged" (memory-proportional) or "dense"

    def __post_init__(self):
        self.cost_model = self.cost_model or analytic_model()
        plan_fn = (
            planner_lib.PLANNERS[self.planner]
            if isinstance(self.planner, str)
            else self.planner
        )
        self.plan: Plan = plan_fn(
            self.workload, self.n_cores, self.cost_model, **self.planner_kwargs
        )
        self.plan.validate(self.workload.tables)
        self.s_max = max(t.seq for t in self.workload.tables)
        self.n_tables = len(self.workload.tables)

    # -- parameters ---------------------------------------------------------

    def init(self, rng: jax.Array) -> list[jax.Array]:
        keys = jax.random.split(rng, self.n_tables)
        return [
            jax.random.normal(k, (t.rows, t.dim), self.dtype)
            / np.sqrt(t.dim)
            for k, t in zip(keys, self.workload.tables)
        ]

    def pack(
        self,
        table_data: Sequence[jax.Array] | None,
        *,
        layout: str | None = None,
        block_r: int | None = None,
        block_b: int | None = None,
        autotune: bool = False,
        freqs=None,
        unique_cap: int | None = None,
        cache_rows: int | None = None,
        kernel_path: str | None = None,
        tuning_cache=None,
        mesh: jax.sharding.Mesh | None = None,
    ) -> PackedPlan:
        """Materialize the plan.  ``autotune=True`` sweeps the fused kernel's
        ``block_r``/``block_b`` first (recorded in ``plan.meta["tuning"]``).

        ``unique_cap``/``cache_rows`` default to the planner's selection in
        ``plan.meta["cache"]`` (set by ``planner_kwargs`` ``dedup=``/
        ``cache=``); ``freqs`` defaults to the histograms the plan was priced
        under, so a dedup/cache plan packs its residency cache without extra
        arguments.  ``kernel_path`` (``None`` = the planner's cost-modeled
        choice in ``plan.meta["kernel"]``) selects the dedup'd gather
        implementation; ``tuning_cache`` (a
        :class:`repro.core.autotune.TuningCache`) lets the autotune sweep
        reuse prior picks for shape-identical plans.  With a ``mesh`` the
        large per-core buffers go straight onto their cores' devices
        (:func:`~repro.core.partition.pack_plan`)."""
        layout = layout or self.layout
        if freqs is None:
            freqs = self.planner_kwargs.get("freqs")
        if autotune and layout == "ragged" and block_r is None:
            from repro.core.autotune import autotune_block_sizes

            best = autotune_block_sizes(
                self.plan, self.workload.tables, batch=self.workload.batch,
                freqs=freqs, cache=tuning_cache,
            )
            block_r, block_b = best["block_r"], block_b or best["block_b"]
            # the sweep's winning access-reduction sizes ship with its block
            # sizes (with default candidates these equal the planner's pick)
            if unique_cap is None:
                unique_cap = best["unique_cap"]
            if cache_rows is None:
                cache_rows = best["cache_rows"]
            if kernel_path is None:
                kernel_path = best["kernel_path"]
        return pack_plan(
            self.plan,
            self.workload.tables,
            table_data,
            dtype=self.dtype,
            layout=layout,
            block_r=block_r,
            block_b=block_b,
            freqs=freqs,
            unique_cap=unique_cap,
            cache_rows=cache_rows,
            kernel_path=kernel_path,
            mesh=mesh,
        )

    def layout_summary(self) -> dict:
        """Packing-efficiency summary recorded by the last :meth:`pack`."""
        return dict(self.plan.meta.get("layout", {}))

    # -- execution ----------------------------------------------------------

    def apply(
        self,
        packed: PackedPlan,
        indices,
        *,
        mesh: jax.sharding.Mesh,
        axis: str = "model",
        batch_axes: tuple[str, ...] = (),
        use_kernels="fused",
        reduce_mode: str = "sparse",
    ) -> jax.Array:
        if isinstance(indices, (list, tuple)):
            indices = stack_indices(indices, self.s_max)
        return partitioned_lookup(
            packed,
            indices,
            mesh=mesh,
            axis=axis,
            batch_axes=batch_axes,
            n_tables=self.n_tables,
            use_kernels=use_kernels,
            reduce_mode=reduce_mode,
        )

    def reference(self, table_data, indices) -> jax.Array:
        """Dense single-device oracle for testing."""
        if isinstance(indices, (list, tuple)):
            indices = stack_indices(indices, self.s_max)
        outs = []
        for i, t in enumerate(table_data):
            idx = indices[i]
            valid = idx >= 0
            safe = jnp.where(valid, idx, 0)
            g = jnp.take(t, safe, axis=0)
            g = jnp.where(valid[..., None], g, jnp.zeros_like(g))
            outs.append(g.sum(axis=1).astype(jnp.float32))
        return jnp.stack(outs)
