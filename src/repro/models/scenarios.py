"""ScenarioModel wrappers — every model family served through the engine.

The scenario matrix (DESIGN.md §10) turns the repo's model zoo into engine
workloads: each wrapper owns a recommender-shaped *workload* (embedding
tables + batch), extracts the table arrays for
:meth:`repro.engine.InferenceEngine.build`, and supplies the two execution
paths every cell of the matrix is measured on:

* :meth:`ScenarioModel.make_step` — the served path: pooled embeddings come
  out of the engine's fused partitioned executor, then flow through the
  model's *tower* (the dense compute on top of the lookups);
* :meth:`ScenarioModel.reference_forward` — the oracle: plain
  ``jnp.take``-based lookups into the source tables, then the **same**
  jitted tower.

All scenario tables use ``seq=1`` (the paper fixes s=1 for every public
workload), which makes the pooled fused lookup *bit-exact* against the
dense reference — each pooled vector is one row reached through exact-zero
one-hot arithmetic — so the matrix gates bitwise parity, not a tolerance.
The tower is compiled once per scenario and shared by both paths: bitwise
equal pooled embeddings in, bitwise equal scores out.

Four towers cover the embedding/MLP-ratio spread production fleets run
(Gupta et al. 1906.03109, Park et al. 1811.09886):

* ``dlrm``        — the paper's model: bottom MLP + pairwise interaction
  + top MLP (:mod:`repro.models.dlrm`);
* ``moe``         — pooled feature embeddings as a token group through a
  capacity-routed mixture-of-experts layer (:mod:`repro.models.moe`);
* ``mamba2``      — the per-query feature sequence scanned by an SSD
  state-space block (:mod:`repro.models.mamba2`) — the "user history"
  shape where the tower is recurrent;
* ``transformer`` — a pre-norm self-attention + SwiGLU block over the
  feature tokens (:mod:`repro.models.layers`).

Wrappers register in :data:`repro.models.registry.SCENARIOS`; adding a
model there without passing the conformance battery in
``tests/test_scenario_matrix.py`` fails CI.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Mapping, Protocol, Sequence, runtime_checkable

import numpy as np

from repro.core.tables import Workload, make_workload

__all__ = [
    "ScenarioModel",
    "DLRMScenario",
    "MoEScenario",
    "Mamba2Scenario",
    "TransformerScenario",
]


@runtime_checkable
class ScenarioModel(Protocol):
    """What the scenario matrix needs from a model wrapper.

    A conforming wrapper owns a workload, hands its embedding tables to the
    engine, and exposes paired fused/reference forwards whose outputs the
    matrix can diff bit-for-bit.  ``make_step(engine)`` must work on *any*
    engine built from ``workload`` — including the re-planned engine a
    drift hot-swap produces — because the drift policy re-invokes it on
    every shadow re-pack.
    """

    name: str
    workload: Workload

    def table_data(self) -> list:
        """Per-table (rows, dim) embedding arrays, aligned with
        ``workload.tables`` — what :meth:`InferenceEngine.build` packs."""
        ...

    def sample_batch(self, rng, distribution, batch: int | None = None) -> dict:
        """Draw one batch of queries under a traffic distribution."""
        ...

    def payloads(self, batch: Mapping) -> list:
        """Split a batch into per-query ``submit_request`` payloads."""
        ...

    def reference_forward(self, batch: Mapping) -> np.ndarray:
        """Dense-lookup oracle scores (B,) for a batch."""
        ...

    def make_step(self, engine) -> Callable:
        """Served path: payloads -> (B,) scores through the engine."""
        ...

    def split(self, out, n: int) -> Sequence:
        """Batch output -> per-request results (``Server`` split_fn)."""
        ...


# --------------------------------------------------------------------------
# shared tower-over-pooled-embeddings base
# --------------------------------------------------------------------------


class _TowerScenario:
    """Common wrapper body: deterministic table + tower init, dense-lookup
    reference path, engine-backed step, per-query payload plumbing.

    Subclasses define ``name``, a default workload, ``_init_tower(key)``
    and ``_tower(params, pooled) -> (B,) scores``; the tower is jitted once
    and shared by the fused and reference paths so parity reduces to the
    pooled lookups (bit-exact at seq=1)."""

    name: str = "tower"

    def __init__(self, workload: Workload, seed: int = 0):
        import jax
        import jax.numpy as jnp

        self.workload = workload
        self.seed = seed
        kt, kp = jax.random.split(jax.random.PRNGKey(seed))
        self._tables = [
            jax.random.normal(k, (t.rows, t.dim), jnp.float32)
            / np.sqrt(float(t.dim))
            for k, t in zip(
                jax.random.split(kt, len(workload.tables)), workload.tables
            )
        ]
        self.params = self._init_tower(kp)
        # one compiled tower for BOTH paths: bitwise-equal pooled inputs
        # produce bitwise-equal scores.
        self._tower_jit = self._build_tower_jit()

    def _build_tower_jit(self):
        import jax

        return jax.jit(lambda pooled: self._tower(self.params, pooled))

    # -- protocol: tables + batches -----------------------------------------

    def table_data(self) -> list:
        return list(self._tables)

    def sample_batch(self, rng, distribution, batch: int | None = None) -> dict:
        from repro.data.distributions import sample_workload

        idx = sample_workload(rng, self.workload, distribution, batch)
        return {"indices": idx}  # (N, B, s_max) int32, -1 padding

    def payloads(self, batch: Mapping) -> list:
        idx = np.asarray(batch["indices"])
        return [{"indices": idx[:, i]} for i in range(idx.shape[1])]

    def collate(self, payloads: Sequence[Mapping]) -> dict:
        return {
            "indices": np.stack(
                [np.asarray(p["indices"]) for p in payloads], axis=1
            )
        }

    # -- protocol: the two forwards -----------------------------------------

    def _pooled_reference(self, indices):
        """Dense single-device oracle lookup: (N, B, s) -> (N, B, E) f32."""
        import jax.numpy as jnp

        outs = []
        for i, t in enumerate(self._tables):
            idx = jnp.asarray(indices)[i]
            valid = idx >= 0
            g = jnp.take(t, jnp.where(valid, idx, 0), axis=0)
            g = jnp.where(valid[..., None], g, jnp.zeros_like(g))
            outs.append(g.sum(axis=1).astype(jnp.float32))
        return jnp.stack(outs)

    def reference_forward(self, batch: Mapping) -> np.ndarray:
        pooled = self._pooled_reference(batch["indices"])
        return np.asarray(self._tower_jit(pooled))

    def make_step(self, engine) -> Callable:
        import jax
        import jax.numpy as jnp

        lookup = engine.jitted_lookup()
        tower = self._tower_jit

        def step(payloads):
            batch = self.collate(payloads)
            pooled = lookup(engine.packed, jnp.asarray(batch["indices"]))
            return np.asarray(jax.block_until_ready(tower(pooled)))

        step.bag = engine.bag
        return step

    def split(self, out, n: int) -> Sequence:
        return [out[i] for i in range(n)]

    # -- subclass hooks ------------------------------------------------------

    def _init_tower(self, key):  # pragma: no cover - abstract
        raise NotImplementedError

    def _tower(self, params, pooled):  # pragma: no cover - abstract
        raise NotImplementedError

    @property
    def embed_dim(self) -> int:
        return self.workload.tables[0].dim


# --------------------------------------------------------------------------
# DLRM — the paper's model (dense features + pairwise interaction)
# --------------------------------------------------------------------------


class DLRMScenario(_TowerScenario):
    """Facebook-DLRM: bottom MLP on dense features, sum-pooled embedding
    bags, pairwise dot interaction, top MLP (:mod:`repro.models.dlrm`).
    The only scenario with a dense-feature side input."""

    name = "dlrm"

    def __init__(self, workload: Workload, seed: int = 0, n_dense: int = 13):
        from repro.models.dlrm import DLRMConfig

        self.cfg = DLRMConfig(
            arch="dlrm-scenario",
            workload=workload,
            n_dense=n_dense,
            embed_dim=workload.tables[0].dim,
            bottom_mlp=(32, 16),
            top_mlp=(32,),
        )
        super().__init__(workload, seed)

    def _init_tower(self, key):
        from repro.models.dlrm import init_dlrm

        params = init_dlrm(self.cfg, key)
        params.pop("tables")  # scenario tables live in self._tables
        return params

    def _tower(self, params, pooled, dense=None):
        from repro.models.dlrm import _mlp_apply, interact

        bot = _mlp_apply(params["bottom"], dense, final_act=True)
        feat = interact(bot, pooled.astype(bot.dtype))
        return _mlp_apply(params["top"], feat)[..., 0]

    def _build_tower_jit(self):
        import jax

        return jax.jit(
            lambda pooled, dense: self._tower(self.params, pooled, dense)
        )

    # dense side input: override the batch plumbing -------------------------

    def sample_batch(self, rng, distribution, batch: int | None = None) -> dict:
        out = super().sample_batch(rng, distribution, batch)
        b = out["indices"].shape[1]
        out["dense"] = rng.standard_normal((b, self.cfg.n_dense)).astype(
            np.float32
        )
        return out

    def payloads(self, batch: Mapping) -> list:
        idx = np.asarray(batch["indices"])
        dense = np.asarray(batch["dense"])
        return [
            {"indices": idx[:, i], "dense": dense[i]}
            for i in range(idx.shape[1])
        ]

    def collate(self, payloads: Sequence[Mapping]) -> dict:
        return {
            "indices": np.stack(
                [np.asarray(p["indices"]) for p in payloads], axis=1
            ),
            "dense": np.stack([np.asarray(p["dense"]) for p in payloads]),
        }

    def reference_forward(self, batch: Mapping) -> np.ndarray:
        import jax.numpy as jnp

        pooled = self._pooled_reference(batch["indices"])
        return np.asarray(
            self._tower_jit(pooled, jnp.asarray(batch["dense"]))
        )

    def make_step(self, engine) -> Callable:
        import jax
        import jax.numpy as jnp

        lookup = engine.jitted_lookup()
        tower = self._tower_jit

        def step(payloads):
            batch = self.collate(payloads)
            pooled = lookup(engine.packed, jnp.asarray(batch["indices"]))
            return np.asarray(
                jax.block_until_ready(
                    tower(pooled, jnp.asarray(batch["dense"]))
                )
            )

        step.bag = engine.bag
        return step


# --------------------------------------------------------------------------
# MoE — routed expert tower over the feature tokens
# --------------------------------------------------------------------------


class MoEScenario(_TowerScenario):
    """Pooled per-table embeddings as one routing group through a top-k
    capacity-routed MoE layer (:mod:`repro.models.moe`), mean-pooled into a
    linear scoring head.  ``capacity_factor`` is sized so no token drops:
    routing is a pure function of the (bit-exact) pooled embeddings and the
    fused/reference paths route identically."""

    name = "moe"

    def _init_tower(self, key):
        import jax

        from repro.models.layers import dense_init
        from repro.models.moe import MoESpec, moe_init

        self.spec = MoESpec(
            n_experts=4, top_k=2, d_ff=32, capacity_factor=4.0
        )
        k1, k2 = jax.random.split(key)
        return {
            "moe": moe_init(k1, self.embed_dim, self.spec),
            "head": dense_init(k2, (self.embed_dim, 1)),
        }

    def _tower(self, params, pooled):
        from repro.models.moe import moe_apply

        x = pooled.transpose(1, 0, 2)  # (B, N, E) feature tokens
        y, _aux = moe_apply(params["moe"], x, self.spec)
        return (y.mean(axis=1) @ params["head"])[..., 0]


# --------------------------------------------------------------------------
# Mamba2 — recurrent SSD tower over the feature sequence
# --------------------------------------------------------------------------


class Mamba2Scenario(_TowerScenario):
    """The per-query feature sequence scanned by one SSD block
    (:mod:`repro.models.mamba2`): the "user history" shape where the tower
    carries recurrent state across the embedded features.  The last
    position's output feeds the scoring head."""

    name = "mamba2"

    def _init_tower(self, key):
        import jax

        from repro.models.layers import dense_init
        from repro.models.mamba2 import MambaSpec, mamba_init

        self.spec = MambaSpec(
            d_model=self.embed_dim, d_state=16, head_dim=8, chunk=4
        )
        k1, k2 = jax.random.split(key)
        return {
            "mamba": mamba_init(k1, self.spec),
            "head": dense_init(k2, (self.embed_dim, 1)),
        }

    def _tower(self, params, pooled):
        from repro.models.mamba2 import mamba_apply

        u = pooled.transpose(1, 0, 2)  # (B, N, E) feature sequence
        y, _state = mamba_apply(params["mamba"], u, self.spec)
        return (y[:, -1, :] @ params["head"])[..., 0]


# --------------------------------------------------------------------------
# Transformer — pre-norm attention block over the feature tokens
# --------------------------------------------------------------------------


class TransformerScenario(_TowerScenario):
    """One pre-norm self-attention + SwiGLU block
    (:mod:`repro.models.layers`) over the feature tokens, mean-pooled into
    the scoring head — the attention-interaction DLRM variant."""

    name = "transformer"

    def _init_tower(self, key):
        import jax
        import jax.numpy as jnp

        from repro.models.layers import AttnSpec, attn_init, dense_init, mlp_init

        e = self.embed_dim
        self.spec = AttnSpec(
            n_heads=4, n_kv_heads=2, head_dim=8, causal=False, rope=None
        )
        ks = jax.random.split(key, 3)
        return {
            "ln1": jnp.zeros((e,), jnp.float32),
            "attn": attn_init(ks[0], e, self.spec),
            "ln2": jnp.zeros((e,), jnp.float32),
            "mlp": mlp_init(ks[1], e, 32, "swiglu"),
            "head": dense_init(ks[2], (e, 1)),
        }

    def _tower(self, params, pooled):
        import jax.numpy as jnp

        from repro.models.layers import attention, mlp_apply, rms_norm

        x = pooled.transpose(1, 0, 2)  # (B, N, E) feature tokens
        b, n, _ = x.shape
        pos = jnp.broadcast_to(jnp.arange(n, dtype=jnp.int32)[None, :], (b, n))
        a, _cache = attention(
            params["attn"], rms_norm(x, params["ln1"]), self.spec,
            positions=pos,
        )
        h = x + a
        h = h + mlp_apply(params["mlp"], rms_norm(h, params["ln2"]))
        return (h.mean(axis=1) @ params["head"])[..., 0]


# --------------------------------------------------------------------------
# default workloads — distinct embedding/MLP ratios per family
# --------------------------------------------------------------------------


def _default_workload(name: str, cards, batch: int, seqs=None) -> Workload:
    return make_workload(name, cards, dim=16, batch=batch, seqs=seqs)


def make_dlrm_scenario(batch: int = 64, seed: int = 0) -> DLRMScenario:
    """Mid-size CTR mix: one big table, mixed satellites (paper shape)."""
    return DLRMScenario(
        _default_workload("dlrm-ctr", [4000, 1500, 600, 250], batch), seed
    )


def make_moe_scenario(batch: int = 64, seed: int = 0) -> MoEScenario:
    """Embedding-heavy: one oversized table dominates the bytes."""
    return MoEScenario(
        _default_workload("moe-ranker", [30000, 2000, 500, 120], batch), seed
    )


def make_mamba2_scenario(batch: int = 64, seed: int = 0) -> Mamba2Scenario:
    """History-shaped: many medium tables (a long feature sequence)."""
    return Mamba2Scenario(
        _default_workload(
            "mamba2-session",
            [3000, 3000, 2000, 2000, 800, 800, 200, 200],
            batch,
        ),
        seed,
    )


def make_transformer_scenario(
    batch: int = 64, seed: int = 0
) -> TransformerScenario:
    """MLP-heavy: smaller tables, the tower dominates the FLOPs."""
    return TransformerScenario(
        _default_workload(
            "transformer-ctr", [12000, 6000, 1500, 400, 120, 80], batch
        ),
        seed,
    )
