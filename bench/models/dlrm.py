"""The DLRM kind: weights from the seed, the served step through the
program's API, the plain reference, and the work a batch requires.

The reference is written here from the published DLRM description
(facebookresearch/dlrm, Naumov et al. 2019): sum-pooled embedding bags, a
bottom MLP with ReLU after every layer, the pairwise dot interaction of the
bottom output with every pooled vector (strict upper triangle, row by row,
after the bottom output itself), and a top MLP with ReLU between layers
whose last layer gives the logit.  It imports nothing of the program and
runs on the tables and tower weights this file made from the seed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# What decides ``correct`` for this kind: the widest gap between a served
# logit and the reference's, over every answer the window produced, as a
# share of the reference logits' standard deviation.  PERF.md gives the
# readings the limit was set from.
LOGIT_GAP_LIMIT = 1e-3


def key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size."""
    seed = int(seed)
    k = jax.random.PRNGKey(abs(seed) & 0xFFFFFFFF)
    for word in (abs(seed) >> 32, int(seed < 0)):
        k = jax.random.fold_in(k, word & 0xFFFFFFFF)
    return k


def tower_dims(cfg: dict) -> tuple[list[int], list[int]]:
    n_tables = len(cfg["tables"]["rows"])
    e = cfg["embed_dim"]
    n_int = n_tables + 1
    bottom = [cfg["n_dense"], *cfg["bottom_mlp"], e]
    top = [e + n_int * (n_int - 1) // 2, *cfg["top_mlp"], 1]
    return bottom, top


def make_weights(cfg: dict, seed: int) -> dict:
    """Tables and tower from the seed, on the device, in one jitted call,
    in float32: tables N(0, 1/E); each layer's weights N(0, 1/fan_in) and
    biases N(0, 0.01**2)."""
    rows = [int(r) for r in cfg["tables"]["rows"]]
    e = int(cfg["embed_dim"])
    bottom, top = tower_dims(cfg)

    def mlp(k, dims):
        ks = jax.random.split(k, 2 * (len(dims) - 1))
        return [
            {
                "w": jax.random.normal(ks[2 * i], (a, b), jnp.float32) / np.sqrt(a),
                "b": 0.01 * jax.random.normal(ks[2 * i + 1], (b,), jnp.float32),
            }
            for i, (a, b) in enumerate(zip(dims[:-1], dims[1:]))
        ]

    @jax.jit
    def init(k):
        kt, kb, ktop = jax.random.split(k, 3)
        tables = [
            jax.random.normal(kk, (m, e), jnp.float32) / np.sqrt(e)
            for kk, m in zip(jax.random.split(kt, len(rows)), rows)
        ]
        return {"tables": tables, "bottom": mlp(kb, bottom), "top": mlp(ktop, top)}

    return jax.block_until_ready(init(key(seed)))


def program_workload(cfg: dict):
    """The table set as the program's planner takes it."""
    from repro.core.tables import make_workload

    t = cfg["tables"]
    return make_workload(
        cfg["name"], t["rows"], dim=cfg["embed_dim"], seqs=t["seq"],
        batch=cfg["max_batch"],
    )


def serve(cfg: dict, weights: dict, freqs, mesh, max_wait_s: float):
    """The served path, built through the program's API with the engine's
    defaults: ``InferenceEngine.build`` with the key histogram the
    deployment counted, the DLRM step of ``launch/serve.py``, and
    ``engine.serve()``.  Returns the server."""
    from repro.data.distributions import RowProbs
    from repro.engine import EngineConfig, InferenceEngine
    from repro.launch.serve import dlrm_step_maker
    from repro.models.dlrm import DLRMConfig

    wl = program_workload(cfg)
    probs = [RowProbs.from_counts(ids, n, t.rows) for (ids, n), t in zip(freqs, wl.tables)]
    engine = InferenceEngine.build(
        weights["tables"], wl,
        EngineConfig(mesh_shape=tuple(cfg["mesh_shape"]), max_batch=cfg["max_batch"]),
        mesh=mesh, freqs=probs,
    )
    dcfg = DLRMConfig(
        arch=cfg["name"], workload=wl, n_dense=cfg["n_dense"],
        embed_dim=cfg["embed_dim"], bottom_mlp=tuple(cfg["bottom_mlp"]),
        top_mlp=tuple(cfg["top_mlp"]),
    )
    make_step = dlrm_step_maker(dcfg, {k: weights[k] for k in ("bottom", "top")})
    return engine.serve(
        make_step=make_step, split_fn=lambda out, n: list(out[:n]),
        max_batch=cfg["max_batch"], max_wait_s=max_wait_s,
    )


def step_hlo(srv, indices: np.ndarray, dense: np.ndarray) -> str:
    """The compiled text of the served step: the names of the device ops the
    trace holds, with each one's op-name path."""
    return srv.step_fn.lower({"dense": dense, "indices": indices}).compile().as_text()


def payloads(indices: np.ndarray, dense: np.ndarray) -> list[dict]:
    """One request per query, as a client sends it."""
    return [{"dense": dense[q], "indices": indices[:, q]} for q in range(dense.shape[0])]


def reference(cfg: dict, weights: dict, indices: np.ndarray, dense: np.ndarray, *, low: bool = False):
    """Logits of one batch, by the plain forward pass: (B,) float32.

    ``low=True`` is the control: the same pass with every table, weight and
    activation in bfloat16, the precision a later change might be tempted
    to serve in."""
    fwd = _forward(cfg["tower_matmul_precision"], low)
    return np.asarray(fwd(weights, jnp.asarray(indices), jnp.asarray(dense)))


@functools.cache
def _forward(precision: str, low: bool):
    dt = jnp.bfloat16 if low else jnp.float32
    prec = {"default": jax.lax.Precision.DEFAULT, "highest": jax.lax.Precision.HIGHEST}[precision]

    def layer_stack(layers, x, relu_last):
        for i, l in enumerate(layers):
            x = jnp.dot(x, l["w"].astype(dt), precision=prec) + l["b"].astype(dt)
            if relu_last or i < len(layers) - 1:
                x = jnp.maximum(x, 0)
        return x

    @jax.jit
    def fwd(w, idx, x):
        pooled = []
        for t, tab in enumerate(w["tables"]):
            ids = idx[t]  # (B, s), -1 = no lookup
            rows = jnp.take(tab.astype(dt), jnp.maximum(ids, 0), axis=0)
            pooled.append(jnp.sum(jnp.where((ids >= 0)[..., None], rows, 0), axis=1))
        bot = layer_stack(w["bottom"], x.astype(dt), True)  # (B, E)
        feats = jnp.stack([bot] + pooled, axis=1)  # (B, T+1, E)
        z = jnp.einsum("bie,bje->bij", feats, feats, precision=prec)
        n = feats.shape[1]
        iu, ju = np.triu_indices(n, k=1)
        top_in = jnp.concatenate([bot, z[:, iu, ju]], axis=1)
        return layer_stack(w["top"], top_in, False)[:, 0].astype(jnp.float32)

    return fwd


def logit_gap(served: np.ndarray, want: np.ndarray) -> float:
    """The widest gap between served and reference logits, as a share of the
    reference logits' standard deviation."""
    return float(np.max(np.abs(served - want)) / max(float(np.std(want)), 1e-30))


# --- work a batch requires, whatever implements it -----------------------


def lookup_bytes(cfg: dict, distinct: list[int], batch: int) -> float:
    """Bytes a batch's lookups must move: each distinct row once, every
    index once, every pooled vector once."""
    e = cfg["embed_dim"]
    item = {"float32": 4, "bfloat16": 2, "float16": 2}[cfg["table_dtype"]]
    seqs = cfg["tables"]["seq"]
    return float(sum(distinct) * e * item + batch * sum(seqs) * 4 + batch * len(seqs) * e * 4)


def lookup_flops(cfg: dict, batch: int) -> float:
    """Adds of the pooling: every looked-up row into its bag."""
    return float(batch * sum(cfg["tables"]["seq"]) * cfg["embed_dim"])


def tower_macs(cfg: dict) -> int:
    """Multiply-adds of one query's tower: bottom MLP, the dot products of
    the (T+1) T / 2 distinct pairs of the interaction, top MLP."""
    bottom, top = tower_dims(cfg)
    n = len(cfg["tables"]["rows"]) + 1
    macs = sum(a * b for a, b in zip(bottom[:-1], bottom[1:]))
    macs += n * (n - 1) // 2 * cfg["embed_dim"]
    macs += sum(a * b for a, b in zip(top[:-1], top[1:]))
    return macs


def query_flops(cfg: dict) -> float:
    return 2.0 * tower_macs(cfg)
