"""DLRM (Deep Learning Recommendation Model) — the paper's model family.

Facebook-DLRM structure (Gupta et al., HPCA'20): dense features through a
bottom MLP, categorical features through embedding bags (sum-pooled), pairwise
dot-product feature interaction, top MLP to the CTR logit.

Two execution paths share the math:
* ``forward_dense``  — plain single-device lookups (training, tests);
* ``forward_packed`` — the paper's partitioned execution: embeddings come out
  of :func:`core.partition.partitioned_lookup` over a placement plan.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.embedding import PartitionedEmbeddingBag, stack_indices
from repro.core.tables import Workload
from repro.models.layers import dense_init

Params = dict


@dataclasses.dataclass(frozen=True)
class DLRMConfig:
    arch: str
    workload: Workload
    n_dense: int = 13
    embed_dim: int = 16
    bottom_mlp: tuple[int, ...] = (512, 256, 64)
    top_mlp: tuple[int, ...] = (512, 256)
    family: str = "dlrm"

    @property
    def n_tables(self) -> int:
        return len(self.workload.tables)

    def param_count(self) -> int:
        n = sum(t.rows * t.dim for t in self.workload.tables)
        dims = [self.n_dense, *self.bottom_mlp, self.embed_dim]
        n += sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
        n_int = self.n_tables + 1
        top_in = self.embed_dim + n_int * (n_int - 1) // 2
        dims = [top_in, *self.top_mlp, 1]
        n += sum(a * b + b for a, b in zip(dims[:-1], dims[1:]))
        return n


def _mlp_init(key, dims: Sequence[int]) -> list[Params]:
    ks = jax.random.split(key, len(dims) - 1)
    return [
        {"w": dense_init(k, (a, b)), "b": jnp.zeros((b,), jnp.float32)}
        for k, a, b in zip(ks, dims[:-1], dims[1:])
    ]


def _mlp_apply(layers: list[Params], x: jax.Array, final_act: bool = False):
    for i, l in enumerate(layers):
        x = x @ l["w"].astype(x.dtype) + l["b"].astype(x.dtype)
        if i < len(layers) - 1 or final_act:
            x = jax.nn.relu(x)
    return x


def init_dlrm(cfg: DLRMConfig, rng: jax.Array) -> Params:
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    tables = [
        jax.random.normal(k, (t.rows, t.dim), jnp.float32) / jnp.sqrt(float(t.dim))
        for k, t in zip(
            jax.random.split(k1, cfg.n_tables), cfg.workload.tables
        )
    ]
    bottom = _mlp_init(k2, [cfg.n_dense, *cfg.bottom_mlp, cfg.embed_dim])
    n_int = cfg.n_tables + 1
    top_in = cfg.embed_dim + n_int * (n_int - 1) // 2
    top = _mlp_init(k3, [top_in, *cfg.top_mlp, 1])
    return {"tables": tables, "bottom": bottom, "top": top}


def interact(bottom_out: jax.Array, emb: jax.Array) -> jax.Array:
    """Pairwise dot interaction. bottom_out (B, E), emb (N, B, E) -> (B, F)."""
    feats = jnp.concatenate([bottom_out[None], emb], axis=0)  # (N+1, B, E)
    feats = feats.transpose(1, 0, 2)  # (B, N+1, E)
    z = jnp.einsum("bne,bme->bnm", feats, feats)
    n = feats.shape[1]
    iu, ju = jnp.triu_indices(n, k=1)
    pairs = z[:, iu, ju]  # (B, n(n-1)/2)
    return jnp.concatenate([bottom_out, pairs], axis=-1)


def forward_dense(cfg: DLRMConfig, params: Params, batch: dict) -> jax.Array:
    """batch: {"dense": (B, n_dense) f32, "indices": (N, B, s_max) i32}."""
    x = batch["dense"]
    idx = batch["indices"]
    outs = []
    for i, tab in enumerate(params["tables"]):
        ii = idx[i]
        valid = ii >= 0
        g = jnp.take(tab, jnp.where(valid, ii, 0), axis=0)
        g = jnp.where(valid[..., None], g, jnp.zeros_like(g))
        outs.append(g.sum(axis=1))
    emb = jnp.stack(outs)  # (N, B, E)
    bot = _mlp_apply(params["bottom"], x, final_act=True)
    feat = interact(bot, emb.astype(bot.dtype))
    return _mlp_apply(params["top"], feat)[..., 0]  # (B,) logits


def forward_packed(
    cfg: DLRMConfig,
    bag: PartitionedEmbeddingBag,
    packed,
    mlp_params: Params,
    batch: dict,
    *,
    mesh,
    axis: str = "model",
    batch_axes: tuple[str, ...] = (),
    use_kernels="fused",
    reduce_mode: str = "sparse",
) -> jax.Array:
    """The paper's partitioned serving path (fused streaming executor +
    owner-sharded sparse rejoin by default).  The tower's device ops carry
    the ``tower`` name scope, so a profiler trace can tell them apart."""
    emb = bag.apply(
        packed,
        batch["indices"],
        mesh=mesh,
        axis=axis,
        batch_axes=batch_axes,
        use_kernels=use_kernels,
        reduce_mode=reduce_mode,
    )  # (N, B, E) f32
    with jax.named_scope("tower"):
        bot = _mlp_apply(mlp_params["bottom"], batch["dense"], final_act=True)
        feat = interact(bot, emb.astype(bot.dtype))
        return _mlp_apply(mlp_params["top"], feat)[..., 0]


def bce_loss(logits: jax.Array, labels: jax.Array) -> jax.Array:
    z = logits.astype(jnp.float32)
    y = labels.astype(jnp.float32)
    return jnp.mean(jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))))


def make_dlrm_train_step(cfg: DLRMConfig, optimizer):
    def loss_fn(params, batch):
        logits = forward_dense(cfg, params, batch)
        return bce_loss(logits, batch["labels"])

    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        params, opt_state = optimizer.update(grads, opt_state, params)
        return params, opt_state, {"loss": loss}

    return train_step
