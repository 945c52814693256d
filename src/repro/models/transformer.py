"""Family stacks: dense / MoE / SSM / hybrid / enc-dec / VLM LMs.

One generic implementation parameterized by :class:`ArchConfig`:

* layer parameters are *stacked* ``(L, ...)`` and the stack runs under
  ``lax.scan`` (small HLO, fast SPMD compile) with per-layer ``jax.checkpoint``
  for training;
* the token embedding (and its transpose direction, the LM head) is the
  paper's lookup-table component: when a :class:`ShardCtx` is given the
  embedding runs *vocab-parallel* through ``core.partition.vocab_parallel_embed``
  (chunk offset-subtract + clip + psum — the paper's asymmetric chunking,
  pool-free case);
* serve paths use scalar-position KV caches (linear, or rolling for
  sliding-window archs) and the chunked online-softmax attention.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeCfg
from repro.core.partition import vocab_parallel_embed
from repro.models import layers as L
from repro.models.layers import AttnSpec, Params
from repro.models.mamba2 import (
    MambaSpec,
    mamba_apply,
    mamba_decode_step,
    mamba_init,
    mamba_init_state,
)
from repro.models.moe import moe_apply, moe_init

AUX_LOSS_WEIGHT = 0.01


@dataclasses.dataclass(frozen=True)
class ShardCtx:
    """Mesh context threaded through model code (None = single device)."""

    mesh: Any
    model_axis: str = "model"
    data_axes: tuple[str, ...] = ("data",)
    shard_batch: bool = True

    @property
    def batch_spec(self):
        return self.data_axes if self.shard_batch else None


def attn_spec(cfg: ArchConfig, *, causal: bool = True, window_on: bool = True) -> AttnSpec:
    return AttnSpec(
        n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim,
        causal=causal,
        window=cfg.window if window_on else None,
        qk_norm=cfg.qk_norm,
        rope=cfg.rope,
        rope_base=cfg.rope_base,
        rotary_frac=cfg.rotary_frac,
        mrope_sections=cfg.mrope_sections,
        attn_block=cfg.attn_block,
    )


# ==========================================================================
# parameter init
# ==========================================================================


def _stacked(init_fn: Callable, key, n: int):
    return jax.vmap(init_fn)(jax.random.split(key, n))


def _dense_layer_init(cfg: ArchConfig, key) -> Params:
    ks = jax.random.split(key, 4)
    norm_init, _ = L.make_norm(cfg.norm, cfg.d_model)
    p = {
        "ln1": norm_init(ks[0]),
        "attn": L.attn_init(ks[1], cfg.d_model, attn_spec(cfg)),
        "ln2": norm_init(ks[2]),
    }
    if cfg.moe is not None:
        p["moe"] = moe_init(ks[3], cfg.d_model, cfg.moe)
    else:
        p["mlp"] = L.mlp_init(ks[3], cfg.d_model, cfg.d_ff, cfg.mlp)
    return p


def _mamba_layer_init(cfg: ArchConfig, key) -> Params:
    ks = jax.random.split(key, 2)
    norm_init, _ = L.make_norm(cfg.norm, cfg.d_model)
    return {"ln": norm_init(ks[0]), "mamba": mamba_init(ks[1], cfg.ssm)}


def _shared_block_init(cfg: ArchConfig, key) -> Params:
    """Zamba2 shared attention block at width 2*d (concat(h, emb0))."""
    d2 = 2 * cfg.d_model
    ks = jax.random.split(key, 6)
    norm_init, _ = L.make_norm(cfg.norm, d2)
    spec = attn_spec(cfg)
    return {
        "ln1": norm_init(ks[0]),
        "attn": L.attn_init(ks[1], d2, spec),
        "ln2": norm_init(ks[2]),
        "mlp": L.mlp_init(ks[3], d2, cfg.d_ff, cfg.mlp),
        "proj_out": L.dense_init(ks[4], (d2, cfg.d_model)),
    }


def _encdec_layer_init(cfg: ArchConfig, key, *, cross: bool) -> Params:
    ks = jax.random.split(key, 6)
    norm_init, _ = L.make_norm(cfg.norm, cfg.d_model)
    p = {
        "ln1": norm_init(ks[0]),
        "attn": L.attn_init(ks[1], cfg.d_model, attn_spec(cfg)),
        "ln2": norm_init(ks[2]),
        "mlp": L.mlp_init(ks[3], cfg.d_model, cfg.d_ff, cfg.mlp),
    }
    if cross:
        p["ln_x"] = norm_init(ks[4])
        p["xattn"] = L.attn_init(ks[5], cfg.d_model, attn_spec(cfg, causal=False))
    return p


def init_params(cfg: ArchConfig, rng: jax.Array) -> Params:
    ks = jax.random.split(rng, 8)
    vpad = cfg.vocab_padded
    d = cfg.d_model
    norm_init, _ = L.make_norm(cfg.norm, d)
    p: Params = {"final_norm": norm_init(ks[0])}
    if cfg.vocab:
        p["embed"] = L.embed_init(ks[1], (vpad, d))
        p["lm_head"] = L.dense_init(ks[2], (d, vpad))

    if cfg.family in ("dense", "moe", "vlm"):
        p["layers"] = _stacked(
            functools.partial(_dense_layer_init, cfg), ks[3], cfg.n_layers
        )
    elif cfg.family == "ssm":
        p["layers"] = _stacked(
            functools.partial(_mamba_layer_init, cfg), ks[3], cfg.n_layers
        )
    elif cfg.family == "hybrid":
        p["layers"] = _stacked(
            functools.partial(_mamba_layer_init, cfg), ks[3], cfg.n_layers
        )
        p["shared"] = _shared_block_init(cfg, ks[4])
    elif cfg.family == "encdec":
        p["enc_layers"] = _stacked(
            functools.partial(_encdec_layer_init, cfg, cross=False),
            ks[3],
            cfg.enc_layers,
        )
        p["layers"] = _stacked(
            functools.partial(_encdec_layer_init, cfg, cross=True),
            ks[4],
            cfg.n_layers,
        )
        p["enc_final_norm"] = norm_init(ks[5])
        p["pos_emb"] = L.embed_init(ks[6], (cfg.max_target_positions, d))
    else:
        raise ValueError(cfg.family)
    return p


# ==========================================================================
# embedding / head (the paper's lookup component)
# ==========================================================================


def embed_tokens(cfg: ArchConfig, params: Params, tokens: jax.Array, ctx: ShardCtx | None):
    if ctx is None:
        return jnp.take(params["embed"], tokens, axis=0)
    fn = jax.shard_map(
        lambda tab, tok: vocab_parallel_embed(tab, tok, ctx.model_axis),
        mesh=ctx.mesh,
        in_specs=(P(ctx.model_axis, None), P(ctx.batch_spec, None)),
        out_specs=P(ctx.batch_spec, None, None),
        check_vma=False,
    )
    return fn(params["embed"], tokens)


def lm_logits(cfg: ArchConfig, params: Params, h: jax.Array) -> jax.Array:
    return h @ params["lm_head"].astype(h.dtype)


def ce_loss(cfg: ArchConfig, logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Masked CE over the padded vocab; labels < 0 are ignored."""
    logits = logits.astype(jnp.float32)
    vpad = logits.shape[-1]
    if vpad != cfg.vocab:
        vmask = jnp.arange(vpad) < cfg.vocab
        logits = jnp.where(vmask, logits, -1e30)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(
        logits, jnp.maximum(labels, 0)[..., None], axis=-1
    )[..., 0]
    loss = lse - ll
    mask = (labels >= 0).astype(jnp.float32)
    return (loss * mask).sum() / jnp.maximum(mask.sum(), 1.0)


# ==========================================================================
# blocks
# ==========================================================================


def _norm(cfg: ArchConfig, p, x, d=None):
    _, apply = L.make_norm(cfg.norm, d or cfg.d_model)
    return apply(p, x)


def _sp_constrain(ctx: "ShardCtx | None", h: jax.Array, cfg: "ArchConfig | None" = None):
    """Megatron-style sequence parallelism on the residual stream: between
    layers (and in the remat-saved layer inputs — the dominant train-memory
    term) the hidden states live (batch x seq/TP x d); attention/MLP gather
    the seq dim locally.  Cuts the checkpointed-activation stack by the TP
    degree at the cost of per-layer seq all-gathers."""
    if ctx is None or h.ndim != 3 or (cfg is not None and not cfg.seq_parallel):
        return h
    tp = ctx.mesh.shape[ctx.model_axis]
    if h.shape[1] % tp != 0:
        return h
    return jax.lax.with_sharding_constraint(
        h,
        jax.sharding.NamedSharding(
            ctx.mesh, P(ctx.batch_spec, ctx.model_axis, None)
        ),
    )


def _moe_constrain(ctx: "ShardCtx | None"):
    """Expert-parallel sharding constraints for the expert GEMMs.

    Dispatch output ``xe (G,E,C,d)`` is re-sharded from token(G)-sharded to
    expert(E)-sharded — an all-to-all (the EP dispatch).  Expert weights live
    E-over-"data" x ff-over-"model" (see sharding.param_spec), so the GEMMs
    are fully local in E and psum only small ff-partials.  ``ye`` re-shards
    back to token-sharded before the combine (the EP return all-to-all).

    (First attempt replicated ``xe`` — refuted: every device then holds and
    computes ALL tokens' expert inputs; peak memory 3-10x worse.  Logged in
    EXPERIMENTS.md §Perf.)
    """
    if ctx is None:
        return None
    pod = "pod" if "pod" in ctx.data_axes else None
    g_shard = tuple(ctx.data_axes) if ctx.shard_batch else None
    # two back-to-back constraints pin the all-to-all *between* them —
    # a single E-sharded constraint propagates backward into the dispatch
    # einsum and all-gathers the one-hots to global size (measured: 2.5 GiB
    # per tensor on granite train; logged in EXPERIMENTS.md §Perf).
    specs = {
        "xe": [P(g_shard, None, None, None), P(pod, "data", None, None)],
        "h": [P(pod, "data", None, ctx.model_axis)],
        "ye": [P(pod, "data", None, None), P(g_shard, None, None, None)],
    }

    def constrain(name, x):
        for spec in specs[name]:
            x = jax.lax.with_sharding_constraint(
                x, jax.sharding.NamedSharding(ctx.mesh, spec)
            )
        return x

    return constrain


def dense_block(
    cfg: ArchConfig,
    p: Params,
    h: jax.Array,
    positions,
    *,
    cache=None,
    cache_pos=None,
    cache_mode="linear",
    q_chunk=None,
    ctx=None,
):
    a, new_cache = L.attention(
        p["attn"],
        _norm(cfg, p["ln1"], h),
        attn_spec(cfg),
        positions=positions,
        kv_cache=cache,
        cache_pos=cache_pos,
        cache_mode=cache_mode,
        q_chunk=q_chunk,
    )
    h = h + a
    m_in = _norm(cfg, p["ln2"], h)
    if cfg.moe is not None:
        mo, aux = moe_apply(p["moe"], m_in, cfg.moe, constrain=_moe_constrain(ctx))
    else:
        mo, aux = L.mlp_apply(p["mlp"], m_in, cfg.mlp), jnp.zeros((), jnp.float32)
    return h + mo, new_cache, aux


def shared_block(
    cfg: ArchConfig,
    p: Params,
    h: jax.Array,
    emb0: jax.Array,
    positions,
    *,
    cache=None,
    cache_pos=None,
    q_chunk=None,
):
    """Zamba2 shared attention block at width 2d."""
    g = jnp.concatenate([h, emb0], axis=-1)
    a, new_cache = L.attention(
        p["attn"],
        _norm(cfg, p["ln1"], g, 2 * cfg.d_model),
        attn_spec(cfg),
        positions=positions,
        kv_cache=cache,
        cache_pos=cache_pos,
        q_chunk=q_chunk,
    )
    g = g + a
    g = g + L.mlp_apply(p["mlp"], _norm(cfg, p["ln2"], g, 2 * cfg.d_model), cfg.mlp)
    return h + g @ p["proj_out"].astype(h.dtype), new_cache


# ==========================================================================
# full-sequence forward (train / prefill)
# ==========================================================================


def _positions_default(batch_sz: int, seq: int, offset: int = 0):
    return jnp.broadcast_to(jnp.arange(seq, dtype=jnp.int32)[None, :], (batch_sz, seq)) + offset


def forward_seq(
    cfg: ArchConfig,
    params: Params,
    batch: dict,
    ctx: ShardCtx | None,
    *,
    want_cache: ShapeCfg | None = None,
    remat: bool = False,
):
    """Full-sequence forward.

    Returns (hidden (B,S,d), aux_loss, caches or None).  ``want_cache`` (a
    decode ShapeCfg) makes the serve caches be built (prefill path).
    """
    if cfg.family == "encdec":
        cap = _cache_capacity(cfg, want_cache) if want_cache is not None else 0
        return _encdec_forward(
            cfg, params, batch, ctx, want_cache is not None, cap,
            remat=remat, q_chunk=cfg.q_chunk,
        )
    if cfg.input_kind == "embeds":
        h = batch["embeds"]
        bsz, seq, _ = h.shape
    else:
        tokens = batch["tokens"]
        bsz, seq = tokens.shape
        h = embed_tokens(cfg, params, tokens, ctx)
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    h = h.astype(compute_dtype)
    positions = batch.get("positions")
    if positions is None:
        positions = _positions_default(bsz, seq)
    q_chunk = cfg.q_chunk if seq > cfg.q_chunk else None

    build_cache = want_cache is not None
    cap = _cache_capacity(cfg, want_cache) if build_cache else 0

    if cfg.family in ("dense", "moe", "vlm"):
        spec = attn_spec(cfg)

        def body(carry, lp):
            hh, aux = carry
            hh = _sp_constrain(ctx, hh, cfg) if remat else hh
            kv_out = None
            if build_cache:
                kv_out = _extract_kv(cfg, spec, lp["attn"],
                                     _norm(cfg, lp["ln1"], hh), positions, cap)
            hh, _, aux_l = dense_block(
                cfg, lp, hh, positions, q_chunk=q_chunk, ctx=ctx
            )
            hh = _sp_constrain(ctx, hh, cfg) if remat else hh
            return (hh, aux + aux_l), kv_out

        blk = jax.checkpoint(body) if remat and not build_cache else body
        (h, aux), kvs = lax.scan(
            blk, (h, jnp.zeros((), jnp.float32)), params["layers"]
        )
        caches = None
        if build_cache:
            caches = {"k": kvs[0], "v": kvs[1], "pos": jnp.asarray(seq, jnp.int32)}
        h = _norm(cfg, params["final_norm"], h)
        return h, aux, caches

    if cfg.family == "ssm":

        def body(carry, lp):
            hh = carry
            hh = _sp_constrain(ctx, hh, cfg) if remat else hh
            out, st = mamba_apply(
                lp["mamba"], _norm(cfg, lp["ln"], hh), cfg.ssm,
                state=mamba_init_state(cfg.ssm, bsz, compute_dtype) if build_cache else None,
            )
            return hh + out, st

        blk = jax.checkpoint(body) if remat and not build_cache else body
        h, states = lax.scan(blk, h, params["layers"])
        h = _norm(cfg, params["final_norm"], h)
        caches = None
        if build_cache:
            caches = {"conv": states[0], "ssm": states[1], "pos": jnp.asarray(seq, jnp.int32)}
        return h, jnp.zeros((), jnp.float32), caches

    if cfg.family == "hybrid":
        return _hybrid_forward(cfg, params, h, positions, build_cache, cap, remat=remat, q_chunk=q_chunk, ctx=ctx)

    raise ValueError(cfg.family)


def _cache_capacity(cfg: ArchConfig, shape: ShapeCfg) -> int:
    if cfg.window is not None:
        return min(cfg.window, shape.seq)
    return shape.seq


def _extract_kv(cfg, spec, attn_p, x, positions, cap):
    """Compute cache-ready (rope-rotated, packed) K/V for one layer.

    Recomputes the K/V projections (~5% extra prefill FLOPs) to keep the main
    attention path unchanged; packed to ``cap`` slots (rolling for SWA).
    """
    bsz, seq = x.shape[0], x.shape[1]
    dt = x.dtype
    kvh, dh = spec.n_kv_heads, spec.head_dim
    k = (x @ attn_p["wk"].astype(dt)).reshape(bsz, seq, kvh, dh)
    v = (x @ attn_p["wv"].astype(dt)).reshape(bsz, seq, kvh, dh)
    if spec.qk_norm:
        k = L.rms_norm(k, attn_p["k_norm"])
    if spec.rope is not None:
        k = L.apply_rope(
            k, positions, base=spec.rope_base,
            rotary_frac=spec.rotary_frac, mrope_sections=spec.mrope_sections,
        )
    return _pack_cache(cfg, k, cap), _pack_cache(cfg, v, cap)


def _pack_cache(cfg: ArchConfig, kv: jax.Array, cap: int) -> jax.Array:
    """(B, S, KV, dh) -> (B, cap, KV, dh); rolling layout for SWA archs."""
    seq = kv.shape[1]
    if cfg.window is None or seq <= cap:
        if seq == cap:
            return kv
        out = jnp.zeros((kv.shape[0], cap, *kv.shape[2:]), kv.dtype)
        return lax.dynamic_update_slice(out, kv, (0, 0, 0, 0))
    # rolling: slot j holds the last position p < seq with p % cap == j.
    j = jnp.arange(cap)
    p = seq - 1 - ((seq - 1 - j) % cap)
    return jnp.take(kv, p, axis=1)


def _hybrid_forward(cfg, params, h, positions, build_cache, cap, *, remat, q_chunk, ctx=None):
    bsz, seq = h.shape[0], h.shape[1]
    compute_dtype = h.dtype
    emb0 = h
    every = cfg.shared_attn_every
    n_super = cfg.n_layers // every
    n_rest = cfg.n_layers - n_super * every
    spec = attn_spec(cfg)
    aux0 = jnp.zeros((), jnp.float32)

    grouped = jax.tree.map(
        lambda a: a[: n_super * every].reshape(n_super, every, *a.shape[1:]),
        params["layers"],
    )
    rest = jax.tree.map(lambda a: a[n_super * every :], params["layers"])

    def mamba_body(carry, lp):
        hh = carry
        hh = _sp_constrain(ctx, hh, cfg) if remat else hh
        out, st = mamba_apply(
            lp["mamba"], _norm(cfg, lp["ln"], hh), cfg.ssm,
            state=mamba_init_state(cfg.ssm, bsz, compute_dtype) if build_cache else None,
        )
        return hh + out, st

    mb = jax.checkpoint(mamba_body) if remat and not build_cache else mamba_body

    def super_body(carry, lps):
        hh = carry
        hh, states = lax.scan(mb, hh, lps)
        # shared attention block (weights shared; cache per invocation)
        kv_out = None
        if build_cache:
            x = _norm(cfg, params["shared"]["ln1"],
                      jnp.concatenate([hh, emb0], axis=-1), 2 * cfg.d_model)
            kv_out = _extract_kv(cfg, spec, params["shared"]["attn"], x,
                                 positions, cap)
        hh, _ = shared_block(cfg, params["shared"], hh, emb0, positions, q_chunk=q_chunk)
        out = (states, kv_out) if build_cache else None
        return hh, out

    sb = jax.checkpoint(super_body) if remat and not build_cache else super_body
    h, sup_out = lax.scan(sb, h, grouped)
    if n_rest:
        h, rest_states = lax.scan(mb, h, rest)
    h = _norm(cfg, params["final_norm"], h)

    caches = None
    if build_cache:
        states, (ks, vs) = sup_out
        conv = states[0].reshape(n_super * every, *states[0].shape[2:])
        ssm = states[1].reshape(n_super * every, *states[1].shape[2:])
        if n_rest:
            conv = jnp.concatenate([conv, rest_states[0]], axis=0)
            ssm = jnp.concatenate([ssm, rest_states[1]], axis=0)
        caches = {
            "conv": conv,
            "ssm": ssm,
            "shared_k": ks,
            "shared_v": vs,
            "pos": jnp.asarray(seq, jnp.int32),
        }
    return h, aux0, caches


def _encdec_forward(cfg, params, batch, ctx, build_cache, cap, *, remat, q_chunk):
    frames = batch["frames"]  # (B, S_enc, d) stubbed modality frontend
    bsz, s_enc, _ = frames.shape
    compute_dtype = jnp.dtype(cfg.compute_dtype)
    frames = frames.astype(compute_dtype)
    enc_h = frames + L.sinusoidal_positions(s_enc, cfg.d_model, compute_dtype)[None]
    enc_pos = _positions_default(bsz, s_enc)
    enc_spec = attn_spec(cfg, causal=False)

    def enc_body(carry, lp):
        hh = carry
        hh = _sp_constrain(ctx, hh, cfg) if remat else hh
        a, _ = L.attention(lp["attn"], _norm(cfg, lp["ln1"], hh), enc_spec,
                           positions=enc_pos, q_chunk=q_chunk)
        hh = hh + a
        hh = hh + L.mlp_apply(lp["mlp"], _norm(cfg, lp["ln2"], hh), cfg.mlp)
        return hh, None

    eb = jax.checkpoint(enc_body) if remat else enc_body
    enc_h, _ = lax.scan(eb, enc_h, params["enc_layers"])
    enc_h = _norm(cfg, params["enc_final_norm"], enc_h)

    tokens = batch["tokens"]
    s_dec = tokens.shape[1]
    h = embed_tokens(cfg, params, tokens, ctx).astype(compute_dtype)
    h = h + params["pos_emb"][None, :s_dec].astype(compute_dtype)
    pos = _positions_default(bsz, s_dec)
    spec = attn_spec(cfg)
    xspec = attn_spec(cfg, causal=False)

    def dec_body(carry, lp):
        hh = carry
        hh = _sp_constrain(ctx, hh, cfg) if remat else hh
        cache_out = None
        if build_cache:
            x = _norm(cfg, lp["ln1"], hh)
            kc, vc = _extract_kv(cfg, spec, lp["attn"], x, pos, cap)
            dt = x.dtype
            kvh, dh = spec.n_kv_heads, spec.head_dim
            ck = (enc_h @ lp["xattn"]["wk"].astype(dt)).reshape(bsz, s_enc, kvh, dh)
            cv = (enc_h @ lp["xattn"]["wv"].astype(dt)).reshape(bsz, s_enc, kvh, dh)
            cache_out = (kc, vc, ck, cv)
        a, _ = L.attention(lp["attn"], _norm(cfg, lp["ln1"], hh), spec,
                           positions=pos, q_chunk=q_chunk)
        hh = hh + a
        xa, _ = L.attention(lp["xattn"], _norm(cfg, lp["ln_x"], hh), xspec,
                            positions=pos, kv_x=enc_h, q_chunk=q_chunk)
        hh = hh + xa
        hh = hh + L.mlp_apply(lp["mlp"], _norm(cfg, lp["ln2"], hh), cfg.mlp)
        return hh, cache_out

    db = jax.checkpoint(dec_body) if remat and not build_cache else dec_body
    h, cache_ys = lax.scan(db, h, params["layers"])

    caches = None
    if build_cache:
        ks, vs, cks, cvs = cache_ys
        caches = {
            "k": ks, "v": vs, "ck": cks, "cv": cvs,
            "pos": jnp.asarray(s_dec, jnp.int32),
        }
    h = _norm(cfg, params["final_norm"], h)
    return h, jnp.zeros((), jnp.float32), caches

# ==========================================================================
# decode (single-token serve step)
# ==========================================================================


def init_cache(cfg: ArchConfig, shape: ShapeCfg, dtype=jnp.bfloat16, pos: int | None = None):
    """Zero-initialized serve cache for a decode shape.

    Capacity is ``shape.seq`` (the assignment's decode semantics: one new
    token with a KV cache of seq_len — the cache arrives holding seq-1
    tokens and the step writes slot seq-1).  SWA archs use a rolling cache
    of ``window`` slots.
    """
    cap = _cache_capacity(cfg, shape)
    b = shape.batch
    kvh, dh, l = cfg.n_kv_heads, cfg.head_dim, cfg.n_layers
    pos = shape.seq - 1 if pos is None else pos
    posa = jnp.asarray(pos, jnp.int32)
    if cfg.family in ("dense", "moe", "vlm"):
        return {
            "k": jnp.zeros((l, b, cap, kvh, dh), dtype),
            "v": jnp.zeros((l, b, cap, kvh, dh), dtype),
            "pos": posa,
        }
    if cfg.family == "ssm":
        conv, ssm = mamba_init_state(cfg.ssm, b, dtype)
        return {
            "conv": jnp.zeros((l, *conv.shape), dtype),
            "ssm": jnp.zeros((l, *ssm.shape), dtype),
            "pos": posa,
        }
    if cfg.family == "hybrid":
        conv, ssm = mamba_init_state(cfg.ssm, b, dtype)
        n_inv = cfg.n_layers // cfg.shared_attn_every
        return {
            "conv": jnp.zeros((l, *conv.shape), dtype),
            "ssm": jnp.zeros((l, *ssm.shape), dtype),
            "shared_k": jnp.zeros((n_inv, b, cap, kvh, dh), dtype),
            "shared_v": jnp.zeros((n_inv, b, cap, kvh, dh), dtype),
            "pos": posa,
        }
    if cfg.family == "encdec":
        s_enc = shape.seq
        return {
            "k": jnp.zeros((l, b, cap, kvh, dh), dtype),
            "v": jnp.zeros((l, b, cap, kvh, dh), dtype),
            "ck": jnp.zeros((l, b, s_enc, kvh, dh), dtype),
            "cv": jnp.zeros((l, b, s_enc, kvh, dh), dtype),
            "pos": posa,
        }
    raise ValueError(cfg.family)


def decode_step(cfg: ArchConfig, params: Params, cache: dict, batch: dict, ctx):
    """One-token decode. Returns (logits (B,1,Vpad), new_cache)."""
    pos = cache["pos"]
    mode = "rolling" if cfg.window is not None else "linear"
    if cfg.input_kind == "embeds":
        h = batch["embeds"]  # (B,1,d)
        bsz = h.shape[0]
    else:
        tokens = batch["tokens"]  # (B,1)
        bsz = tokens.shape[0]
        h = embed_tokens(cfg, params, tokens, ctx)
    h = h.astype(jnp.dtype(cfg.compute_dtype))
    positions = batch.get("positions")
    if positions is None:
        positions = jnp.broadcast_to(pos[None, None], (bsz, 1)).astype(jnp.int32)

    new_cache = dict(cache)
    if cfg.family in ("dense", "moe", "vlm"):

        def body(hh, xs):
            lp, kl, vl = xs
            hh, kv, _ = dense_block(
                cfg, lp, hh, positions,
                cache=(kl, vl), cache_pos=pos, cache_mode=mode, ctx=ctx,
            )
            return hh, kv

        h, (ks, vs) = lax.scan(body, h, (params["layers"], cache["k"], cache["v"]))
        new_cache.update(k=ks, v=vs)

    elif cfg.family == "ssm":

        def body(hh, xs):
            lp, conv, ssm = xs
            out, st = mamba_decode_step(
                lp["mamba"], _norm(cfg, lp["ln"], hh), cfg.ssm, (conv, ssm)
            )
            return hh + out, st

        h, (convs, ssms) = lax.scan(
            body, h, (params["layers"], cache["conv"], cache["ssm"])
        )
        new_cache.update(conv=convs, ssm=ssms)

    elif cfg.family == "hybrid":
        emb0 = h
        every = cfg.shared_attn_every
        n_super = cfg.n_layers // every
        n_rest = cfg.n_layers - n_super * every

        def mamba_body(hh, xs):
            lp, conv, ssm = xs
            out, st = mamba_decode_step(
                lp["mamba"], _norm(cfg, lp["ln"], hh), cfg.ssm, (conv, ssm)
            )
            return hh + out, st

        def group(t, n0, n1):
            return jax.tree.map(lambda a: a[n0:n1], t)

        def regroup(t, g):
            return jax.tree.map(
                lambda a: a[: n_super * every].reshape(n_super, every, *a.shape[1:]),
                t,
            ) if g else t

        glayers = regroup(params["layers"], True)
        gconv = cache["conv"][: n_super * every].reshape(
            n_super, every, *cache["conv"].shape[1:]
        )
        gssm = cache["ssm"][: n_super * every].reshape(
            n_super, every, *cache["ssm"].shape[1:]
        )

        def super_body(hh, xs):
            lps, convs, ssms, sk, sv = xs
            hh, st = lax.scan(mamba_body, hh, (lps, convs, ssms))
            hh, kv = shared_block(
                cfg, params["shared"], hh, emb0, positions,
                cache=(sk, sv), cache_pos=pos,
            )
            return hh, (st, kv)

        h, (sts, kvs) = lax.scan(
            super_body, h,
            (glayers, gconv, gssm, cache["shared_k"], cache["shared_v"]),
        )
        conv_new = sts[0].reshape(n_super * every, *sts[0].shape[2:])
        ssm_new = sts[1].reshape(n_super * every, *sts[1].shape[2:])
        if n_rest:
            rest = group(params["layers"], n_super * every, cfg.n_layers)
            h, st_r = lax.scan(
                mamba_body, h,
                (rest, cache["conv"][n_super * every :], cache["ssm"][n_super * every :]),
            )
            conv_new = jnp.concatenate([conv_new, st_r[0]], axis=0)
            ssm_new = jnp.concatenate([ssm_new, st_r[1]], axis=0)
        new_cache.update(conv=conv_new, ssm=ssm_new, shared_k=kvs[0], shared_v=kvs[1])

    elif cfg.family == "encdec":
        posvec = jnp.broadcast_to(pos[None, None], (bsz, 1)).astype(jnp.int32)
        pe = lax.dynamic_slice_in_dim(params["pos_emb"], pos, 1, axis=0)
        h = h + pe[None].astype(h.dtype)
        spec = attn_spec(cfg)
        xspec = attn_spec(cfg, causal=False)

        def body(hh, xs):
            lp, kl, vl, ckl, cvl = xs
            a, kv = L.attention(
                lp["attn"], _norm(cfg, lp["ln1"], hh), spec,
                positions=posvec, kv_cache=(kl, vl), cache_pos=pos,
            )
            hh = hh + a
            xa, _ = L.attention(
                lp["xattn"], _norm(cfg, lp["ln_x"], hh), xspec,
                positions=posvec, precomputed_kv=(ckl, cvl),
            )
            hh = hh + xa
            hh = hh + L.mlp_apply(lp["mlp"], _norm(cfg, lp["ln2"], hh), cfg.mlp)
            return hh, kv

        h, (ks, vs) = lax.scan(
            body, h,
            (params["layers"], cache["k"], cache["v"], cache["ck"], cache["cv"]),
        )
        new_cache.update(k=ks, v=vs)
    else:
        raise ValueError(cfg.family)

    h = _norm(cfg, params["final_norm"], h)
    logits = lm_logits(cfg, params, h)
    new_cache["pos"] = pos + 1
    return logits, new_cache


# ==========================================================================
# step builders
# ==========================================================================


_BATCH_AXIS = {"positions": 1}  # all other batch leaves have batch at axis 0


def _split_microbatches(batch: dict, accum: int) -> dict:
    """Split the batch into grad-accum microbatches, STRIDED over the batch
    dim (sample j*accum+i -> microbatch i) so every microbatch stays evenly
    sharded over the data axes.  (A contiguous reshape puts each microbatch
    on a single data shard and forces a full reshard per accumulation step.)
    """
    out = {}
    for key, x in batch.items():
        ax = _BATCH_AXIS.get(key, 0)
        b = x.shape[ax]
        assert b % accum == 0, (key, b, accum)
        shp = list(x.shape)
        shp[ax : ax + 1] = [b // accum, accum]
        x = x.reshape(shp)
        x = jnp.moveaxis(x, ax + 1, 0)  # accum dim leads (scan xs)
        out[key] = x
    return out



def _dp_size(ctx) -> int:
    if ctx is None or not ctx.shard_batch:
        return 1
    n = 1
    for a in ctx.data_axes:
        n *= ctx.mesh.shape[a]
    return n

def make_train_step(cfg: ArchConfig, ctx, optimizer, shape: ShapeCfg):
    accum = cfg.grad_accum.get(shape.name, 1)
    # sub-batches must still divide the data axes (multi-pod has 2x the dp)
    accum = max(min(accum, shape.batch // max(_dp_size(ctx), 1)), 1)
    cdt = jnp.dtype(cfg.compute_dtype)

    def loss_fn(params, mb):
        # cast once, while still sharded — ZeRO-3 all-gathers then move
        # compute-dtype bytes, not fp32 master weights.
        params_c = jax.tree.map(
            lambda p: p.astype(cdt) if p.dtype == jnp.float32 else p, params
        )
        h, aux, _ = forward_seq(cfg, params_c, mb, ctx, remat=True)
        logits = lm_logits(cfg, params_c, h)
        loss = ce_loss(cfg, logits, mb["labels"])
        return loss + AUX_LOSS_WEIGHT * aux, (loss, aux)

    def train_step(params, opt_state, batch):
        if accum == 1:
            grads, (loss, aux) = jax.grad(loss_fn, has_aux=True)(params, batch)
        else:
            mbs = _split_microbatches(batch, accum)

            def micro(carry, mb):
                gsum, lsum, asum = carry
                g, (l, a) = jax.grad(loss_fn, has_aux=True)(params, mb)
                return (
                    jax.tree.map(lambda a_, g_: a_ + g_.astype(a_.dtype), gsum, g),
                    lsum + l,
                    asum + a,
                ), None

            acc_dt = cdt if cfg.low_precision_opt else None
            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, acc_dt or p.dtype), params
            )
            (gsum, lsum, asum), _ = lax.scan(
                micro, (zeros, jnp.zeros(()), jnp.zeros(())), mbs
            )
            grads = jax.tree.map(lambda g: g / accum, gsum)
            loss, aux = lsum / accum, asum / accum
        new_params, new_opt = optimizer.update(grads, opt_state, params)
        return new_params, new_opt, {"loss": loss, "aux": aux}

    return train_step


def make_prefill_step(cfg: ArchConfig, ctx, shape: ShapeCfg):
    mb = cfg.serve_microbatch.get(shape.name, 1)
    mb = max(min(mb, shape.batch // max(_dp_size(ctx), 1)), 1)

    def _one(params, batch):
        h, _, caches = forward_seq(cfg, params, batch, ctx, want_cache=shape)
        logits = lm_logits(cfg, params, h[:, -1:, :])
        return logits, caches

    if mb == 1:
        return _one

    def prefill_step(params, batch):
        """Batch-split prefill (bounds the live EP/attention transients at
        long sequence — MoE archs at prefill_32k).  Sub-batches are STRIDED
        (v[i::mb]) so each stays evenly spread over the data axis; outputs
        re-interleave to restore order."""
        outs = []
        for i in range(mb):
            sub = {}
            for k, v in batch.items():
                ax = _BATCH_AXIS.get(k, 0)
                sl = [slice(None)] * v.ndim
                sl[ax] = slice(i, None, mb)
                sub[k] = v[tuple(sl)]
            outs.append(_one(params, sub))
        # re-interleave: merged[..., j*mb + i, ...] = outs[i][..., j, ...]
        logits = jnp.stack([o[0] for o in outs], axis=1)
        logits = logits.reshape(-1, *logits.shape[2:])

        def merge(*leaves):
            if leaves[0].ndim == 0:  # pos scalar
                return leaves[0]
            st = jnp.stack(leaves, axis=2)  # batch dim is axis 1
            return st.reshape(*st.shape[:1], -1, *st.shape[3:])

        caches = jax.tree.map(merge, *[o[1] for o in outs])
        return logits, caches

    return prefill_step


def make_serve_step(cfg: ArchConfig, ctx):
    def serve_step(params, cache, batch):
        return decode_step(cfg, params, cache, batch, ctx)

    return serve_step
