"""The DLRM kind for table sets that no chip holds whole: the tables are
made from the seed on the host, in row blocks, so no table is ever whole on
a device; the served path is the one ``dlrm.py`` builds; the reference
gathers each batch's rows on the host.

Everything but the tables is ``dlrm.py``'s, loaded from its file: the
served path (``InferenceEngine.build`` with the engine's defaults, the DLRM
step of ``launch/serve.py``, ``engine.serve()``), the plain reference and
the limit of its logit gap, and the work a batch requires.  The reference
imports nothing of the program: for each batch it copies the rows the batch
looks up into small tables of a fixed size, with the ids renumbered to
match, and hands those to ``dlrm.py``'s reference.
"""
from __future__ import annotations

import importlib.util
import os
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np


def _load_dlrm():
    path = Path(__file__).with_name("dlrm.py")
    spec = importlib.util.spec_from_file_location("bench_models_dlrm_shared", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_dlrm = _load_dlrm()
LOGIT_GAP_LIMIT = _dlrm.LOGIT_GAP_LIMIT
serve = _dlrm.serve
step_hlo = _dlrm.step_hlo
payloads = _dlrm.payloads
logit_gap = _dlrm.logit_gap
lookup_bytes = _dlrm.lookup_bytes
lookup_flops = _dlrm.lookup_flops
tower_macs = _dlrm.tower_macs
query_flops = _dlrm.query_flops

_BLOCK_ROWS = 1 << 20  # rows of a table made at a time
_STREAM_TABLES = 4  # the seed's numpy stream for tables (bench/gen.py's STREAM_WEIGHTS)


def make_weights(cfg: dict, seed: int) -> dict:
    """Tower on the device, as ``dlrm.py`` makes it; tables on the host in
    float32, N(0, 1/E), each block of rows from its own numpy stream of the
    seed, made on as many threads as the process may use."""
    try:
        from repro.core import capacity  # noqa: F401
    except ImportError as e:  # a program that cannot place these tables
        raise SystemExit(f"{cfg['name']}: the program has no capacity planner ({e})")
    e = int(cfg["embed_dim"])
    bottom, top = _dlrm.tower_dims(cfg)

    def mlp(k, dims):
        ks = jax.random.split(k, 2 * (len(dims) - 1))
        return [
            {
                "w": jax.random.normal(ks[2 * i], (a, b), jnp.float32) / np.sqrt(a),
                "b": 0.01 * jax.random.normal(ks[2 * i + 1], (b,), jnp.float32),
            }
            for i, (a, b) in enumerate(zip(dims[:-1], dims[1:]))
        ]

    _, kb, ktop = jax.random.split(_dlrm.key(seed), 3)
    weights = {"bottom": mlp(kb, bottom), "top": mlp(ktop, top)}
    tables = [np.empty((int(m), e), np.float32) for m in cfg["tables"]["rows"]]
    scale = np.float32(1.0 / np.sqrt(e))
    words = [abs(int(seed)), int(seed < 0), _STREAM_TABLES]

    def fill(job):
        t, lo = job
        out = tables[t][lo : lo + _BLOCK_ROWS]
        np.random.default_rng(words + [t, lo]).standard_normal(out=out, dtype=np.float32)
        out *= scale

    jobs = [(t, lo) for t, tab in enumerate(tables) for lo in range(0, len(tab), _BLOCK_ROWS)]
    with ThreadPoolExecutor(min(32, len(os.sched_getaffinity(0)))) as pool:
        list(pool.map(fill, jobs))
    weights["tables"] = tables
    return jax.block_until_ready(weights)


def reference(cfg: dict, weights: dict, indices: np.ndarray, dense: np.ndarray, *, low: bool = False):
    """Logits of one batch by ``dlrm.py``'s plain forward pass, on per-batch
    tables of ``B * s`` rows: each table's looked-up rows, copied from the
    host tables, and zeros after them."""
    t_n, b, s = indices.shape
    e = int(cfg["embed_dim"])
    small, ids = [], np.full(indices.shape, -1, np.int32)
    for t in range(t_n):
        hit = indices[t] >= 0
        rows, ids[t][hit] = np.unique(indices[t][hit], return_inverse=True)
        tab = np.zeros((b * s, e), np.float32)
        tab[: len(rows)] = weights["tables"][t][rows]
        small.append(tab)
    local = {"tables": small, "bottom": weights["bottom"], "top": weights["top"]}
    return _dlrm.reference(cfg, local, ids, dense, low=low)


def gather_bytes(cfg: dict, distinct: list[int], batch: int, hbm_bytes: float, chips: int) -> float:
    """Bytes a batch's lookups into the tables too large to replicate must
    move: the tables whose float32 bytes exceed ``hbm_bytes / chips``; for
    each, its distinct rows once, every index once, every pooled vector
    once (``lookup_bytes`` over those tables)."""
    e = cfg["embed_dim"]
    item = {"float32": 4, "bfloat16": 2, "float16": 2}[cfg["table_dtype"]]
    total = 0.0
    for rows, seq, u in zip(cfg["tables"]["rows"], cfg["tables"]["seq"], distinct):
        if rows * e * 4 > hbm_bytes / chips:
            total += u * e * item + batch * seq * 4 + batch * e * 4
    return float(total)
