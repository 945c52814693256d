"""Kernel-level strategy + layout comparison (CPU wall-clock).

Measures the XLA-gather reference vs the four Pallas strategies in interpret
mode (correctness path) and the partitioned executor's paths.  Off-TPU the
Pallas numbers run in interpret mode and are labelled ``*_interpret_us`` —
NOT performance-representative; on a TPU backend the same harness times the
compiled kernels and labels them ``*_us``.  Because interpret wall-clock says
nothing about data movement, every path also gets a **modeled HBM-traffic
column** (``repro.core.traffic``), which is what actually separates the
layouts/executors on hardware: the schedule-driven fused kernel streams each
buffer window once per core, the retired per-slot scan paid O(S·R_max·E).

``layout_scenario`` is the ragged-vs-dense packed-layout comparison on a
Zipf-skewed 1-big+31-small workload (DESIGN.md §3–§4): pack bytes, padding
fraction, modeled traffic, autotuned block sizes, and executor wall time for
both layouts, written to ``BENCH_embedding_layout.json``.

``crossover_sweep`` is the dense-vs-sparse kernel-path matrix (DESIGN.md
§11): forced one-hot vs forced true-sparse packs over a (rows, batch) grid,
recording modeled gather cost/bytes (the deterministic gated columns — the
crossover story is a chunk-width-vs-unique-count tradeoff, which interpret
wall can't see), bitwise parity between the two packs, and the interpret
walls (informational).  A dedup-armed plan over the zipf-skew workload adds
the plan-level claim: ``kernel_path=auto``'s modeled cost never exceeds the
better of the two forced paths.  Written into the same
``BENCH_embedding_layout.json`` under ``"crossover"``.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.core import (
    PartitionedEmbeddingBag,
    analytic_model,
    make_workload,
    modeled_hbm_traffic,
)
from repro.core.strategies import Strategy
from repro.kernels import ops, ref

_REPO_ROOT = Path(__file__).resolve().parent.parent


def _time(fn, *args, iters: int = 5) -> float:
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def run(csv: bool = True):
    rows = []
    m, e, b, s = 4096, 16, 512, 4
    key = jax.random.PRNGKey(0)
    table = jax.random.normal(key, (m, e), jnp.float32)
    idx = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, m)

    ref_fn = jax.jit(lambda t, i: ref.embedding_bag_ref(t, i))
    us = _time(ref_fn, table, idx)
    rows.append(("xla_gather_ref", us))
    interp = compat.pallas_interpret()
    tag = "_interpret" if interp else ""
    for strat in Strategy:
        fn = jax.jit(
            lambda t, i, st=strat: ops.embedding_bag(t, i, st, interpret=interp)
        )
        us = _time(fn, table, idx, iters=2)
        rows.append((f"pallas_{strat.value}{tag}", us))
    if csv:
        for name, us in rows:
            print(f"kernelbench,{name},{us:.1f}us_per_call,m={m}xE={e}xB={b}xs={s}")
    return rows


def zipf_skewed_workload(big_rows: int = 50_000, n_small: int = 31, batch: int = 128):
    """The paper's pathological shape: one huge table + many tiny ones."""
    rng = np.random.default_rng(0)
    rows = [big_rows] + [int(x) for x in rng.integers(16, 256, n_small)]
    return make_workload("zipf-skew", rows, dim=16, batch=batch, zipf_alpha=1.2)


def crossover_sweep(csv: bool = True) -> dict:
    """Dense-vs-sparse kernel-path crossover matrix (DESIGN.md §11).

    One single-chunk GM plan per (rows, batch) cell, packed twice —
    ``kernel_path="onehot"`` and ``"sparse"`` with the same dedup width —
    and executed on the chunk's core.  Gated columns are the modeled gather
    seconds/bytes per path and the modeled winner (deterministic closed
    forms); parity is bitwise np.array_equal between the two packs.
    Interpret walls ride along unlabeled as performance claims — on CPU the
    one-hot GEMM hits BLAS while the sparse gather serializes, so only a
    TPU backend makes the wall column meaningful.
    """
    from repro.core.partition import _fused_asym_lookup, pack_plan
    from repro.core.strategies import ChunkAssignment, Plan
    from repro.core.traffic import modeled_kernel_path_traffic
    from repro.data.distributions import Zipf, workload_probs
    from repro.core.planner import plan_asymmetric

    model = analytic_model()
    block_r = 512
    interp = compat.pallas_interpret()
    cells = []
    for rows in (1024, 32_768):
        for batch in (64, 512):
            wl = make_workload(
                f"xover-{rows}x{batch}", [rows], dim=16, seqs=[4], batch=batch
            )
            table = wl.tables[0]
            plan = Plan(
                workload_name=wl.name, n_cores=1,
                assignments=(ChunkAssignment(0, 0, 0, rows, Strategy.GM),),
                symmetric_tables=(), symmetric_strategies=(),
            )
            plan.validate(wl.tables)
            costs = model.kernel_path_costs(
                table, batch, 1, block_r=block_r
            )
            # dedup width from the modeled uniques (planner sizing rule),
            # bounded so the interpret-mode gather loop stays CPU-quick;
            # the overflow spills identically on both paths.
            cap = int(min(1.25 * costs["unique"] + 8, batch * 4, rows, 768))
            cap = -(-cap // 8) * 8
            params = [
                jax.random.normal(
                    jax.random.PRNGKey(rows + batch), (rows, 16), jnp.float32
                )
            ]
            idx = jnp.asarray(
                np.random.default_rng(rows ^ batch).integers(
                    0, rows, (1, batch, 4)
                ),
                jnp.int32,
            )
            outs, walls = {}, {}
            for kp in ("onehot", "sparse"):
                packed = pack_plan(
                    plan, wl.tables, params, block_r=block_r,
                    unique_cap=cap, kernel_path=kp,
                )
                local = packed.strip_core(0)
                fn = jax.jit(
                    lambda p, i: _fused_asym_lookup(p, i, n_tables=1)
                )
                walls[kp] = _time(fn, local, idx, iters=2)
                outs[kp] = np.asarray(fn(local, idx))
            parity = bool(np.array_equal(outs["onehot"], outs["sparse"]))
            winner = "sparse" if costs["sparse"] < costs["onehot"] else "onehot"
            cell = {
                "rows": rows,
                "batch": batch,
                "unique_cap": cap,
                "modeled_unique": costs["unique"],
                "onehot_model_us": costs["onehot"] * 1e6,
                "sparse_model_us": costs["sparse"] * 1e6,
                "onehot_model_bytes": costs["onehot_bytes"],
                "sparse_model_bytes": costs["sparse_bytes"],
                "modeled_winner": winner,
                f"onehot{'_interpret' if interp else ''}_wall_us": walls["onehot"],
                f"sparse{'_interpret' if interp else ''}_wall_us": walls["sparse"],
                "parity_ok": parity,
            }
            cells.append(cell)
            if csv:
                print(
                    f"kernelbench,crossover,rows={rows},batch={batch},"
                    f"u={costs['unique']:.0f},"
                    f"model_onehot={cell['onehot_model_us']:.2f}us,"
                    f"model_sparse={cell['sparse_model_us']:.2f}us,"
                    f"winner={winner},parity={parity}"
                )

    # plan-level auto-never-worse on the paper's pathological shape
    wl = zipf_skewed_workload()
    freqs = workload_probs(wl, Zipf(1.2))
    plan = plan_asymmetric(
        wl, jax.device_count(), model, freqs=freqs, dedup=True,
        lif_threshold=1e9, rock_theta=None,
    )
    tr = modeled_kernel_path_traffic(plan, wl.tables, wl.batch, freqs)
    workload_rec = {
        "workload": "zipf-skew-1big-31small",
        "n_sparse": tr["n_sparse"],
        "n_onehot": tr["n_onehot"],
        "onehot_us": tr["onehot_us"],
        "sparse_us": tr["sparse_us"],
        "auto_us": tr["auto_us"],
        "onehot_bytes": tr["onehot_bytes"],
        "sparse_bytes": tr["sparse_bytes"],
        "auto_bytes": tr["auto_bytes"],
        "auto_never_worse": tr["auto_never_worse"],
    }
    big = [c for c in cells if c["rows"] >= 32_768]
    small = [c for c in cells if c["rows"] < 32_768]
    record = {
        "backend": jax.default_backend(),
        "compiled": not interp,
        "block_r": block_r,
        "cells": cells,
        "workload": workload_rec,
        "invariants": {
            "parity_ok": all(c["parity_ok"] for c in cells),
            "sparse_wins_past_crossover": bool(big) and all(
                c["modeled_winner"] == "sparse" for c in big
            ),
            "onehot_wins_below_crossover": bool(small) and all(
                c["modeled_winner"] == "onehot" for c in small
            ),
            "both_paths_chosen": {
                c["modeled_winner"] for c in cells
            } == {"onehot", "sparse"},
            "auto_never_worse": bool(tr["auto_never_worse"]),
        },
    }
    if csv:
        print(
            f"kernelbench,crossover_auto,"
            f"sparse_chunks={tr['n_sparse']},onehot_chunks={tr['n_onehot']},"
            f"auto={tr['auto_us']:.2f}us,"
            f"best_forced={min(tr['onehot_us'], tr['sparse_us']):.2f}us,"
            f"never_worse={tr['auto_never_worse']}"
        )
    return record


def layout_scenario(csv: bool = True, out_path: Path | None = None) -> dict:
    """Ragged vs dense packed layout: bytes + modeled traffic + wall time.

    The asymmetric plan keeps every table asymmetric (high LIF threshold), so
    one core carries the huge chunk while others carry handfuls of tiny
    tables — exactly the shape where the dense stacked-slot layout pads every
    slot to the global max_rows.  The fused kernel is timed COMPILED on a TPU
    backend (``fused_us``); off-TPU it falls back to interpret mode and the
    column is labelled ``fused_interpret_us`` so nobody mistakes it for a
    hardware number — the modeled-traffic columns carry the layout story on
    CPU.
    """
    wl = zipf_skewed_workload()
    n_dev = jax.device_count()
    mesh = compat.make_mesh((1, n_dev), ("data", "model"))
    bag = PartitionedEmbeddingBag(
        wl, n_cores=n_dev, planner="asymmetric", cost_model=analytic_model(),
        planner_kwargs=dict(lif_threshold=1e9, rock_theta=None),
    )
    params = bag.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    idx = [
        jnp.asarray(rng.integers(0, t.rows, (wl.batch, t.seq)), jnp.int32)
        for t in wl.tables
    ]
    compiled = jax.default_backend() == "tpu"
    fused_key = "fused_us" if compiled else "fused_interpret_us"

    record: dict = {
        "workload": "zipf-skew-1big-31small",
        "batch": wl.batch,
        "n_tables": len(wl.tables),
        "n_cores": n_dev,
        "backend": jax.default_backend(),
        "fused_compiled": compiled,
        "layouts": {},
    }
    for layout in ("ragged", "dense"):
        # the ragged layout gets the autotuned block sizes (the sweep is
        # recorded in plan.meta["tuning"] and copied into the record).
        packed = bag.pack(params, layout=layout, autotune=layout == "ragged")
        summary = bag.layout_summary()
        traffic = modeled_hbm_traffic(
            packed, batch=wl.batch, seq=bag.s_max, n_tables=bag.n_tables
        )
        timings = {}
        for mode, uk in (("xla", False), (fused_key[:-3], "fused")):
            fn = jax.jit(
                lambda p, i, uk=uk: bag.apply(
                    p, i, mesh=mesh, use_kernels=uk, reduce_mode="sparse"
                )
            )
            timings[f"{mode}_us"] = _time(fn, packed, idx, iters=2)
        entry = {**summary, **timings, "modeled_traffic": traffic}
        if layout == "ragged":
            entry["tuning"] = bag.plan.meta.get("tuning", {})
        record["layouts"][layout] = entry
        if csv:
            tp = traffic["paths"]
            print(
                f"kernelbench,layout_{layout},"
                f"bytes={summary['chunk_bytes']},"
                f"padding_frac={summary['padding_frac']:.3f},"
                f"xla={timings['xla_us']:.0f}us,"
                f"fused={timings[f'{fused_key[:-3]}_us']:.0f}us"
                f"{'' if compiled else '(interpret)'},"
                f"model_fused_MB={tp['fused']['total'] / 1e6:.2f},"
                f"model_scan_MB={tp['per_slot_scan_legacy']['total'] / 1e6:.2f}"
            )
    r = record["layouts"]
    record["bytes_shrink_vs_dense"] = (
        r["dense"]["chunk_bytes"] / max(r["ragged"]["chunk_bytes"], 1)
    )
    record["modeled_fused_traffic_shrink_vs_dense"] = (
        r["dense"]["modeled_traffic"]["paths"]["fused"]["total"]
        / max(r["ragged"]["modeled_traffic"]["paths"]["fused"]["total"], 1)
    )
    record["modeled_fused_traffic_shrink_vs_scan"] = (
        r["ragged"]["modeled_traffic"]["paths"]["per_slot_scan_legacy"]["total"]
        / max(r["ragged"]["modeled_traffic"]["paths"]["fused"]["total"], 1)
    )
    if csv:
        print(f"kernelbench,layout_shrink,{record['bytes_shrink_vs_dense']:.2f}x")
        print(
            "kernelbench,traffic_shrink,"
            f"vs_dense={record['modeled_fused_traffic_shrink_vs_dense']:.2f}x,"
            f"vs_scan={record['modeled_fused_traffic_shrink_vs_scan']:.2f}x"
        )
    record["crossover"] = crossover_sweep(csv=csv)
    out_path = out_path or _REPO_ROOT / "BENCH_embedding_layout.json"
    out_path.write_text(json.dumps(record, indent=2))
    return record


if __name__ == "__main__":
    run()
    layout_scenario()
