"""The planner's capacity term, the row gather and the pack streamed to the
chips, against the plain reference on seeded random weights.

Small chips stand in for the v5e: a ``HardwareSpec`` with a few MB of HBM
and a few hundred bytes of SMEM makes one table too large to replicate and
one too large for the fused kernel's step schedule, at sizes the CPU runs in
interpret mode.  The benchmark configurations' plans are frozen as they were
before the capacity term existed.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import capacity
from repro.core.cost_model import TPU_V5E, analytic_model
from repro.core.planner import CapacityError, plan_asymmetric
from repro.core.tables import make_workload
from repro.kernels.embedding_gm import embedding_bag_gm_gather

ROOT = Path(__file__).resolve().parent.parent
# 4 MB chips with 512 B of SMEM (a step limit of 8 row-blocks)
SMALL = {"hbm_bytes": 4_000_000, "smem_bytes": 512}
ROWS = [3000, 50, 700, 9, 20000, 5]
SEQS = [1, 2, 1, 1, 1, 3]


def _small_model(**hw):
    return analytic_model(dataclasses.replace(TPU_V5E, **{**SMALL, **hw}))


# --------------------------------------------------------------------------
# the planner
# --------------------------------------------------------------------------


def test_too_large_to_replicate_is_row_sharded_and_gathered():
    wl = make_workload("t", ROWS, dim=16, seqs=SEQS, batch=64)
    plan = plan_asymmetric(wl, 4, _small_model())
    cap = plan.meta["capacity"]
    # 6 tables x 20,001 rows x 64 B is over the 3 MB budget: row-sharded
    assert cap["budget"] == 3_000_000
    assert 4 in cap["row_sharded"] and 4 not in plan.symmetric_tables
    shards = plan.chunks_of(4)
    assert sorted(a.core for a in shards) == [0, 1, 2, 3]
    assert max(cap["per_chip"]) <= cap["budget"]
    # its 5,000-row shards need more row-blocks than the schedule holds
    gathered = {(c, r["table"]) for c, recs in
                enumerate(plan.meta["gather"]["per_chip"]) for r in recs}
    assert {(c, 4) for c in range(4)} <= gathered
    assert cap["step_limit"] == 8 and cap["gather_bytes"] > 0


def test_nothing_fits_raises_a_typed_error():
    wl = make_workload("t", ROWS, dim=16, seqs=SEQS, batch=64)
    with pytest.raises(CapacityError) as e:
        plan_asymmetric(wl, 4, _small_model(hbm_bytes=100_000))
    err = e.value
    assert err.need > err.budget == 75_000 and 0 <= err.chip < 4
    assert f"chip {err.chip}" in str(err) and f"{err.need:,}" in str(err)
    # one chip cannot row-shard at all
    with pytest.raises(CapacityError):
        plan_asymmetric(make_workload("t", [200_000], dim=16), 1, _small_model())


def test_no_capacity_term_without_an_hbm_size():
    wl = make_workload("t", ROWS, dim=16, seqs=SEQS, batch=64)
    plan = plan_asymmetric(wl, 4, _small_model(hbm_bytes=0, smem_bytes=0))
    assert plan.meta["capacity"]["budget"] is None
    assert plan.meta["capacity"]["row_sharded"] == []
    assert not any(plan.meta["gather"]["per_chip"])


def test_split_gather_moves_the_largest_first():
    rows = {0: [100, 5000, 900, 3000], 1: [10, 20]}
    # 512-row blocks: 1 + 10 + 2 + 6 = 19 steps on core 0, limit 8
    assert capacity.split_gather(rows, 512, 8) == {(0, 1), (0, 3)}
    assert capacity.split_gather(rows, 512, None) == set()
    assert capacity.step_limit(1 << 20) == 16384


# the plans the engine's defaults gave the benchmark's configurations before
# the capacity term existed (bench/gen.py histograms, seed 7): frozen
_FROZEN = {
    "dlrm-taobao": (
        [(0, 0, 0, 1141730, "L1"), (1, 0, 0, 846812, "L1"),
         (2, 0, 0, 12978, "L1"), (3, 0, 0, 423437, "GM_UB"),
         (4, 0, 0, 255876, "GM_UB"), (5, 0, 0, 461498, "GM_UB"),
         (6, 0, 0, 2, "L1"), (7, 0, 0, 98, "L1"), (8, 0, 0, 13, "L1"),
         (9, 0, 0, 3, "L1"), (10, 0, 0, 7, "L1"), (11, 0, 0, 4, "L1"),
         (12, 0, 0, 3, "L1"), (13, 0, 0, 2, "L1"), (14, 0, 0, 5, "L1")],
        [], [],
    ),
    "dlrm-taobao-4chip": (
        [(0, 0, 0, 1141730, "L1"), (1, 1, 0, 846812, "L1"),
         (2, 2, 0, 12978, "L1"), (3, 3, 0, 423437, "L1"),
         (4, 0, 0, 255876, "L1")],
        [5, 6, 7, 8, 9, 10, 11, 12, 13, 14], ["GM_UB"] * 10,
    ),
    "dlrm-kuairec-big": (
        [(i, 0, 0, r, "L1") for i, r in enumerate(
            [7176, 10728, 31, 9, 467, 340, 5, 3, 8, 2, 118, 4])],
        [], [],
    ),
}


@pytest.mark.parametrize("name", sorted(_FROZEN))
def test_benchmark_configurations_keep_their_plans(name):
    """The benchmark's three configurations, planned as the engine plans
    them (histograms from their own traffic, f32 rows), get exactly the plan
    they got before: assignments, strategies and symmetric group, and
    nothing row-sharded or gathered."""
    sys.path.insert(0, str(ROOT))
    from bench import gen
    from repro.data.distributions import RowProbs

    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    mix = json.loads((ROOT / "bench" / "traffic" / "zipf1.2-closed.json").read_text())
    rows, seqs = cfg["tables"]["rows"], cfg["tables"]["seq"]
    laws = gen.key_laws(mix["keys"], rows, 7)
    hist = gen.make_batches(laws, seqs, cfg["n_dense"], cfg["max_batch"],
                            mix["history_batches"], gen.rng(7, gen.STREAM_HISTORY))
    wl = make_workload(name, rows, dim=16, seqs=seqs, batch=cfg["max_batch"])
    probs = [RowProbs.from_counts(ids, n, t.rows)
             for (ids, n), t in zip(gen.key_counts(hist, len(rows)), wl.tables)]
    plan = plan_asymmetric(wl, cfg["mesh_shape"][1], analytic_model(),
                           freqs=probs, kernel_path="auto", itemsize=4)
    got = [(a.table_idx, a.core, a.row_offset, a.rows, a.strategy.name)
           for a in plan.assignments]
    want, sym, strats = _FROZEN[name]
    assert got == want
    assert list(plan.symmetric_tables) == sym
    assert [s.name for s in plan.symmetric_strategies] == strats
    assert plan.meta["capacity"]["row_sharded"] == []
    assert not any(plan.meta["gather"]["per_chip"])
    assert max(plan.meta["capacity"]["per_chip"]) <= plan.meta["capacity"]["budget"]


# --------------------------------------------------------------------------
# the row gather kernel
# --------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_gather_kernel_matches_take(dtype):
    """Interpret mode against ``jnp.take`` on the lane-dense buffer, with
    lookups outside the chip's rows (-1) and multi-hot bags."""
    rng = np.random.default_rng(3)
    e, rows = 16, 1000
    rpb = capacity.gather_rows_per_block(e, jnp.dtype(dtype).itemsize)
    n = -(-rows // rpb) * rpb
    table = np.zeros((n, e), np.float32)
    table[:rows] = rng.standard_normal((rows, e))
    table = table.astype(dtype)
    lines = jnp.asarray(table.reshape(n // rpb, rpb * e // 128, 128))
    ridx = rng.integers(-1, rows, (3, 40, 2)).astype(np.int32)
    ridx[rng.random(ridx.shape) < 0.5] = -1
    got = embedding_bag_gm_gather(lines, jnp.asarray(ridx), e=e, interpret=True)
    rows_ = jnp.take(jnp.asarray(table), jnp.maximum(ridx, 0), axis=0)
    want = jnp.where((ridx >= 0)[..., None], rows_, 0).astype(jnp.float32).sum(-2)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# --------------------------------------------------------------------------
# served on four devices
# --------------------------------------------------------------------------


def test_served_logits_match_the_reference_on_four_devices():
    """Four forced CPU devices: the engine's defaults on chips too small to
    replicate one table or to stream another serve DLRM logits equal to the
    plain forward pass, from a pack put onto the devices core by core."""
    code = textwrap.dedent(f"""
        import dataclasses, json, numpy as np, jax, jax.numpy as jnp
        from repro.core import cost_model
        from repro.core.tables import make_workload
        from repro.engine import EngineConfig, InferenceEngine
        from repro.launch.serve import dlrm_step_maker
        from repro.models.dlrm import DLRMConfig, forward_dense, init_dlrm
        cost_model.TPU_V5E = dataclasses.replace(cost_model.TPU_V5E, **{SMALL!r})
        wl = make_workload("t", {ROWS!r}, dim=16, seqs={SEQS!r}, batch=64)
        cfg = DLRMConfig(arch="t", workload=wl, bottom_mlp=(32, 16), top_mlp=(32,))
        params = init_dlrm(cfg, jax.random.PRNGKey(0))
        tables = [np.asarray(t) for t in params["tables"]]
        out = {{}}
        for kern in ("fused", "xla"):
            eng = InferenceEngine.build(
                tables, wl, EngineConfig(mesh_shape=(1, 4), use_kernels=kern))
            srv = eng.serve(make_step=dlrm_step_maker(cfg, params),
                            split_fn=lambda o, n: list(o[:n]), max_batch=64)
            rng = np.random.default_rng(1)
            idx = np.stack([np.pad(rng.integers(0, t.rows, (64, t.seq)),
                                   ((0, 0), (0, 3 - t.seq)), constant_values=-1)
                            for t in wl.tables]).astype(np.int32)
            dense = rng.standard_normal((64, 13)).astype(np.float32)
            hs = [srv.submit_request({{"dense": dense[q], "indices": idx[:, q]}})
                  for q in range(64)]
            srv.pump()
            got = np.array([h.result() for h in hs])
            want = np.asarray(forward_dense(cfg, params, {{"dense": dense, "indices": idx}}))
            pooled = np.asarray(eng.lookup(idx))
            ref = np.asarray(eng.bag.reference(tables, jnp.asarray(idx)))
            cap = eng.plan.meta["capacity"]
            out[kern] = {{
                "logit_gap": float(np.abs(got - want).max()),
                "pooled_gap": float(np.abs(pooled - ref).max()),
                "row_sharded": cap["row_sharded"],
                "fits": max(cap["per_chip"]) <= cap["budget"],
                "gathered": sum(len(c) for c in eng.plan.meta["gather"]["per_chip"]),
                "sharded": [str(eng.packed.gather_data.sharding.spec),
                            len(eng.packed.gather_data.addressable_shards)],
            }}
        print(json.dumps(out))
    """)
    env = {**os.environ, "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": str(ROOT / "src"), "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    for kern, o in out.items():
        assert o["pooled_gap"] == 0.0, kern
        assert o["logit_gap"] <= 1e-6, kern
        assert 4 in o["row_sharded"] and o["fits"] and o["gathered"] >= 4
        assert o["sharded"] == ["PartitionSpec('model',)", 4]
