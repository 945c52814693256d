"""Planner + cost-model unit & property tests (paper §III invariants)."""
import dataclasses

import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core.cost_model import ASCEND_910, TPU_V5E, CostModel, analytic_model
from repro.core.planner import (
    plan_asymmetric,
    plan_baseline,
    plan_symmetric,
    predicted_p99,
)
from repro.core.strategies import Strategy
from repro.core.tables import TableSpec, make_workload
from repro.data.workloads import WORKLOADS
from repro.sim.ascend import SimParams, collect_measurements, strategy_time


def small_model(l1_bytes=4096, **hw):
    return analytic_model(dataclasses.replace(TPU_V5E, l1_bytes=l1_bytes, **hw))


# the paper's planner alone: without a chip's HBM the capacity term is off
# (a 50M-row f32 table padded 4x in the symmetric group would overflow a
# v5e's budget and be row-sharded; tests/test_capacity.py covers that)
PAPER_HW = {"hbm_bytes": 0}


def test_cost_model_recovers_planted_betas():
    """OLS fit recovers planted linear coefficients exactly."""
    b0, b1, b2 = 2e-6, 3e-9, 1.5e-10
    meas = []
    rng = np.random.default_rng(0)
    for _ in range(50):
        t = TableSpec("t", rows=int(rng.integers(10, 10_000)), dim=16,
                      seq=int(rng.integers(1, 8)))
        batch = int(rng.integers(128, 8192))
        for s in Strategy:
            work = batch * t.seq
            y = b0 + b1 * work + (b2 * t.rows if s.is_ub else 0.0)
            meas.append((t, batch, 1, s, y))
    m = CostModel.fit(meas)
    for s in Strategy:
        got = m.betas[s]
        assert abs(got[0] - b0) / b0 < 1e-3
        assert abs(got[1] - b1) / b1 < 1e-3
        if s.is_ub:
            assert abs(got[2] - b2) / b2 < 1e-3
    assert m.r2(meas) > 0.999


def test_cost_model_monotonic_in_batch():
    m = small_model()
    t = TableSpec("t", rows=1000, dim=16, seq=2)
    for s in Strategy:
        costs = [m.predict(t, b, 4, s) for b in (256, 1024, 4096)]
        assert costs == sorted(costs)


@settings(max_examples=40, deadline=None)
@given(
    cards=st.lists(st.integers(4, 100_000), min_size=1, max_size=24),
    seqs_seed=st.integers(0, 1000),
    k=st.sampled_from([2, 4, 8, 16, 32]),
    batch=st.sampled_from([256, 1024, 8192]),
    l1=st.sampled_from([1 << 12, 1 << 16, 1 << 20]),
    lpt=st.booleans(),
    rep=st.booleans(),
)
def test_asymmetric_plan_invariants(cards, seqs_seed, k, batch, l1, lpt, rep):
    """Any asymmetric plan: full row coverage, no overlaps, all tables placed,
    valid cores, L1 budget respected per core."""
    rng = np.random.default_rng(seqs_seed)
    seqs = rng.integers(1, 9, len(cards)).tolist()
    wl = make_workload("prop", cards, seqs=seqs, batch=batch)
    model = small_model(l1)
    plan = plan_asymmetric(wl, k, model, lpt=lpt, replicate_hot=rep)
    plan.validate(wl.tables)  # raises on violation
    # L1 budget per core
    used = {c: 0 for c in range(k)}
    for a in plan.assignments:
        if a.strategy.is_l1:
            used[a.core] += a.rows * wl.tables[a.table_idx].row_bytes
    for c, u in used.items():
        assert u <= model.hardware.l1_bytes


@settings(max_examples=20, deadline=None)
@given(
    cards=st.lists(st.integers(16, 50_000), min_size=2, max_size=16),
    k=st.sampled_from([4, 8, 32]),
)
def test_asymmetric_not_worse_than_symmetric_by_model(cards, k):
    """Under the fitted model, asymmetric predicted P99 <= 1.3x symmetric
    (the rock pre-pass guarantees near-symmetric behaviour in the worst case)."""
    wl = make_workload("cmp", cards, batch=4096)
    model = small_model(1 << 16)
    sym = predicted_p99(model, wl.tables, wl.batch, plan_symmetric(wl, k, model))
    asym = predicted_p99(model, wl.tables, wl.batch, plan_asymmetric(wl, k, model))
    assert asym <= 1.3 * sym + 1e-5


def test_lif_fallback_triggers():
    """A pathologically imbalanced workload trips the LIF fallback."""
    cards = [100] * 3 + [50_000_000] * 1
    wl = make_workload("lif", cards, seqs=[1, 1, 1, 64], batch=8192)
    model = small_model(1 << 20, **PAPER_HW)
    plan = plan_asymmetric(wl, 8, model, lif_threshold=1.05)
    assert plan.symmetric_tables, "expected symmetric fallback"


def test_chunking_only_when_beneficial():
    """Tables larger than L1 are chunked iff L1 speedup > n_chunks (paper rule)."""
    model = small_model(1 << 20, **PAPER_HW)  # 1 MiB
    # huge table: chunk count ~ GB/MB >> speedup -> not chunked
    wl = make_workload("big", [50_000_000], batch=8192)
    plan = plan_asymmetric(wl, 8, model)
    chunks = [a for a in plan.assignments if a.table_idx == 0]
    assert len(chunks) <= 1  # symmetric rock or single GM chunk


def test_paper_workloads_all_plan(tmp_path):
    p = SimParams()
    model = CostModel.fit(collect_measurements(list(WORKLOADS.values()), p), ASCEND_910)
    for wl in WORKLOADS.values():
        for planner in (plan_baseline, plan_symmetric, plan_asymmetric):
            plan = planner(wl.scaled(8192), 32, model)
            plan.validate(wl.tables)


def test_simulator_distribution_sensitivity():
    """L1 strategies are distribution-independent; baseline degrades on
    fixed >> real > uniform (paper's qualitative claims)."""
    p = SimParams()
    t = TableSpec("t", rows=20_000, dim=16, seq=1)
    for s in (Strategy.L1, Strategy.L1_UB):
        tu = strategy_time(s, t.rows, t, 8192, "uniform", p)
        tf = strategy_time(s, t.rows, t, 8192, "fixed", p)
        assert tu == pytest.approx(tf, rel=1e-9)
    from repro.sim.ascend import baseline_time
    bu = baseline_time(t, 8192, 32, "uniform", p)
    bf = baseline_time(t, 8192, 32, "fixed", p)
    assert bf > 5 * bu
