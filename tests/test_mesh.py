"""Two-level mesh subsystem (DESIGN.md §12): hierarchical placement,
rejoin-map hierarchy, the (1, n) collapse guarantee, mesh-shape resolution,
and the build-time device validation that closes the silent-fallback bug.

Like test_fused_executor.py, multi-core execution is emulated in-process
(pure-python all_to_all/all_gather over the packed rejoin maps) so every
mesh shape is checked against the pure-jnp oracle on one CPU device.
"""
import dataclasses
import warnings

import jax
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro.core import PartitionedEmbeddingBag, analytic_model, make_workload
from repro.core.cost_model import TPU_V5E
from repro.core.embedding import stack_indices
from repro.core.mesh import (
    MeshShapeError,
    host_of_core,
    plan_hierarchical,
    resolve_mesh_shape,
)
from repro.core.planner import plan_asymmetric
from repro.core.traffic import modeled_cross_host_traffic
from repro.data.distributions import Zipf, workload_probs
from test_fused_executor import (
    _emulate_sparse_rejoin, _full_lookup, _local_partials,
)

E = 16


def _model(l1_bytes=4096):
    return analytic_model(dataclasses.replace(TPU_V5E, l1_bytes=l1_bytes))


def _wl(batch=32, name="mesh"):
    return make_workload(
        name, [900, 260, 1400, 70, 40, 512], dim=E,
        seqs=[2, 1, 3, 1, 1, 2], batch=batch,
    )


def _indices(wl, seed=3):
    return [
        jax.random.randint(
            jax.random.PRNGKey(seed + i), (wl.batch, t.seq), 0, t.rows
        )
        for i, t in enumerate(wl.tables)
    ]


def _hier_bag(wl, hosts, cph, model=None, **kw):
    return PartitionedEmbeddingBag(
        wl, n_cores=hosts * cph, planner="hierarchical",
        cost_model=model or _model(),
        planner_kwargs=dict(hosts=hosts, **kw),
    )


def _emulated_lookup(bag, packed, sidx):
    """Asymmetric partials + emulated sparse rejoin, plus the symmetric
    group a one-host plan (plain ``plan_asymmetric``) may fall back to."""
    if not bag.plan.symmetric_tables:
        locals_ = _local_partials(packed, sidx, bag.n_tables)
        return _emulate_sparse_rejoin(locals_, packed, bag.n_tables)
    return _full_lookup(bag, packed, sidx, rejoin="sparse")


# --------------------------------------------------------------------------
# resolve_mesh_shape / host_of_core
# --------------------------------------------------------------------------


def test_resolve_mesh_shape_wins_over_n_cores():
    assert resolve_mesh_shape((2, 3), None) == (2, 3)
    assert resolve_mesh_shape([4, 2], 8) == (4, 2)  # JSON delivers a list


def test_resolve_legacy_n_cores_warns_deprecation():
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert resolve_mesh_shape(None, 4) == (1, 4)
    assert any(
        issubclass(w.category, DeprecationWarning)
        and "mesh_shape=(1, 4)" in str(w.message)
        for w in caught
    )


def test_resolve_default_has_no_warning():
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        assert resolve_mesh_shape(None, None, default_cores=6) == (1, 6)
    assert not caught


@pytest.mark.parametrize(
    "shape,n_cores",
    [((2, 3), 5), ((0, 4), None), ((2, -1), None), ("2x3", None), ((2,), None)],
)
def test_resolve_rejects_bad_geometry(shape, n_cores):
    with pytest.raises(MeshShapeError):
        resolve_mesh_shape(shape, n_cores, warn=False)


def test_mesh_shape_error_is_value_error():
    assert issubclass(MeshShapeError, ValueError)


def test_host_of_core():
    assert [host_of_core(c, 2) for c in range(6)] == [0, 0, 1, 1, 2, 2]


# --------------------------------------------------------------------------
# (1, n) collapse guarantee: bit-identical plans / packs / outputs
# --------------------------------------------------------------------------


def test_single_host_plan_is_bit_identical():
    wl = _wl()
    model = _model()
    flat = plan_asymmetric(wl, 4, model, lpt=True)
    hier = plan_hierarchical(wl, 4, model, hosts=1, lpt=True)
    assert hier.assignments == flat.assignments
    assert hier.symmetric_tables == flat.symmetric_tables
    assert hier.symmetric_strategies == flat.symmetric_strategies
    assert hier.meta["planner"] == flat.meta["planner"]
    assert hier.meta["mesh"] == {
        "hosts": 1, "cores_per_host": 4,
        "host_tables": [sorted({a.table_idx for a in flat.assignments})],
        "rocks": [],
    }


def test_single_host_pack_and_output_identical():
    wl = _wl()
    model = _model()
    flat_bag = PartitionedEmbeddingBag(
        wl, n_cores=4, planner="asymmetric", cost_model=model
    )
    hier_bag = _hier_bag(wl, 1, 4, model)
    tables = flat_bag.init(jax.random.PRNGKey(0))
    flat_packed = flat_bag.pack(tables)
    hier_packed = hier_bag.pack(tables)
    for field in (
        "chunk_data", "chunk_table", "chunk_offset", "chunk_rows",
        "rejoin_send", "rejoin_owned_pos", "rejoin_bucket",
    ):
        a = getattr(flat_packed, field, None)
        b = getattr(hier_packed, field, None)
        if a is None and b is None:
            continue
        assert np.array_equal(np.asarray(a), np.asarray(b)), field
    sidx = stack_indices(_indices(wl), flat_bag.s_max)
    out_flat = _emulated_lookup(flat_bag, flat_packed, sidx)
    out_hier = _emulated_lookup(hier_bag, hier_packed, sidx)
    np.testing.assert_array_equal(out_flat, out_hier)


# --------------------------------------------------------------------------
# multi-host plans: validity, host-locality, hierarchical rejoin maps
# --------------------------------------------------------------------------


def test_hierarchical_plan_host_local_and_valid():
    wl = _wl()
    plan = plan_hierarchical(wl, 4, _model(), hosts=2, lpt=True)
    plan.validate(wl.tables)
    mesh = plan.meta["mesh"]
    assert mesh["hosts"] == 2 and mesh["cores_per_host"] == 2
    assert plan.symmetric_tables == ()  # structurally disabled
    rocks = set(mesh["rocks"])
    hosts_of = {}
    for a in plan.assignments:
        hosts_of.setdefault(a.table_idx, set()).add(host_of_core(a.core, 2))
    for ti, hs in hosts_of.items():
        if ti not in rocks:
            assert len(hs) == 1, f"non-rock table {ti} spans hosts {hs}"
    for h, ids in enumerate(mesh["host_tables"]):
        for ti in ids:
            assert hosts_of[ti] == {h}


def test_hierarchical_rejoin_has_no_cross_host_sends():
    wl = _wl()
    bag = _hier_bag(wl, 2, 2)
    bag.pack(bag.init(jax.random.PRNGKey(1)))
    rejoin = bag.plan.meta["rejoin"]
    assert rejoin["hosts"] == 2
    assert rejoin["cross_host_sends"] == 0


def test_hosts_must_divide_cores():
    with pytest.raises(MeshShapeError):
        plan_hierarchical(_wl(), 4, _model(), hosts=3)
    with pytest.raises(MeshShapeError):
        plan_hierarchical(_wl(), 4, _model(), hosts=0)


@pytest.mark.parametrize("hosts,cph", [(1, 4), (4, 1), (2, 2), (3, 2)])
def test_emulated_rejoin_matches_oracle(hosts, cph):
    wl = _wl()
    bag = _hier_bag(wl, hosts, cph)
    tables = bag.init(jax.random.PRNGKey(2))
    packed = bag.pack(tables)
    idx = _indices(wl)
    got = _emulated_lookup(bag, packed, stack_indices(idx, bag.s_max))
    want = np.asarray(bag.reference(tables, idx))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_hierarchical_with_dedup_and_freqs():
    wl = _wl()
    freqs = workload_probs(wl, Zipf(1.2))
    bag = _hier_bag(wl, 2, 2, freqs=freqs, dedup=True)
    tables = bag.init(jax.random.PRNGKey(4))
    packed = bag.pack(tables)
    assert bag.plan.meta["cache"]["unique_cap"] > 0
    idx = _indices(wl)
    got = _emulated_lookup(bag, packed, stack_indices(idx, bag.s_max))
    want = np.asarray(bag.reference(tables, idx))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------
# partition property: every (table, row) owned by exactly one (host, core)
# --------------------------------------------------------------------------


def _assert_partition(plan, wl, hosts, cph):
    plan.validate(wl.tables)  # exact coverage, no overlap
    sym = set(plan.symmetric_tables)
    owners = {}
    for a in plan.assignments:
        assert 0 <= a.core < hosts * cph
        key = (a.table_idx, a.row_offset, a.rows)
        assert key not in owners, f"row span {key} owned twice"
        owners[key] = (host_of_core(a.core, cph), a.core)
    covered = {ti for ti, _, _ in owners}
    assert covered | sym == set(range(len(wl.tables)))


@pytest.mark.parametrize("hosts,cph", [(1, 1), (1, 4), (4, 1), (2, 3), (3, 2)])
def test_partition_property_fixed_shapes(hosts, cph):
    wl = _wl()
    plan = plan_hierarchical(wl, hosts * cph, _model(), hosts=hosts)
    _assert_partition(plan, wl, hosts, cph)


@given(
    hosts=st.integers(min_value=1, max_value=4),
    cph=st.integers(min_value=1, max_value=4),
    seed=st.integers(min_value=0, max_value=2**16),
    n_tables=st.integers(min_value=2, max_value=7),
)
@settings(max_examples=25, deadline=None)
def test_partition_property_random(hosts, cph, seed, n_tables):
    """Property: hierarchical owner-bucket partitioning is a true partition
    — every (table, row) lands on exactly one (host, core), and the emulated
    rejoin reconstructs the flat gather exactly, for arbitrary mesh shapes
    including (1, n) and (n, 1)."""
    rng = np.random.default_rng(seed)
    rows = [int(rng.integers(8, 600)) for _ in range(n_tables)]
    seqs = [int(rng.integers(1, 3)) for _ in range(n_tables)]
    # 24 splits evenly over the 1-4 cores of a one-host plan, the only kind
    # with a batch-split symmetric group
    wl = make_workload("prop", rows, dim=E, seqs=seqs, batch=24)
    bag = _hier_bag(wl, hosts, cph)
    _assert_partition(bag.plan, wl, hosts, cph)
    tables = bag.init(jax.random.PRNGKey(seed % 97))
    packed = bag.pack(tables)
    idx = _indices(wl, seed=seed % 89)
    got = _emulated_lookup(bag, packed, stack_indices(idx, bag.s_max))
    want = np.asarray(bag.reference(tables, idx))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------------
# cross-host traffic model
# --------------------------------------------------------------------------


def test_flat_plan_models_zero_cross_host():
    wl = _wl()
    plan = plan_asymmetric(wl, 4, _model())
    x = modeled_cross_host_traffic(plan, wl.tables, wl.batch)
    assert x["hosts"] == 1
    assert x["cross_host_bytes"] == 0.0
    assert x["reduction_vs_flat"] == 1.0


def test_cross_host_bytes_beat_flat_and_flatten_in_batch():
    wl = _wl(batch=64)
    freqs = workload_probs(wl, Zipf(1.2))
    plan = plan_hierarchical(
        wl, 8, _model(), hosts=4, freqs=freqs, dedup=True
    )
    x = modeled_cross_host_traffic(plan, wl.tables, wl.batch, freqs)
    assert x["cross_host_bytes"] > 0
    assert x["cross_host_bytes"] < x["flat_allgather_bytes"]
    # unique_cap clamps the payload: bytes are FLAT in batch past dedup
    # saturation while the flat baseline keeps growing linearly
    big = modeled_cross_host_traffic(plan, wl.tables, wl.batch * 64, freqs)
    assert big["cross_host_bytes"] <= x["cross_host_bytes"] * 64
    even_bigger = modeled_cross_host_traffic(
        plan, wl.tables, wl.batch * 128, freqs
    )
    # doubling the batch again doubles the flat baseline but moves the
    # clamped hierarchical payload by under 2%
    growth = even_bigger["cross_host_bytes"] / big["cross_host_bytes"]
    assert growth < 1.02
    assert even_bigger["flat_allgather_bytes"] == 2 * big["flat_allgather_bytes"]


def test_cross_host_time_model():
    model = _model()
    assert model.cross_host_time(1 << 20, hosts=1) == 0.0
    assert model.cross_host_time(0, hosts=4) == 0.0
    t2 = model.cross_host_time(1 << 20, hosts=2)
    t4 = model.cross_host_time(1 << 20, hosts=4)
    assert t4 > t2 > 0


# --------------------------------------------------------------------------
# engine wiring: config validation, device check, simulate mode
# --------------------------------------------------------------------------


def test_engine_config_validates_mesh_shape():
    from repro.engine import EngineConfig

    with pytest.raises(MeshShapeError):
        EngineConfig(mesh_shape=(2, 3), n_cores=5).validate()
    EngineConfig(mesh_shape=(1, 1)).validate()
    EngineConfig(planner="hierarchical", access="dedup",
                 mesh_shape=(2, 2), simulate=True).validate()


def test_build_rejects_undersized_device_mesh():
    """The silent-fallback bug: an oversized plan on a tiny device mesh
    used to shard_map the FULL stacked buffers onto every device and
    silently drop all but core 0's partials.  Now it raises, actionably."""
    from repro.engine import EngineConfig, InferenceEngine

    wl = _wl()
    with pytest.raises(MeshShapeError, match="simulate=True"):
        InferenceEngine.build(None, wl, EngineConfig(mesh_shape=(2, 2)))
    with pytest.raises(MeshShapeError):
        InferenceEngine.build(None, wl, EngineConfig(n_cores=4))


def test_simulate_builds_but_refuses_to_execute():
    from repro.engine import EngineConfig, InferenceEngine

    wl = _wl()
    cfg = EngineConfig(
        planner="hierarchical", mesh_shape=(2, 2), simulate=True
    )
    eng = InferenceEngine.build(None, wl, cfg)
    assert eng.packed.n_cores == 4
    stats = eng.stats()
    assert stats["mesh_shape"] == [2, 2]
    assert stats["cross_host"]["flat_allgather_bytes"] > 0
    report = eng.plan_report()
    assert "host 0" in report and "host 1" in report
    assert "cross-host" in report and "mesh 2x2" in report
    idx = stack_indices(_indices(wl))
    with pytest.raises(MeshShapeError, match="simulate=True"):
        eng.lookup(idx)


def test_engine_single_host_mesh_executes():
    from repro.engine import EngineConfig, InferenceEngine

    wl = _wl()
    eng = InferenceEngine.build(
        None, wl, EngineConfig(planner="hierarchical", mesh_shape=(1, 1))
    )
    idx = _indices(wl)
    out = eng.lookup(idx)
    want = eng.bag.reference(eng.table_data, idx)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5
    )
