"""Compile the main path's Pallas kernels for a TPU v5e, at taobao shapes.

Nothing runs: the installed TPU compiler compiles each kernel for a described
(not attached) ``v5e:2x2`` topology, so what the chip's compiler refuses —
dynamic value slices, blocks that break the (8, 128) rule, more VMEM or SMEM
than a kernel may use — fails here, on the CPU, at no chip time.  The
topology is described inside a fixture, never at import, so that every test
worker collects the same tests and only the one running this file loads the
TPU library.  The operands are the engine's own packs of taobao at batch
8192, built from abstract tables: one chip (every table in the fused kernel)
and four chips (the symmetric group's per-table kernels on a quarter of the
batch); and the row gather at Criteo-1TB's per-chip shards.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro import compat
from repro.compat import vmem_bytes
from repro.core.cost_model import HARDWARE, analytic_model
from repro.core.partition import (
    STRATEGY_CODE,
    _bag_with_strategy,
    _fused_asym_lookup,
)
from repro.core.strategies import Strategy
from repro.data.workloads import get_workload
from repro.engine import EngineConfig, InferenceEngine
from repro.kernels.embedding_l1 import PIN_VMEM_BYTES

BATCH = 8192
E = 16
# the fused kernel's custom call, named by the op that emits it
_FUSED = re.compile(
    r'custom_call_target="tpu_custom_call".*'
    r'op_name="[^"]*multi_embedding_bag_ragged[^"]*pallas_call"'
)


@pytest.fixture(scope="module")
def tpu():
    """One device of a described v5e:2x2; fails where none can be described."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    # a persistent-cache entry written here could not be read back without
    # a chip; keep these compiles out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def taobao():
    return get_workload("taobao", BATCH)


def _core_pack(taobao, sharding, **config):
    """Core 0's slice of the engine's abstract taobao pack: its arrays as
    shapes on the described device, its static fields as packed."""
    engine = InferenceEngine.build(
        "abstract", taobao, EngineConfig(simulate=True, **config)
    )
    packed = engine.packed.strip_core(0)
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        packed,
    )


@pytest.fixture(scope="module")
def sym_pack(taobao, tpu):
    """The four-chip pack: its symmetric group runs per-table kernels."""
    return _core_pack(taobao, tpu, mesh_shape=(1, 4))


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _compile_fused(packed, tpu, monkeypatch):
    """Compile the engine's fused lookup of one core's pack for the TPU."""
    monkeypatch.setattr(compat, "pallas_interpret", lambda: False)
    n = len(get_workload("taobao", BATCH).tables)
    idx = jax.ShapeDtypeStruct((n, BATCH, 1), jnp.int32, sharding=tpu)
    compiled = _compile(
        lambda pk, i: _fused_asym_lookup(pk, i, n_tables=n), packed, idx
    )
    assert _FUSED.search(compiled.as_text())


def test_fused_kernel_plain(tpu, taobao, monkeypatch):
    packed = _core_pack(taobao, tpu, mesh_shape=(1, 1))
    assert packed.block_r == 512  # the schedule stays small enough for SMEM
    assert packed.kernel_path == "onehot" and not packed.unique_cap
    _compile_fused(packed, tpu, monkeypatch)


def test_fused_kernel_dedup_cache_sparse(tpu, taobao, monkeypatch):
    # chip_smoke.py's phase (b): with no L1 budget the tiny tables stream
    # as GM chunks, which the residency cache carves
    packed = _core_pack(
        taobao, tpu, mesh_shape=(1, 1), distribution="zipf:1.2",
        access="full", kernel_path="auto", hardware_options={"l1_bytes": 0},
    )
    assert packed.unique_cap > 0 and packed.cache_rows > 0
    assert packed.kernel_path != "onehot"
    _compile_fused(packed, tpu, monkeypatch)


_PINNED_ROWS = PIN_VMEM_BYTES // vmem_bytes((8, E)) * 8  # largest pinnable


@pytest.mark.parametrize("strategy", list(Strategy), ids=lambda s: s.name)
def test_symmetric_kernel(tpu, taobao, sym_pack, strategy, monkeypatch):
    """Each strategy's kernel on a quarter of the batch, for the largest
    table that strategy gets: the four-chip pack's own symmetric table where
    it has one, else the largest table the planner may pin (L1 kinds) or
    the largest taobao table (GM kinds)."""
    code = STRATEGY_CODE[strategy]
    mine = [rows for _, rows, c in sym_pack.sym_static if c == code]
    if mine:
        rows = max(mine)
        assert sym_pack.sym_data.shape[-2] > rows  # + the zero row
    elif strategy.is_l1:
        rows = _PINNED_ROWS - 1
    else:
        rows = max(t.rows for t in taobao.tables)
    table = jax.ShapeDtypeStruct((rows + 1, E), jnp.float32, sharding=tpu)
    idx = jax.ShapeDtypeStruct((BATCH // 4, 1), jnp.int32, sharding=tpu)
    monkeypatch.setattr(compat, "pallas_interpret", lambda: False)
    _compile(lambda t, i: _bag_with_strategy(t, i, code, True), table, idx)


@pytest.mark.parametrize("rows", [47_000_000, 33_000_000])
def test_row_gather_kernel(tpu, rows):
    """The row gather at Criteo-1TB's per-chip shards (the 187M- and
    130M-row tables over four chips), lane-dense, for a batch of 8192 over
    three gathered tables: the buffer stays the operand as laid out (no
    copy of it) and the kernel fits its SMEM and VMEM."""
    from repro.core.capacity import gather_rows_per_block
    from repro.kernels.embedding_gm import embedding_bag_gm_gather

    rpb = gather_rows_per_block(E, 4)
    lines = jax.ShapeDtypeStruct(
        (-(-rows // rpb), rpb * E // 128, 128), jnp.float32, sharding=tpu
    )
    ridx = jax.ShapeDtypeStruct((3, BATCH, 1), jnp.int32, sharding=tpu)
    compiled = _compile(
        lambda l, r: embedding_bag_gm_gather(l, r, e=E), lines, ridx
    )
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < rows * E * 4 * 1.01
    assert mem.temp_size_in_bytes < 64 << 20


def test_planner_never_pins_what_the_kernel_refuses(taobao):
    """The planner's pin budget is the kernels' own: every table the
    planner may give an L1 strategy in the symmetric group pins."""
    model = analytic_model()
    for t in taobao.tables:
        if model.fits_l1(t):
            assert vmem_bytes((t.rows + 1, E)) <= PIN_VMEM_BYTES
    big = dataclasses.replace(taobao.tables[0], rows=_PINNED_ROWS)
    assert not model.fits_l1(big)


@pytest.mark.parametrize("hw", sorted(HARDWARE))
def test_pin_cap_binds_on_every_preset(taobao, hw):
    """However large a preset's (or a user's) L1 budget, the planner pins
    nothing the L1 kernels refuse: the cap is theirs, not the hardware's."""
    roomy = dataclasses.replace(HARDWARE[hw], l1_bytes=1 << 40)
    model = analytic_model(roomy)
    fits = dataclasses.replace(taobao.tables[0], rows=_PINNED_ROWS - 1)
    big = dataclasses.replace(taobao.tables[0], rows=_PINNED_ROWS)
    assert model.fits_l1(fits) and not model.fits_l1(big)
