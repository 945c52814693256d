"""Multi-device tests: run in subprocesses so the 8-device host flag never
leaks into the main test process (per the dry-run isolation rule)."""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parent.parent / "src")

def run_py(code: str, devices: int = 8, timeout: int = 900) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    return out.stdout


def test_partitioned_lookup_matches_oracle():
    run_py("""
        import dataclasses, jax, numpy as np
        from repro.core import PartitionedEmbeddingBag, make_workload, analytic_model, TPU_V5E
        hw = dataclasses.replace(TPU_V5E, l1_bytes=4096)
        model = analytic_model(hw)
        wl = make_workload("t", [100, 57, 1000, 8, 3000, 16, 450, 333], dim=16,
                           seqs=[1,2,1,4,1,1,3,1], batch=64)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        for planner in ["baseline", "symmetric", "asymmetric"]:
            bag = PartitionedEmbeddingBag(wl, n_cores=4, planner=planner, cost_model=model)
            params = bag.init(jax.random.PRNGKey(0))
            packed = bag.pack(params)
            idx = [jax.random.randint(jax.random.PRNGKey(i+10), (wl.batch, t.seq), 0, t.rows)
                   for i, t in enumerate(wl.tables)]
            want = bag.reference(params, idx)
            for mode in ("psum", "ring"):
                got = bag.apply(packed, idx, mesh=mesh, reduce_mode=mode)
                np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                           rtol=2e-5, atol=2e-5)
        print("OK")
    """)


def test_partitioned_lookup_with_pallas_kernels():
    run_py("""
        import dataclasses, jax, numpy as np
        from repro.core import PartitionedEmbeddingBag, make_workload, analytic_model, TPU_V5E
        hw = dataclasses.replace(TPU_V5E, l1_bytes=4096)
        model = analytic_model(hw)
        wl = make_workload("t", [64, 120, 500], dim=16, seqs=[1,2,1], batch=32)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        bag = PartitionedEmbeddingBag(wl, n_cores=4, planner="asymmetric", cost_model=model)
        params = bag.init(jax.random.PRNGKey(0)); packed = bag.pack(params)
        idx = [jax.random.randint(jax.random.PRNGKey(i+10), (wl.batch, t.seq), 0, t.rows)
               for i, t in enumerate(wl.tables)]
        got = bag.apply(packed, idx, mesh=mesh, use_kernels=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(bag.reference(params, idx)),
                                   rtol=2e-5, atol=2e-5)
        print("OK")
    """)


def test_vocab_parallel_embed():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.partition import vocab_parallel_embed
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        V, D, B, S = 64, 16, 8, 12
        table = jax.random.normal(jax.random.PRNGKey(0), (V, D))
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0, V)
        fn = jax.shard_map(
            lambda t, x: vocab_parallel_embed(t, x, "model"),
            mesh=mesh, in_specs=(P("model", None), P("data", None)),
            out_specs=P("data", None, None), check_vma=False)
        got = fn(table, toks)
        np.testing.assert_allclose(np.asarray(got), np.asarray(jnp.take(table, toks, axis=0)),
                                   rtol=1e-6, atol=1e-6)
        print("OK")
    """)


def test_sharded_train_step_runs():
    """An actual sharded train step executes on the debug mesh and matches
    the unsharded step's loss."""
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.dryrun import lower_cell, make_ctx
        from repro.launch.mesh import make_debug_mesh
        from repro.models import registry
        from repro.configs.base import ShapeCfg
        from repro.training.optimizer import adamw
        import repro.sharding as sh

        mesh = make_debug_mesh()
        arch = "qwen3-0.6b"
        b = registry.build(arch, smoke=True)
        shape = ShapeCfg("t", "train", 64, 8)
        opt = adamw(1e-3)
        params = b.init(jax.random.PRNGKey(0))
        opt_state = opt.init(params)
        batch = b.make_batch(shape, jax.random.PRNGKey(1), act_dtype=jnp.float32)

        # unsharded reference
        _, _, m_ref = jax.jit(b.train_step(None, opt, shape))(params, opt_state, batch)

        ctx = make_ctx(mesh, shape, False)
        pspecs = sh.param_pspecs(params, False)
        named = jax.tree.map(lambda s: jax.sharding.NamedSharding(mesh, s), pspecs,
                             is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        params_s = jax.device_put(params, named)
        step = jax.jit(b.train_step(ctx, opt, shape))
        _, _, m = step(params_s, opt.init(params_s), batch)
        np.testing.assert_allclose(float(m["loss"]), float(m_ref["loss"]), rtol=5e-3)
        print("OK", float(m["loss"]), float(m_ref["loss"]))
    """)


def test_dryrun_cells_debug_mesh():
    """The dry-run machinery end-to-end on the debug mesh (smoke configs)."""
    run_py("""
        import tempfile
        from pathlib import Path
        from repro.launch import dryrun
        from repro.launch.mesh import make_debug_mesh
        out = Path(tempfile.mkdtemp())
        mesh = make_debug_mesh()
        for arch in ("olmo-1b", "mamba2-780m"):
            for shape in ("train_4k", "decode_32k"):
                rec = dryrun.run_cell(arch, shape, False, smoke=True, mesh=mesh, out_dir=out)
                assert rec["status"] == "ok", rec
                assert rec["hlo"]["flops"] > 0
        print("OK")
    """, devices=8)


def test_sparse_rejoin_matches_psum_on_mesh():
    """Owner-sharded sparse rejoin ≡ dense psum on a real 8-device mesh,
    including batch-split replicas, a row-split table, and the symmetric
    fallback group."""
    run_py("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.core import make_workload, stack_indices
        from repro.core.partition import pack_plan, partitioned_lookup
        from repro.core.strategies import ChunkAssignment, Plan, Strategy
        wl = make_workload("rej", [512, 64, 96, 40], dim=16, batch=32)
        plan = Plan(
            workload_name="rej", n_cores=4,
            assignments=(
                ChunkAssignment(0, 0, 0, 512, Strategy.GM, batch_frac=(0, 2)),
                ChunkAssignment(0, 1, 0, 512, Strategy.L1, batch_frac=(1, 2)),
                ChunkAssignment(1, 1, 0, 32, Strategy.L1_UB),
                ChunkAssignment(1, 2, 32, 32, Strategy.L1_UB),
                ChunkAssignment(2, 3, 0, 96, Strategy.GM_UB),
            ),
            symmetric_tables=(3,), symmetric_strategies=(Strategy.L1_UB,),
        )
        plan.validate(wl.tables)
        params = [jax.random.normal(jax.random.PRNGKey(i), (t.rows, 16), jnp.float32)
                  for i, t in enumerate(wl.tables)]
        packed = pack_plan(plan, wl.tables, params)
        idx = [jax.random.randint(jax.random.PRNGKey(i+10), (wl.batch, t.seq), 0, t.rows)
               for i, t in enumerate(wl.tables)]
        sidx = stack_indices(idx, 1)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        outs = {}
        for mode in ("sparse", "psum"):
            for uk in (False, "fused"):
                outs[(mode, uk)] = np.asarray(partitioned_lookup(
                    packed, sidx, mesh=mesh, n_tables=4,
                    use_kernels=uk, reduce_mode=mode))
        for key, got in outs.items():
            np.testing.assert_allclose(got, outs[("psum", False)],
                                       rtol=2e-5, atol=2e-5, err_msg=str(key))
        print("OK")
    """)


def test_partitioned_lookup_fused_kernel():
    """One fused multi-slot pallas_call for the whole slot sweep."""
    run_py("""
        import dataclasses, jax, numpy as np
        from repro.core import PartitionedEmbeddingBag, make_workload, analytic_model, TPU_V5E
        hw = dataclasses.replace(TPU_V5E, l1_bytes=4096)
        model = analytic_model(hw)
        wl = make_workload("t", [100, 57, 1000, 8, 3000], dim=16, seqs=[1,2,1,4,1], batch=32)
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,)*2)
        bag = PartitionedEmbeddingBag(wl, n_cores=4, planner="asymmetric", cost_model=model)
        params = bag.init(jax.random.PRNGKey(0)); packed = bag.pack(params)
        idx = [jax.random.randint(jax.random.PRNGKey(i+10), (wl.batch, t.seq), 0, t.rows)
               for i, t in enumerate(wl.tables)]
        got = bag.apply(packed, idx, mesh=mesh, use_kernels="fused")
        np.testing.assert_allclose(np.asarray(got), np.asarray(bag.reference(params, idx)),
                                   rtol=2e-5, atol=2e-5)
        print("OK")
    """)
