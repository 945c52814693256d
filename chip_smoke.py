"""Prove that the taobao DLRM serving path runs on a TPU, end to end.

    python chip_smoke.py               # one chip: phases (a) and (b)
    python chip_smoke.py --four-chips  # four chips: the (1, 4) mesh phases

Every phase builds the paper's taobao table set at its published
cardinalities (15 tables, 3,142,468 rows, E=16, float32, random weights from
``--seed``) behind the default DLRM towers, through
``InferenceEngine.build``, and serves a few batches of 8192 queries through
``engine.serve()`` with the DLRM step of ``repro.launch.serve``.  It checks
that the compiled step holds the fused ragged Pallas kernel, that every
query was served with no failed or degraded batch, and that the answers
agree with ``engine.reference_view()`` (the XLA ``jnp.take`` path on the
same packed tables):

* (a) uniform traffic, the default ``EngineConfig`` (asymmetric planner,
  ragged layout, fused kernel, sparse rejoin, no access reduction);
* (b) zipf-1.2 traffic with ``access="full"`` and ``kernel_path="auto"``, so
  batch dedup, the residency cache and the sparse gather all run;
* ``--four-chips``: taobao on a (1, 4) mesh, the owner-sharded sparse rejoin
  against psum and against the reference, under two plans: the default one
  (the large tables in the replicated, batch-split symmetric group) and one
  with ``shard_rocks`` (each large table owned by one core).

Latencies it prints come from a smoke run, not a benchmark.  The last line
of its output is one JSON object, printed only when every phase passed on a
TPU.  It runs in one process: nothing it starts touches the chip.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time
from pathlib import Path

import numpy as np

BATCH = 8192  # the paper's batch (Table I)
N_BATCHES = 3  # served per phase
# the fused ragged kernel's custom call, named by the op that emits it (the
# symmetric group's per-table kernels are tpu_custom_calls too)
_FUSED = re.compile(
    r'custom_call_target="tpu_custom_call".*'
    r'op_name="[^"]*multi_embedding_bag_ragged[^"]*pallas_call"'
)


def _fail(msg: str) -> None:
    print(f"[smoke] FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def _device() -> dict:
    import jax

    devs = jax.devices()
    dev = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    print(f"[smoke] device platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}")
    if dev["platform"] != "tpu":
        _fail(f"no TPU: JAX runs on {dev['platform']!r}")
    return dev


def _plan_line(engine) -> str:
    plan = engine.plan
    cores = sorted({a.core for a in plan.assignments})
    strat = {}
    for a in plan.assignments:
        strat[a.strategy.name] = strat.get(a.strategy.name, 0) + 1
    sym = {}
    for s in plan.symmetric_strategies:
        sym[s.name] = sym.get(s.name, 0) + 1
    return (f"plan cores={plan.n_cores} chunk_cores={cores} "
            f"chunks={len(plan.assignments)} strategies={strat} "
            f"symmetric={sym} block_r={engine.packed.block_r} "
            f"steps={engine.packed.step_slot.shape[-1]} "
            f"unique_cap={engine.packed.unique_cap} "
            f"cache_rows={engine.packed.cache_rows} "
            f"kernel_path={engine.packed.kernel_path}")


def _require_kernel(name: str, compiled) -> None:
    if not _FUSED.search(compiled.as_text()):
        _fail(f"{name}: the compiled step holds no fused ragged kernel "
              "(tpu_custom_call of multi_embedding_bag_ragged)")


def run_phase(
    name, config, traffic, *, seed, mesh=None, rejoin_modes=(),
    access_paths=False,
):
    """Build, compile, serve and check one configuration; returns the
    engine.  ``rejoin_modes`` lists the rejoins whose pooled output is
    checked against the reference (default: the config's own);
    ``access_paths`` requires the pack to arm batch dedup, the residency
    cache and sparse-gather steps."""
    import jax

    from repro.data import distributions as dist_lib
    from repro.data.workloads import get_workload
    from repro.engine import InferenceEngine
    from repro.launch.serve import dlrm_step_maker, serving_faults
    from repro.models.dlrm import DLRMConfig, init_dlrm

    wl = get_workload("taobao", BATCH)
    cfg = DLRMConfig(arch="dlrm-taobao", workload=wl)
    params = init_dlrm(cfg, jax.random.PRNGKey(seed))
    t0 = time.perf_counter()
    engine = InferenceEngine.build(params["tables"], wl, config, mesh=mesh)
    print(f"[smoke] {name}: build {time.perf_counter() - t0:.1f}s "
          f"{_plan_line(engine)}")
    packed = engine.packed
    if access_paths and not (
        packed.unique_cap and packed.cache_rows
        and packed.kernel_path != "onehot"
    ):
        _fail(f"{name}: the pack does not arm dedup, the residency cache "
              "and sparse-gather steps")

    rng = np.random.default_rng(seed)
    dist = dist_lib.get_distribution(traffic)
    batches = [
        (
            dist_lib.sample_workload(rng, wl, dist, BATCH),
            rng.standard_normal((BATCH, cfg.n_dense)).astype(np.float32),
        )
        for _ in range(N_BATCHES)
    ]
    make_step = dlrm_step_maker(cfg, params)
    step = make_step(engine)
    example = {"dense": batches[0][1], "indices": batches[0][0]}
    t0 = time.perf_counter()
    compiled = step.lower(example).compile()
    print(f"[smoke] {name}: compile {time.perf_counter() - t0:.1f}s")
    _require_kernel(name, compiled)

    srv = engine.serve(
        make_step=lambda eng: step, split_fn=lambda out, n: list(out[:n]),
        max_batch=BATCH,
    )
    served = []
    for idx, dense in batches:
        handles = [
            srv.submit_request({"dense": dense[q], "indices": idx[:, q]})
            for q in range(BATCH)
        ]
        srv.pump()
        try:
            served.append(np.array([h.result() for h in handles]))
        except Exception as e:  # a failed batch fails its handles
            _fail(f"{name}: a served request failed: {e!r}")
    unserved = srv.drain()
    stats = srv.stats()
    print(f"[smoke] {name}: served={stats['served']}/{stats['submitted']} "
          f"smoke-run latency (not a benchmark) p50={stats['p50_us']:.0f}us "
          f"p99={stats['p99_us']:.0f}us")
    faults = serving_faults(stats, len(unserved))
    if faults or stats["served"] != stats["submitted"]:
        _fail(f"{name}: {faults or 'served != submitted'}")

    # the reference: the XLA jnp.take path on the same packed tables
    ref = engine.reference_view()
    ref_step = make_step(ref)
    worst_logit = 0.0
    for (idx, dense), got in zip(batches, served):
        want = ref_step([{"dense": dense[q], "indices": idx[:, q]}
                         for q in range(BATCH)])
        scale = max(1.0, float(np.abs(want).max()))
        worst_logit = max(worst_logit, float(np.abs(got - want).max()) / scale)
    # same pooled inputs and the same tower ops: the two programs may fuse
    # differently, and the TPU's default-precision f32 matmuls round through
    # bf16, so reassociated sums may differ in the last bf16 bits.
    if worst_logit > 1e-3:
        _fail(f"{name}: logits differ from the reference by {worst_logit:.3g} "
              "(relative to max(1, |logit|)), over 1e-3")

    def pooled(view, mode):
        return np.asarray(jax.jit(
            lambda packed, i: view.bag.apply(
                packed, i, mesh=view.mesh, use_kernels=view._use_kernels,
                reduce_mode=mode,
            )
        )(view.packed, batches[0][0]))

    want = pooled(ref, ref.config.reduce_mode)
    for mode in rejoin_modes or (config.reduce_mode,):
        got = pooled(engine, mode)
        bitwise = bool(np.array_equal(got, want))
        print(f"[smoke] {name}: pooled rejoin={mode} max|diff| vs reference "
              f"{float(np.abs(got - want).max()):.3g} bitwise={bitwise}; "
              f"logits max relative diff {worst_logit:.3g}")
        # every kernel GEMM runs at HIGHEST precision, where a one-hot row
        # times finite data is an exact copy: the pooled sums must match
        # the reference exactly.
        if not bitwise:
            _fail(f"{name}: pooled embeddings ({mode}) differ from the "
                  "reference")
    return engine


def _check_four_chips(name, engine, *, owned: bool) -> None:
    """Chunks on all four cores, the packed buffer one core per device and,
    when ``owned``, a large table's rows in one core's chunk."""
    plan = engine.plan
    cores = {a.core for a in plan.assignments}
    if cores != {0, 1, 2, 3}:
        _fail(f"{name}: chunks on cores {sorted(cores)}, not all 4")
    buf = engine.packed.chunk_data
    devices = {s.device for s in buf.addressable_shards}
    if len(devices) != 4 or buf.sharding.shard_shape(buf.shape)[0] != 1:
        _fail(f"{name}: packed buffer {buf.shape} is not sharded over "
              f"4 devices ({buf.sharding})")
    biggest = max(t.rows for t in engine.workload.tables)
    held = max(a.rows for a in plan.assignments)
    if owned and (plan.symmetric_tables or held < biggest // 4):
        _fail(f"{name}: no large table is owned by one core (largest chunk "
              f"{held} rows, symmetric tables {list(plan.symmetric_tables)})")
    print(f"[smoke] {name}: chunks on cores {sorted(cores)}, largest chunk "
          f"{held} of the largest table's {biggest} rows; packed buffer "
          f"{buf.shape} sharded one core per device over {len(devices)} "
          "devices")


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run only the (1, 4) mesh phases (needs 4 chips)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro import compat
    from repro.engine import EngineConfig

    print(f"[smoke] compile cache {compat.enable_compilation_cache()}")
    dev = _device()
    if args.four_chips:
        if dev["count"] < 4:
            _fail(f"--four-chips needs 4 chips, JAX sees {dev['count']}")
        mesh = compat.make_mesh((1, 4), ("data", "model"))
        # the default plan sends taobao's large tables to the symmetric
        # (batch-split) group; shard_rocks turns that group off, so each
        # large table is owned by one core and crosses the sparse rejoin.
        for name, options in (("four-chips", {}),
                              ("four-chips-owned", {"shard_rocks": True})):
            engine = run_phase(
                name, EngineConfig(mesh_shape=(1, 4), planner_options=options),
                "uniform", seed=args.seed, mesh=mesh,
                rejoin_modes=("sparse", "psum"),
            )
            _check_four_chips(name, engine, owned=bool(options))
    else:
        mesh = compat.make_mesh((1, 1), ("data", "model"))
        run_phase("a-uniform", EngineConfig(mesh_shape=(1, 1)), "uniform",
                  seed=args.seed, mesh=mesh)
        # the v5e preset gives taobao's tiny tables L1 strategies, and the
        # residency cache carves only GM chunks: with no L1 budget they
        # stream as GM, so the cache has rows to carve.
        run_phase(
            "b-zipf-full",
            EngineConfig(
                mesh_shape=(1, 1), distribution="zipf:1.2", access="full",
                kernel_path="auto", degrade_after=0,
                hardware_options={"l1_bytes": 0},
            ),
            "zipf:1.2", seed=args.seed + 1, mesh=mesh, access_paths=True,
        )
    print(json.dumps({"ok": True, "device": dev}))


if __name__ == "__main__":
    main()
