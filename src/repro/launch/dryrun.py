import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# (debug override must also happen before jax initializes its backends)
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_DRYRUN_DEVICES"]
    )

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this lowers the real step function (train_step / prefill_step /
serve_step) with ShapeDtypeStruct inputs and explicit in/out shardings on the
production mesh, compiles it, and records:

* ``memory_analysis()``  — per-device bytes (proves the cell fits 16 GB HBM);
* ``cost_analysis()``    — XLA's raw numbers (while bodies counted once);
* scan-aware HLO totals  — FLOPs / bytes / collective bytes via
  ``launch.hlo_analysis`` (trip-count-aware; feeds §Roofline);

Artifacts: ``artifacts/dryrun/<arch>__<shape>__<mesh>.json``; reruns skip
existing artifacts (resumable).  Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod both]
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro import sharding as sh
from repro.configs.base import SHAPES, ShapeCfg
from repro.launch.hlo_analysis import analyze_hlo, xla_cost_analysis
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.models import transformer as T
from repro.models.registry import build
from repro.training.optimizer import adamw

ARTIFACT_DIR = Path(os.environ.get("REPRO_ARTIFACTS", "artifacts/dryrun"))


def _named(mesh, specs):
    return jax.tree.map(
        lambda s: jax.sharding.NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec),
    )


def make_ctx(mesh, shape: ShapeCfg, multi_pod: bool) -> T.ShardCtx:
    n_dp = sh.dp_size(mesh)
    return T.ShardCtx(
        mesh=mesh,
        model_axis="model",
        data_axes=("pod", "data") if multi_pod else ("data",),
        shard_batch=shape.batch % n_dp == 0,
    )


DLRM_SHAPES = {
    "serve_8k": 8192,
    "serve_64k": 65536,
}


def lower_dlrm_cell(arch: str, shape_name: str, mesh, multi_pod: bool):
    """DLRM partitioned-serving cells: the paper's own model on the mesh.

    arch = "dlrm-<workload>"; lowers forward_packed (partitioned embedding
    lookups via the asymmetric plan with TPU-profile rock sharding + top MLP)
    with the packed plan sharded over the "model" axis.
    """
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.core import analytic_model
    from repro.core.embedding import PartitionedEmbeddingBag
    from repro.data.workloads import get_workload
    from repro.models.dlrm import DLRMConfig, forward_packed, init_dlrm

    wl_name = arch[len("dlrm-"):]
    batch = DLRM_SHAPES[shape_name]
    wl = get_workload(wl_name, batch)
    cfg = DLRMConfig(arch=arch, workload=wl)
    model = analytic_model()
    k_cores = mesh.shape["model"]
    bag = PartitionedEmbeddingBag(
        wl, n_cores=k_cores, planner="asymmetric", cost_model=model,
        dtype=jnp.bfloat16,
        planner_kwargs=dict(shard_rocks=True),
    )
    packed_struct = jax.eval_shape(lambda: bag.pack(None))
    mlp_struct = jax.eval_shape(
        lambda: init_dlrm(cfg, jax.random.PRNGKey(0))
    )
    mlp_struct = {k: v for k, v in mlp_struct.items() if k != "tables"}
    s_max = max(t.seq for t in wl.tables)
    dp = ("pod", "data") if multi_pod else ("data",)
    batch_struct = {
        "dense": jax.ShapeDtypeStruct((batch, cfg.n_dense), jnp.float32),
        "indices": jax.ShapeDtypeStruct(
            (len(wl.tables), batch, s_max), jnp.int32
        ),
    }

    def named(spec):
        return jax.sharding.NamedSharding(mesh, spec)

    packed_sh = jax.tree.map(
        lambda _: named(P()), packed_struct
    )
    from repro.core.partition import PackedPlan

    for f in PackedPlan._ARRAY_FIELDS:
        if f in PackedPlan._REPLICATED_FIELDS:
            continue
        nd = getattr(packed_struct, f).ndim
        object.__setattr__ if False else setattr(
            packed_sh, f, named(P("model", *([None] * (nd - 1))))
        )
    mlp_sh = jax.tree.map(lambda _: named(P()), mlp_struct)
    batch_sh = {
        "dense": named(P(dp, None)),
        "indices": named(P(None, dp, None)),
    }

    def serve(packed, mlp_params, batch_in):
        return forward_packed(
            cfg, bag, packed, mlp_params, batch_in,
            mesh=mesh, axis="model", batch_axes=(),
        )

    jitted = jax.jit(serve, in_shardings=(packed_sh, mlp_sh, batch_sh))
    return jitted, (packed_struct, mlp_struct, batch_struct), {"cfg": cfg}


def lower_cell(arch: str, shape_name: str, mesh, multi_pod: bool, smoke: bool = False):
    """Returns (jitted_fn, example_args, meta)."""
    if arch.startswith("dlrm-"):
        return lower_dlrm_cell(arch, shape_name, mesh, multi_pod)
    bundle = build(arch, smoke=smoke)
    cfg = bundle.cfg
    shape = SHAPES[shape_name] if shape_name in SHAPES else shape_name
    assert isinstance(shape, ShapeCfg)
    if not cfg.supports(shape.name):
        return None
    ctx = make_ctx(mesh, shape, multi_pod)
    n_dp = sh.dp_size(mesh)

    params_specs = sh.param_pspecs(bundle.param_struct(), multi_pod)
    batch_specs_p = sh.batch_pspecs(cfg, shape, multi_pod, n_dp)
    batch_struct = bundle.batch_specs(shape)

    if shape.kind == "train":
        opt = adamw(
            3e-4,
            moments_dtype=jnp.bfloat16 if cfg.low_precision_opt else None,
        )
        params_struct = bundle.param_struct()
        opt_struct = jax.eval_shape(opt.init, params_struct)
        opt_specs = sh.opt_pspecs(opt_struct, params_specs)
        fn = bundle.train_step(ctx, opt, shape)
        in_sh = (
            _named(mesh, params_specs),
            _named(mesh, opt_specs),
            _named(mesh, batch_specs_p),
        )
        out_sh = (
            _named(mesh, params_specs),
            _named(mesh, opt_specs),
            None,
        )
        jitted = jax.jit(
            fn, in_shardings=in_sh, out_shardings=out_sh, donate_argnums=(0, 1)
        )
        args = (params_struct, opt_struct, batch_struct)
    elif shape.kind == "prefill":
        params_struct = bundle.param_struct(jnp.bfloat16)
        fn = bundle.prefill_step(ctx, shape)
        cache_specs = sh.cache_pspecs(cfg, shape, multi_pod, n_dp)
        in_sh = (_named(mesh, params_specs), _named(mesh, batch_specs_p))
        out_sh = (None, _named(mesh, cache_specs))
        jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh)
        args = (params_struct, batch_struct)
    else:  # decode
        params_struct = bundle.param_struct(jnp.bfloat16)
        cache_struct = bundle.cache_struct(shape)
        cache_specs = sh.cache_pspecs(cfg, shape, multi_pod, n_dp)
        fn = bundle.serve_step(ctx)
        in_sh = (
            _named(mesh, params_specs),
            _named(mesh, cache_specs),
            _named(mesh, batch_specs_p),
        )
        out_sh = (None, _named(mesh, cache_specs))
        jitted = jax.jit(
            fn, in_shardings=in_sh, out_shardings=out_sh, donate_argnums=(1,)
        )
        args = (params_struct, cache_struct, batch_struct)
    return jitted, args, {"cfg": cfg, "shape": shape}


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    *,
    smoke: bool = False,
    mesh=None,
    out_dir: Path = ARTIFACT_DIR,
    force: bool = False,
) -> dict | None:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    if mesh is not None:
        mesh_name = "debug" + "x".join(str(s) for s in mesh.devices.shape)
    out_dir.mkdir(parents=True, exist_ok=True)
    out_path = out_dir / f"{arch}__{shape_name}__{mesh_name}.json"
    if out_path.exists() and not force:
        return json.loads(out_path.read_text())
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)

    t0 = time.time()
    res = lower_cell(arch, shape_name, mesh, multi_pod, smoke=smoke)
    if res is None:
        record = {
            "arch": arch, "shape": shape_name, "mesh": mesh_name,
            "status": "skipped (unsupported: full-attention long-context "
                      "or no decode path)",
        }
        out_path.write_text(json.dumps(record, indent=2))
        return record
    jitted, args, meta = res
    try:
        lowered = jitted.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        cost = xla_cost_analysis(compiled)
        text = compiled.as_text()
        hlo = analyze_hlo(text)
        record = {
            "arch": arch,
            "shape": shape_name,
            "mesh": mesh_name,
            "status": "ok",
            "devices": int(jnp.prod(jnp.asarray(mesh.devices.shape))),
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_compile, 1),
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
                "peak_estimate_bytes": mem.argument_size_in_bytes
                + mem.temp_size_in_bytes
                + mem.output_size_in_bytes
                - mem.alias_size_in_bytes,
            },
            "xla_cost": {
                "flops": cost.get("flops"),
                "bytes_accessed": cost.get("bytes accessed"),
            },
            "hlo": hlo.as_dict(),
        }
    except Exception as e:  # record failures — they are bugs to fix
        record = {
            "arch": arch, "shape": shape_name, "mesh": mesh_name,
            "status": "FAILED",
            "error": f"{type(e).__name__}: {e}"[:2000],
            "traceback": traceback.format_exc()[-4000:],
        }
    out_path.write_text(json.dumps(record, indent=2))
    return record


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None)
    p.add_argument("--all", action="store_true")
    p.add_argument("--multi-pod", choices=["off", "on", "both"], default="off")
    p.add_argument("--smoke", action="store_true", help="reduced configs")
    p.add_argument("--debug-mesh", action="store_true")
    p.add_argument("--force", action="store_true")
    p.add_argument("--out", default=str(ARTIFACT_DIR))
    args = p.parse_args(argv)

    from repro.models.registry import ARCH_IDS

    DLRM_ARCHS = ("dlrm-criteo-1tb", "dlrm-huawei-25mb", "dlrm-avazu-ctr")
    archs = list(ARCH_IDS) if (args.all or not args.arch) else [args.arch]
    if args.all and not args.smoke:
        archs += list(DLRM_ARCHS)
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    pods = {"off": [False], "on": [True], "both": [False, True]}[args.multi_pod]

    failures = 0
    for multi_pod in pods:
        mesh = make_debug_mesh(multi_pod=multi_pod) if args.debug_mesh else None
        for arch in archs:
            arch_shapes = (
                list(DLRM_SHAPES) if arch.startswith("dlrm-") else shapes
            )
            for shape in arch_shapes:
                rec = run_cell(
                    arch, shape, multi_pod,
                    smoke=args.smoke, mesh=mesh,
                    out_dir=Path(args.out), force=args.force,
                )
                status = rec["status"]
                extra = ""
                if status == "ok":
                    peak = rec["memory"]["peak_estimate_bytes"] / 2**30
                    extra = (
                        f" peak={peak:.2f}GiB flops={rec['hlo']['flops']:.3g}"
                        f" coll={sum(rec['hlo']['collective_bytes'].values()):.3g}B"
                        f" compile={rec['compile_s']}s"
                    )
                if status == "FAILED":
                    failures += 1
                    extra = " " + rec["error"][:160]
                print(f"[dryrun] {arch:>22s} {shape:>12s} "
                      f"{'2pod' if multi_pod else '1pod'} {status}{extra}",
                      flush=True)
    if failures:
        print(f"[dryrun] {failures} FAILURES", flush=True)
        sys.exit(1)
    print("[dryrun] all cells OK", flush=True)


if __name__ == "__main__":
    main()
