"""Serving entrypoint: engine-driven partitioned DLRM inference.

    PYTHONPATH=src python -m repro.launch.serve --workload kuairec-big \
        --batch 512 --queries 4096

The pipeline is declared by an :class:`repro.engine.EngineConfig` — load one
with ``--config engine.json``, tweak fields with ``--set field=value``
(JSON-parsed), and persist the resolved artifact with ``--save-config`` so a
deployment is reproducible from the one file::

    PYTHONPATH=src python -m repro.launch.serve --workload smoke \
        --set access=full --set distribution=zipf:1.2 --save-config eng.json

Traffic is a driver concern and stays on its own flags: ``--distribution``
picks the query stream (``uniform`` / ``zipf:<a>`` /
``hotset:<frac>:<mass>[:<off>]`` / preset / ``all``), ``--drift`` a phase
schedule spec (``flip`` = uniform -> zipf-1.2 -> hot-set-flip) routed
through the request-level :class:`repro.serving.server.Server`.

Serving robustness (DESIGN.md §8) is part of the config: ``--set
max_queue=512 --set admission=shed-oldest --set deadline_s=0.05`` bounds
the admission queue and sheds stale requests; ``--set degrade_after=3``
arms the degraded-mode fallback (XLA reference path) against a crashing
fused kernel.  The per-run report includes the request-accounting
counters (submitted/served/shed/rejected/deadline_misses/batch_failures/
degraded_batches).  The run exits non-zero when any batch failed or was
served degraded, or when queries were left unserved.

Legacy flag spellings (``--planner``, ``--layout``, ``--kernels``,
``--reduce``, ``--autotune``, ``--dedup``, ``--cache``, ``--replan``,
``--replan-threshold``) still work: each maps onto the corresponding
``EngineConfig`` field and emits a ``DeprecationWarning`` naming its
replacement (see :func:`config_from_args`).
"""
from __future__ import annotations

import argparse
import json
import sys
import warnings
from pathlib import Path

import numpy as np

from repro.engine import EngineConfig


def _resolve_dists(spec: str) -> list[tuple[str, object]]:
    """CLI --distribution -> [(label, Distribution)]."""
    from repro.data import distributions as dist_lib

    if spec == "all":
        return [
            ("uniform", dist_lib.Uniform()),
            ("real", dist_lib.Zipf(1.05, hot_prefix=False)),
            ("fixed", dist_lib.Fixed()),
        ]
    return [(spec, dist_lib.get_distribution(spec))]


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser()
    # driver flags (what traffic to serve, how much).  --workload /
    # --distribution default to None sentinels so a --preset can fill them;
    # without one they resolve to the historical "smoke" / "real".
    p.add_argument("--workload", default=None)
    p.add_argument("--batch", type=int, default=None,
                   help="serving batch size (default: the config's "
                        "max_batch, 256)")
    p.add_argument("--queries", type=int, default=2048)
    p.add_argument("--distribution", default=None,
                   help="query stream: uniform | real | fixed | all | "
                        "zipf:<a> | hotset:<frac>:<mass>[:<off>] | "
                        "<workload preset> (default: real)")
    p.add_argument("--preset", default=None,
                   help="curated preset pack (workload + traffic + "
                        "EngineConfig) from src/repro/configs/presets, "
                        "e.g. taobao-zipf12; explicit flags still override")
    p.add_argument("--drift", default=None,
                   help="drift schedule spec routed through the Server, "
                        "e.g. 'flip' or 'uniform@8,zipf:1.2@8,"
                        "hotset:0.01:0.9:-1@8'")
    # canonical engine surface
    p.add_argument("--config", type=Path, default=None,
                   help="EngineConfig JSON artifact to build from")
    p.add_argument("--set", action="append", default=[], dest="overrides",
                   metavar="FIELD=VALUE",
                   help="override an EngineConfig field (VALUE is JSON, "
                        "e.g. --set access=full --set "
                        "drift_options='{\"threshold\":0.3}')")
    p.add_argument("--save-config", type=Path, default=None,
                   help="write the resolved EngineConfig JSON and continue")
    # legacy flag spellings — deprecated, mapped onto EngineConfig with a
    # DeprecationWarning each (None/False defaults detect explicit use)
    p.add_argument("--planner", default=None,
                   choices=["baseline", "symmetric", "asymmetric"],
                   help="[deprecated: --set planner=...]")
    p.add_argument("--layout", default=None, choices=["ragged", "dense"],
                   help="[deprecated: --set layout=...]")
    p.add_argument("--kernels", default=None, choices=["fused", "xla"],
                   help="[deprecated: --set use_kernels=...]")
    p.add_argument("--reduce", default=None,
                   choices=["sparse", "psum", "ring"],
                   help="[deprecated: --set reduce_mode=...]")
    p.add_argument("--autotune", action="store_true",
                   help="[deprecated: --set tuning=sweep]")
    p.add_argument("--dedup", action="store_true",
                   help="[deprecated: --set access=dedup|full]")
    p.add_argument("--cache", action="store_true",
                   help="[deprecated: --set access=cache|full]")
    p.add_argument("--replan", action="store_true",
                   help="[deprecated: --set drift=replan]")
    p.add_argument("--replan-threshold", type=float, default=None,
                   help="[deprecated: --set "
                        "drift_options='{\"threshold\":...}']")
    return p


def _warn_legacy(flag: str, replacement: str) -> None:
    warnings.warn(
        f"--{flag} is a deprecated spelling; set EngineConfig.{replacement} "
        f"(via --config / --set) instead",
        DeprecationWarning,
        stacklevel=3,
    )


# the serve CLI's historical drift-trigger cadence (PR 3) — kept as the
# defaults the --replan shim fills into drift_options
_CLI_DRIFT_DEFAULTS = {"check_every": 4, "patience": 2, "cooldown": 8}


def config_from_args(args) -> EngineConfig:
    """Resolve the CLI namespace into one :class:`EngineConfig`.

    Precedence: ``--preset`` / ``--config`` base (mutually exclusive, else
    defaults) < legacy flags (each with a :class:`DeprecationWarning`) <
    ``--set`` overrides.  A preset also fills ``args.workload`` /
    ``args.distribution`` unless those flags were given explicitly.  Also
    bakes in the serve CLI's historical choices: ``shard_rocks=True`` for
    the asymmetric planner (the TPU profile) and the PR3 drift-trigger
    cadence.
    """
    preset = None
    if getattr(args, "preset", None):
        if args.config:
            raise SystemExit("--preset and --config are mutually exclusive")
        from repro.configs.presets import load_preset

        preset = load_preset(args.preset)
    if preset is not None:
        config = EngineConfig.from_dict(preset["config"])
    elif args.config:
        config = EngineConfig.load(args.config)
    else:
        config = EngineConfig()
    # resolve the driver-flag sentinels: explicit flag > preset > historical
    # default — main() reads the resolved values back off the namespace.
    if args.workload is None:
        args.workload = preset["workload"] if preset else "smoke"
    if args.distribution is None:
        args.distribution = (
            preset.get("distribution") if preset else None
        ) or "real"

    if args.planner is not None:
        _warn_legacy("planner", "planner")
        config.planner = args.planner
    if args.layout is not None:
        _warn_legacy("layout", "layout")
        config.layout = args.layout
    if args.kernels is not None:
        _warn_legacy("kernels", "use_kernels")
        config.use_kernels = args.kernels
    if args.reduce is not None:
        _warn_legacy("reduce", "reduce_mode")
        config.reduce_mode = args.reduce
    if args.autotune:
        _warn_legacy("autotune", "tuning='sweep'")
        config.tuning = "sweep"
    if args.dedup or args.cache:
        dedup = args.dedup or config.access in ("dedup", "full")
        cache = args.cache or config.access in ("cache", "full")
        if args.dedup:
            _warn_legacy("dedup", "access='dedup' (or 'full')")
        if args.cache:
            _warn_legacy("cache", "access='cache' (or 'full')")
        config.access = {(True, True): "full", (True, False): "dedup",
                         (False, True): "cache"}[(dedup, cache)]
    if args.replan:
        _warn_legacy("replan", "drift='replan'")
        config.drift = "replan"
    if args.replan_threshold is not None:
        # like the old CLI, the threshold alone does NOT arm replanning —
        # it only takes effect alongside --replan / drift='replan'
        _warn_legacy("replan-threshold", "drift_options['threshold']")
        config.drift_options["threshold"] = args.replan_threshold
    if args.batch is not None:
        config.max_batch = args.batch

    for spec in args.overrides:
        field, sep, value = spec.partition("=")
        if not sep:
            raise SystemExit(f"--set expects FIELD=VALUE, got {spec!r}")
        if field not in {f.name for f in EngineConfig.__dataclass_fields__.values()}:
            raise SystemExit(f"--set: unknown EngineConfig field {field!r}")
        try:
            value = json.loads(value)
        except json.JSONDecodeError:
            pass  # bare strings: --set access=full
        setattr(config, field, value)

    if config.drift == "replan":
        # the serve CLI's historical trigger cadence, however replan was
        # spelled (--replan, --set drift=replan, or a --config file)
        for k, v in _CLI_DRIFT_DEFAULTS.items():
            config.drift_options.setdefault(k, v)
    # the query stream doubles as the pricing distribution unless the
    # config pins its own ("all" streams start from the uniform leg)
    if config.distribution is None and args.distribution:
        config.distribution = ("uniform" if args.distribution == "all"
                               else args.distribution)
    # serve CLI historical default: rocks are row-sharded, not replicated
    # (per-chip HBM on a pod — DESIGN.md §2)
    if config.planner == "asymmetric":
        config.planner_options.setdefault("shard_rocks", True)
    config.validate()
    return config


def dlrm_step_maker(cfg, params):
    """``make_step(engine)`` for the serving loop: one step over request
    payloads is the full DLRM forward on the engine's packed embeddings.
    The drift policy re-invokes it on every shadow re-pack.  The packed
    tables and the tower weights are arguments of the jitted forward, not
    constants baked into it, so the executable stays small enough for the
    compile cache.  ``step.lower(batch)`` lowers that forward for a
    ``{"dense", "indices"}`` batch.

    Each call opens the host spans ``repro.stage`` (the payloads stacked
    into ``dense`` and ``indices``), ``repro.dispatch`` (the jitted call
    until it returns), ``repro.wait`` (``block_until_ready``) and
    ``repro.fetch`` (the output copied to the host)."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.models.dlrm import forward_packed

    mlp = {k: params[k] for k in ("bottom", "top")}

    def make_step(engine):
        @jax.jit
        def infer(packed, mlp, batch):
            return forward_packed(
                cfg, engine.bag, packed, mlp, batch,
                mesh=engine.mesh, use_kernels=engine._use_kernels,
                reduce_mode=engine.config.reduce_mode,
            )

        def step(payloads):
            with TraceAnnotation("repro.stage"):
                dense = np.stack([q["dense"] for q in payloads])
                idx = np.stack([q["indices"] for q in payloads], axis=1)
            batch = {"dense": dense, "indices": idx}
            # the argument copy to the device, then the enqueue
            with TraceAnnotation("repro.dispatch"):
                out = infer(engine.packed, mlp, batch)
            with TraceAnnotation("repro.wait"):
                out = jax.block_until_ready(out)
            with TraceAnnotation("repro.fetch"):
                return np.asarray(out)

        step.lower = lambda batch: infer.lower(engine.packed, mlp, batch)
        return step

    return make_step


def serving_faults(stats: dict, unserved: int = 0) -> list[str]:
    """What went wrong in a serving run, from the server's counters: empty
    when every submitted query was served by the primary path."""
    faults = [
        f"{k}={stats[k]}" for k in ("batch_failures", "degraded_batches")
        if stats.get(k)
    ]
    if unserved:
        faults.append(f"unserved={unserved}")
    return faults


def _exit_on_faults(faults: list[str]) -> None:
    if faults:
        print(f"[serve] FAILED: {' '.join(faults)}")
        sys.exit(1)


def main(argv=None):
    args = build_parser().parse_args(argv)
    config = config_from_args(args)  # also resolves --preset into args
    known = ["smoke"]
    from repro.data.workloads import WORKLOADS

    if args.workload not in known + list(WORKLOADS):
        raise SystemExit(f"unknown workload {args.workload!r}")
    batch = config.max_batch  # precedence: --config < --batch < --set
    if args.save_config:
        config.save(args.save_config)
        print(f"[serve] wrote {args.save_config}")

    import jax

    from repro import compat
    from repro.data import distributions as dist_lib
    from repro.data.workloads import get_workload, small_workload
    from repro.engine import InferenceEngine
    from repro.models.dlrm import DLRMConfig, init_dlrm
    from repro.serving.server import BatchExecutionError

    compat.enable_compilation_cache()

    wl = (small_workload(batch=batch) if args.workload == "smoke"
          else get_workload(args.workload, batch))
    cfg = DLRMConfig(arch=f"dlrm-{args.workload}", workload=wl)
    n_dev = jax.device_count()
    mesh = compat.make_mesh((1, n_dev), ("data", "model"))
    params = init_dlrm(cfg, jax.random.PRNGKey(0))

    # size "flip"-style default phases to a third of the run so every phase
    # is actually visited (explicit "@N" specs override per phase)
    n_batches = max(args.queries // batch, 1)
    schedule = (
        dist_lib.parse_drift(args.drift, phase_batches=max(n_batches // 3, 1))
        if args.drift else None
    )
    resolved = _resolve_dists(args.distribution)[0][1]
    if schedule is None and isinstance(resolved, dist_lib.DriftSchedule):
        # a preset that is itself day-parted (e.g. huawei-25mb) routes
        # through the drift serving loop like an explicit --drift spec
        schedule = resolved
    # pricing: a --drift schedule prices the initial plan under its phase-0
    # distribution (an explicit freqs override, like the drift engine's
    # measured rebuilds); otherwise the engine prices under
    # config.distribution — the file-pinned spec when a --config set one,
    # else the traffic spec config_from_args filled in.
    freqs0 = (
        dist_lib.workload_probs(wl, schedule.at(0))
        if schedule is not None else None
    )
    dist0 = schedule.at(0) if schedule else resolved

    make_step = dlrm_step_maker(cfg, params)
    engine = InferenceEngine.build(
        params["tables"], wl, config, mesh=mesh, freqs=freqs0
    )
    for line in engine.plan_report().splitlines():
        print(f"[serve] {line}")

    # (B,) logits -> one scalar per request handle
    split = lambda out, n: [out[i] for i in range(n)]  # noqa: E731

    if schedule is not None or config.drift != "none":
        _serve_drift(args, wl, schedule or dist_lib.DriftSchedule(
            [(1, dist0)], cycle=True), engine, make_step, split,
            n_dense=cfg.n_dense)
        return

    rng = np.random.default_rng(0)
    step0 = make_step(engine)  # one compile serves every traffic label
    faults = []
    for label, dist in _resolve_dists(args.distribution):
        srv = engine.serve(make_step=lambda eng: step0, split_fn=split)
        failed = 0
        for _ in range(n_batches):
            b = dist_lib.sample_workload(rng, wl, dist, batch)
            dense = rng.standard_normal(
                (batch, cfg.n_dense)).astype(np.float32)
            handles = [
                srv.submit_request({"dense": dense[q], "indices": b[:, q]})
                for q in range(batch)
            ]
            srv.pump()
            for h in handles:
                try:
                    h.result()
                except BatchExecutionError:
                    failed += 1
        unserved = srv.drain()
        if unserved:
            print(f"[serve] WARNING: {len(unserved)} queries left unserved")
        s = srv.stats()
        print(f"[serve] dist={label:8s} p50={_fmt_us(s['p50_us'])} "
              f"p99={_fmt_us(s['p99_us'])}")
        _print_robustness(s)
        faults += [f"{label}:{f}" for f in serving_faults(s, len(unserved))]
        if failed:
            faults.append(f"{label}:failed_requests={failed}")
    _exit_on_faults(faults)


def _fmt_us(v) -> str:
    """An idle server has no latency samples: percentiles come back None
    (not NaN) and must print cleanly."""
    return "     idle" if v is None else f"{v:9.0f}us"


def _print_robustness(s: dict) -> None:
    """One accounting line whenever the run saw any robustness event."""
    if any(s.get(k) for k in ("rejected", "shed", "deadline_misses",
                              "batch_failures", "degraded_batches",
                              "invalid")):
        print(f"[serve]   submitted={s['submitted']} served={s['served']} "
              f"shed={s['shed']} rejected={s['rejected']} "
              f"invalid={s['invalid']} "
              f"deadline_misses={s['deadline_misses']} "
              f"batch_failures={s['batch_failures']} "
              f"degraded_batches={s['degraded_batches']}")
    val = s.get("validation") or {}
    if val.get("oov_indices") or val.get("negative_indices"):
        print(f"[serve]   validation mode={val['mode']} "
              f"oov={val['oov_indices']} negative={val['negative_indices']}")
    integ = s.get("integrity") or {}
    if integ.get("corruptions_detected") or integ.get("poisoned_batches"):
        print(f"[serve]   integrity corruptions={integ['corruptions_detected']} "
              f"heals={integ['heals']} "
              f"quarantined={integ['quarantined_regions']} "
              f"poisoned_batches={integ['poisoned_batches']}")


def _serve_drift(args, wl, schedule, engine, make_step, split, *, n_dense):
    """Drive the engine-built Server through the drift schedule."""
    from repro.data import distributions as dist_lib

    srv = engine.serve(make_step=make_step, split_fn=split)
    rng = np.random.default_rng(0)
    batch = engine.config.max_batch
    n_batches = max(args.queries // batch, 1)
    for b in range(n_batches):
        dist = schedule.at(b)
        idx = dist_lib.sample_workload(rng, wl, dist, batch)
        dense = rng.standard_normal((batch, n_dense)).astype(np.float32)
        for q in range(batch):
            srv.submit({"dense": dense[q], "indices": idx[:, q]})
        srv.pump()
    unserved = srv.drain()
    if unserved:
        print(f"[serve] WARNING: {len(unserved)} queries left unserved")
    s = srv.stats()
    line = (f"[serve] drift p50={_fmt_us(s['p50_us'])} "
            f"p99={_fmt_us(s['p99_us'])}")
    if "replan" in s:
        r = s["replan"]
        line += (f" replans={r['replans']} parity_failures="
                 f"{r['parity_failures']} last_drift={r['last_drift']:.3f}")
    print(line)
    _print_robustness(s)
    for ev in s.get("replan", {}).get("events", []):
        print(f"[serve]   replan@batch={ev['batch']} drift={ev['drift']:.3f} "
              f"parity_ok={ev['parity_ok']}")
    _exit_on_faults(serving_faults(s, len(unserved)))


if __name__ == "__main__":
    main()
