"""Split one cell's host path by the program's own spans, on the chip.

    python3 bench/stages.py --workload taobao.zipf.sat --seed 7 --seconds 20

Sets the cell up as ``bench/run.py`` does, serves it for ``--seconds`` with
the profiler around the same sub-window of a few batches, and prints one
JSON line: per traced batch, the wall time of each program span
(``repro.validate``, ``repro.stage``, ... ; ``bench/program_trace.py``)
and of the harness's ``bench.submit``, ``bench.pump`` and ``bench.collect``;
the cell's per-layer metrics; the idle gaps named by the innermost span
over them; the median wall of a serving pump inside the traced batches and
outside them (what the profiler costs); and the server's whole-run
counters.  It checks no answer and is not a cell of ``BENCHMARK.json``:
``bench/trace.py`` keeps only the harness's spans, so no per-layer metric
reads the program's host spans yet.  With no TPU it exits 2.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
STAGES = ("validate", "stage", "dispatch", "wait", "fetch", "complete", "step")
COUNTERS = ("batch_fill", "queue_wait_p50_us", "queue_wait_p99_us", "p50_us", "p99_us", "n")


def _timed(pump, walls):
    """``pump`` that appends the wall time of each call that served a batch."""
    def run(*args, **kw):
        t = time.perf_counter()
        out = pump(*args, **kw)
        if out is not None:
            walls.append(time.perf_counter() - t)
        return out
    return run


def split(cell, seed: int, seconds: float, devices, *, t_start: float, log=print) -> dict:
    from jax.profiler import ProfileData

    from bench import harness, program_trace
    from bench import trace as trace_lib

    s = harness.set_up(cell, seed, devices, log)
    setup_s = time.time() - t_start
    walls = []
    s.srv.pump = _timed(s.srv.pump, walls)
    tracer = harness.Tracer(True)
    try:
        win = harness.measure(s, seconds, tracer, log)
        hlo = cell.model.step_hlo(s.srv, *s.pool[0])
        reduced = trace_lib.reduce_dir(tracer.dir, n_chips=cell.chips, hlo_text=hlo)
        files = sorted(glob.glob(os.path.join(tracer.dir, "**", "*.xplane.pb"), recursive=True))
        spans = program_trace.program_spans(ProfileData.from_file(files[-1])) if files else []
    finally:
        tracer.cleanup()
    n = len(tracer.batches)
    ctx = harness.Context(cell=cell, window=win, setup_s=setup_s,
                          peaks=harness.peaks_for(devices[0].device_kind), trace=reduced,
                          traced=harness.traced_batches(s, win, tracer), batch=s.batch,
                          chips=cell.chips)
    inside = set(tracer.batches)
    traced = [w for i, w in enumerate(walls) if i in inside]
    untraced = [w for i, w in enumerate(walls) if i not in inside]
    stats = s.srv.stats()
    return {
        "workload": cell.name, "seed": seed, "traced_batches": n,
        "span_ms": {k: program_trace.span_ms(spans, f"repro.{k}", n) for k in STAGES},
        "harness_ms": {k: program_trace.span_ms(reduced.spans, f"bench.{k}", n)
                       for k in ("submit", "pump", "collect")} if reduced else {},
        "metrics": {k: v["value"] for k, v in harness.read_metrics(ctx, cell.per_layer).items()},
        "idle_gaps": program_trace.idle_gaps(reduced, spans) if reduced else [],
        "pump_ms": {
            "traced_median": float(np.median(traced)) * 1e3 if traced else None,
            "untraced_median": float(np.median(untraced)) * 1e3 if untraced else None,
            "untraced_batches": len(untraced),
        },
        "server": {k: stats.get(k) for k in COUNTERS},
        "device": {"kind": devices[0].device_kind, "count": len(devices)},
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    from repro import compat

    compat.enable_compilation_cache()
    cell = harness.load_cell(args.workload)
    try:
        devices = harness.check_devices(cell.chips)
    except harness.Refused as e:
        print(f"[stages] refused: {e}", file=sys.stderr)
        return 2
    out = split(cell, args.seed, args.seconds, devices, t_start=T_START,
                log=lambda *a, **k: print(*a, file=sys.stderr))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
