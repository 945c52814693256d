"""One run of one cell: set-up, the measured window, the traced window,
the check against the plain reference, and the result line.

Everything a cell needs is found by name from ``BENCHMARK.json``:

* ``bench/configs/<config>.json``: the deployment (its ``kind`` names the
  model adapter ``bench/models/<kind>.py``);
* ``bench/traffic/<traffic>.json``: the mix, read by ``bench/gen.py``;
* ``bench/metrics/<metric>.py``: one reader per metric, ``read(ctx)``,
  which returns a number or ``None`` when it finds nothing to read;
* ``bench/peaks.json``: the chip's peaks, by ``device_kind``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import gen, trace as trace_lib

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
WARM_BATCHES = 2  # full batches served in set-up, so the window compiles nothing
TRACE_BATCHES = 4  # batches inside the traced sub-window
TRACE_SKIP = 2  # window batches served before the profiler starts
LATE_S = 60.0  # how long an open loop waits past its window for the last answers


class Refused(RuntimeError):
    """The run cannot measure this cell here (no chip, too few chips)."""


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(f"bench_{path.stem.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    mix: dict
    model: object
    end_to_end: list
    per_layer: list


def load_cell(name: str) -> Cell:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = json.loads((BENCH / "configs" / f"{w['config']}.json").read_text())
    cfg["name"] = w["config"]
    mix = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())

    def mine(m):
        return name in m.get("workloads", [name])

    return Cell(
        name=name, chips=int(w["chips"]), cfg=cfg, mix=mix,
        model=load_module(BENCH / "models" / f"{cfg['kind']}.py"),
        end_to_end=[m for m in spec["end_to_end"] if mine(m)],
        per_layer=[m for m in spec["per_layer"] if mine(m)],
    )


def check_devices(chips: int) -> list:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise Refused(f"no accelerator: JAX runs on {devs[0].platform!r}")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    if peaks_for(devs[0].device_kind) is None:
        raise Refused(f"no peaks for device kind {devs[0].device_kind!r} in bench/peaks.json")
    return devs


def make_mesh(devices, shape):
    import jax

    n = int(np.prod(shape))
    return jax.sharding.Mesh(
        np.array(devices[:n]).reshape(tuple(shape)), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )


class CompileCounter:
    """Counts XLA compiles (cache hits included) while it is open."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.n = 0

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.n += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


def _span(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


class Tracer:
    """The profiler around a sub-window of a few batches."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir = tempfile.mkdtemp(prefix="bench-trace-") if enabled else None
        self.active = False
        self.done = False
        self.batches = []  # window batch numbers inside the traced window

    def start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.active = True

    def stop(self):
        import jax

        jax.profiler.stop_trace()
        self.active, self.done = False, True

    def cleanup(self):
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


def _collect(handles):
    """(answers, ok mask) of a batch's handles."""
    vals = np.zeros(len(handles), np.float32)
    ok = np.zeros(len(handles), bool)
    for i, h in enumerate(handles):
        if h.done() and h._error is None:
            vals[i] = h.result()
            ok[i] = True
    return vals, ok


def closed_window(srv, pool, seconds, tracer, clock=time.perf_counter):
    """One caller: a full batch, pump, every answer, again, for ``seconds``."""
    n_pool = len(pool)
    batches = []
    t0 = clock()
    while clock() - t0 < seconds:
        i = len(batches)
        if tracer.enabled and not tracer.done:
            if i == TRACE_SKIP:
                tracer.start()
            elif i == TRACE_SKIP + TRACE_BATCHES:
                tracer.stop()
        on = tracer.active
        if on:
            tracer.batches.append(i)
        with _span(on, "bench.submit"):
            handles = [srv.submit_request(q) for q in pool[i % n_pool]]
        deg = srv.degraded_batches
        with _span(on, "bench.pump"):
            srv.pump()
        with _span(on, "bench.collect"):
            vals, ok = _collect(handles)
        if srv.degraded_batches != deg:
            ok[:] = False  # served by the fallback, not by the path under test
        batches.append({"pool": i % n_pool, "vals": vals, "ok": ok})
    t_end = clock()
    if tracer.active:
        tracer.stop()
    served = int(sum(b["ok"].sum() for b in batches))
    attempted = sum(len(b["ok"]) for b in batches)
    return {
        "loop": "closed", "t0": t0, "t_end": t_end, "batches": batches,
        "attempted": attempted, "served": served,
        "qps": served / (t_end - t0),
    }


def _flush(srv, filler):
    """Serve what the schedule left queued, topped up to whole batches so no
    new shape compiles; these answers are not measured."""
    batch = srv.batcher.max_batch
    while srv.batcher.queue:
        for q in filler[: (-len(srv.batcher.queue)) % batch]:
            srv.submit_request(q)
        srv.pump(force=True)


def open_window(srv, pool, seconds, rate_qps, tracer, seed, batch, clock=time.perf_counter):
    """Arrivals due on a schedule from the seed, each submitted stamped with
    its due time; the queries due in the first ``seconds`` are measured, and
    the schedule runs on until every one of them has an answer."""
    n_pool_q = len(pool) * batch
    n = int(gen.mean_rate(rate_qps) * (seconds + LATE_S)) + 2 * batch
    due = gen.arrivals(rate_qps, n, seed)
    n_win = int(np.searchsorted(due, seconds))
    handles = [None] * n  # dropped once answered: the client keeps no history
    vals = np.zeros(n, np.float32)
    ok = np.zeros(n, bool)
    release = np.full(n, np.nan)
    done = np.full(n, np.nan)
    pumps = []
    i = served_ptr = 0
    late = []
    trace_from = seconds / 3
    backlog = None  # queries due but unanswered when the window closed
    t0 = clock()
    deadline = t0 + seconds + LATE_S
    while served_ptr < n_win and i < n:
        now = clock()
        if now > deadline:
            break
        if backlog is None and now - t0 >= seconds:
            backlog = int(np.searchsorted(due, now - t0)) - served_ptr
        if tracer.enabled and not tracer.done:
            if not tracer.active and now - t0 >= trace_from:
                tracer.start()
            elif tracer.active and len(tracer.batches) >= TRACE_BATCHES:
                tracer.stop()
        on = tracer.active
        with _span(on, "bench.submit"):
            while i < n and t0 + due[i] <= now:
                q = i % n_pool_q
                handles[i] = srv.submit_request(pool[q // batch][q % batch], now=t0 + due[i])
                late.append(now - (t0 + due[i]))
                i += 1
        tp = clock()
        deg = srv.degraded_batches
        with _span(on, "bench.pump"):
            out = srv.pump()
        te = clock()
        if out is None:
            with _span(on, "bench.wait"):
                nxt = t0 + due[i] if i < n else te
                time.sleep(min(max(nxt - clock(), 0.0), 0.001))
            continue
        if on:
            tracer.batches.append(len(pumps))
        first = served_ptr
        while served_ptr < i and handles[served_ptr].done():
            served_ptr += 1
        release[first:served_ptr] = tp
        done[first:served_ptr] = te
        vals[first:served_ptr], ok[first:served_ptr] = _collect(handles[first:served_ptr])
        handles[first:served_ptr] = [None] * (served_ptr - first)
        if srv.degraded_batches != deg:
            ok[first:served_ptr] = False  # served by the fallback
        pumps.append({"t_pump": tp, "t_done": te, "pool": (first // batch) % len(pool)})
    if tracer.active:
        tracer.stop()
    t_end = clock()
    _flush(srv, pool[0])
    vals, ok = vals[:n_win], ok[:n_win]
    lat = done[:n_win] - (t0 + due[:n_win])
    queue = release[:n_win] - (t0 + due[:n_win])
    return {
        "loop": "open", "t0": t0, "t_end": t_end, "n_win": n_win,
        "attempted": n_win, "served": int(ok.sum()), "vals": vals, "ok": ok,
        "latency_s": lat[ok], "queue_s": queue[ok], "pumps": pumps,
        "late_s": np.asarray(late), "backlog": backlog,
    }


def check_answers(cell, weights, pool, win, batch, low=False):
    """The widest logit gap between every answer the window produced and the
    reference's answer to the same query.  ``low=True`` puts the control
    (the reference in bfloat16) in the program's place."""
    model, cfg = cell.model, cell.cfg
    want = np.concatenate([model.reference(cfg, weights, *b) for b in pool])
    if low:
        lows = np.concatenate([model.reference(cfg, weights, *b, low=True) for b in pool])
    n_pool_q = len(pool) * batch
    if win["loop"] == "closed":
        q = np.concatenate([b["pool"] * batch + np.arange(batch) for b in win["batches"]])
        vals = np.concatenate([b["vals"] for b in win["batches"]])
        ok = np.concatenate([b["ok"] for b in win["batches"]])
    else:
        q = np.arange(win["n_win"]) % n_pool_q
        vals, ok = win["vals"], win["ok"]
    got = lows[q] if low else vals
    if not ok.any():
        return math.inf
    return model.logit_gap(got[ok], want[q][ok])


@dataclasses.dataclass
class Context:
    """What a metric reader may read."""

    cell: Cell
    window: dict
    setup_s: float
    peaks: dict | None
    trace: object | None  # bench.trace.Reduced of the traced sub-window
    traced: list  # per traced batch: {"distinct": [u_t of each table]}
    batch: int
    chips: int


def peaks_for(kind: str) -> dict | None:
    return json.loads((BENCH / "peaks.json").read_text())["devices"].get(kind)


def read_metrics(ctx: Context, metrics: list) -> dict:
    out = {}
    for m in metrics:
        v = load_module(BENCH / "metrics" / f"{m['name']}.py").read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices]
    return int(max(peaks))


@dataclasses.dataclass
class Served:
    """A cell set up from its seed: the traffic pool, the weights, the
    server, and the requests as the client sends them."""

    cell: Cell
    seed: int
    pool: list  # [(indices (T, B, s), dense (B, n_dense))]
    weights: dict
    srv: object
    requests: list

    @property
    def batch(self) -> int:
        return int(self.cell.cfg["max_batch"])


class _Stages:
    """Logs the seconds each stage of set-up took."""

    def __init__(self, log):
        self.log, self.t = log, time.perf_counter()

    def __call__(self, name):
        t = time.perf_counter()
        self.log(f"[bench] set-up {name} {t - self.t:.3f}s")
        self.t = t


def set_up(cell: Cell, seed: int, devices, log=print) -> Served:
    """Traffic pool and key histogram from the seed, weights on the device,
    the served path built, and every shape the window uses warmed up."""
    cfg, mix, model = cell.cfg, cell.mix, cell.model
    batch = int(cfg["max_batch"])
    rows, seqs = cfg["tables"]["rows"], cfg["tables"]["seq"]
    stage = _Stages(log)
    laws = gen.key_laws(mix["keys"], rows, seed)
    pool = gen.make_batches(laws, seqs, cfg["n_dense"], batch, int(mix["pool_batches"]),
                            gen.rng(seed, gen.STREAM_POOL))
    history = gen.make_batches(laws, seqs, cfg["n_dense"], batch, int(mix["history_batches"]),
                               gen.rng(seed, gen.STREAM_HISTORY))
    freqs = gen.key_counts(history, len(rows))
    stage("traffic")
    weights = model.make_weights(cfg, seed)
    stage("weights")
    srv = model.serve(cfg, weights, freqs, make_mesh(devices, cfg["mesh_shape"]),
                      mix.get("max_wait_s"))
    stage("build")
    requests = [model.payloads(idx, dense) for idx, dense in pool]
    stage("requests")
    for i in range(WARM_BATCHES):
        handles = [srv.submit_request(q) for q in requests[i % len(requests)]]
        srv.pump()
        if not _collect(handles)[1].all():
            log(f"[bench] warm-up batch {i} failed ({srv.stats()['batch_failures']} batch failures)")
    stage("warm-up")
    return Served(cell, seed, pool, weights, srv, requests)


def measure(s: Served, seconds: float, tracer: Tracer, log=print) -> dict:
    """The measured window, with the profiler around a few of its batches
    when ``tracer`` is on."""
    with CompileCounter() as compiles:
        if s.cell.mix["loop"] == "closed":
            win = closed_window(s.srv, s.requests, seconds, tracer)
        else:
            win = open_window(s.srv, s.requests, seconds, s.cell.mix["rate_qps"], tracer,
                              s.seed, s.batch)
    stats = s.srv.stats()
    log(f"[bench] window {win['t_end'] - win['t0']:.3f}s attempted={win['attempted']} "
        f"served={win['served']} compiles_in_window={compiles.n} "
        f"batch_failures={stats['batch_failures']} degraded_batches={stats['degraded_batches']} "
        f"pending={stats['pending']}")
    if win["loop"] == "open" and len(win["late_s"]):
        log(f"[bench] submit lateness p50={np.percentile(win['late_s'], 50) * 1e3:.3f}ms "
            f"p99={np.percentile(win['late_s'], 99) * 1e3:.3f}ms (waits behind a pump included)")
    return win


def traced_batches(s: Served, win: dict, tracer: Tracer) -> list:
    """Per batch inside the traced window, the distinct ids it looked up."""
    items = win["batches"] if win["loop"] == "closed" else win["pumps"]
    return [{"distinct": gen.distinct_per_table(s.pool[items[i]["pool"]][0])}
            for i in tracer.batches]


def verdict(s: Served, win: dict) -> dict:
    """Each number compared with the reference, beside its limit."""
    gap = check_answers(s.cell, s.weights, s.pool, win, s.batch)
    return {
        "logit_gap": {"value": gap, "limit": s.cell.model.LOGIT_GAP_LIMIT},
        "unanswered": {"value": int(win["attempted"] - win["served"]), "limit": 0},
    }


def run(cell: Cell, seed: int, seconds: float, trace: bool, devices, *, t_start: float,
        log=print) -> dict:
    """Set up, measure, check; returns the result object."""
    import jax

    s = set_up(cell, seed, devices, log)
    setup_s = time.time() - t_start
    log(f"[bench] set-up {setup_s:.3f}s")
    tracer = Tracer(trace)
    try:
        win = measure(s, seconds, tracer, log)
        mem = memory_peak(devices[: cell.chips])
        reduced = None
        if trace:
            hlo = cell.model.step_hlo(s.srv, *s.pool[0])
            reduced = trace_lib.reduce_dir(tracer.dir, n_chips=cell.chips, hlo_text=hlo)
    finally:
        tracer.cleanup()
    traced = traced_batches(s, win, tracer)
    # free the program's state before the reference runs
    s.srv = None
    gc.collect()
    checks = verdict(s, win)
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    dev0 = devices[0]
    ctx = Context(cell=cell, window=win, setup_s=setup_s, peaks=peaks_for(dev0.device_kind),
                  trace=reduced, traced=traced, batch=s.batch, chips=cell.chips)
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": int(win["attempted"]),
              "failed": checks["unanswered"]["value"],
              "metrics": read_metrics(ctx, cell.per_layer if trace else cell.end_to_end),
              "device": device}
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = reduced.breakdown()
    for k, c in checks.items():
        log(f"[bench] check {k} = {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    result["checks"] = checks
    return result
