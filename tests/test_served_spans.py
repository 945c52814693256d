"""Host spans and device name scopes of the served DLRM path: a profiler
trace of a few served batches holds each span once per batch, the step's
stages inside ``repro.step``, and nothing for an empty pump."""
import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.data.distributions import Uniform, sample_workload
from repro.data.workloads import small_workload
from repro.engine import EngineConfig, InferenceEngine
from repro.launch.serve import dlrm_step_maker
from repro.models.dlrm import DLRMConfig, init_dlrm

BATCH = 16
STEP_STAGES = ("repro.stage", "repro.dispatch", "repro.wait", "repro.fetch")
SERVER_SPANS = ("repro.validate", "repro.step", "repro.complete")


@pytest.fixture(scope="module")
def served():
    wl = small_workload(batch=BATCH)
    cfg = DLRMConfig(arch="dlrm-smoke", workload=wl)
    params = init_dlrm(cfg, jax.random.PRNGKey(0))
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    engine = InferenceEngine.build(params["tables"], wl, EngineConfig(max_batch=BATCH), mesh=mesh)
    srv = engine.serve(make_step=dlrm_step_maker(cfg, params),
                       split_fn=lambda out, n: list(out[:n]))
    rng = np.random.default_rng(0)
    batches = []
    for _ in range(3):
        idx = sample_workload(rng, wl, Uniform(), BATCH)
        dense = rng.standard_normal((BATCH, cfg.n_dense)).astype(np.float32)
        batches.append([{"dense": dense[q], "indices": idx[:, q]} for q in range(BATCH)])
    return srv, batches


def _serve(srv, payloads):
    handles = [srv.submit_request(p) for p in payloads]
    srv.pump()
    assert all(h.done() and h._error is None for h in handles)


def _host_spans(trace_dir):
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                          for ev in line.events if ev.name.startswith("repro.")]
    return sorted(spans, key=lambda s: s[1])


def test_a_traced_batch_opens_each_span_once(served, tmp_path):
    srv, batches = served
    _serve(srv, batches[0])  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        for payloads in batches[1:]:
            _serve(srv, payloads)
            assert srv.pump() is None  # an empty pump: no span
    spans = _host_spans(str(tmp_path))
    names = [n for n, _, _ in spans]
    for name in SERVER_SPANS + STEP_STAGES:
        assert names.count(name) == 2, (name, names)  # once per batch, never per query
    steps = [(s, e) for n, s, e in spans if n == "repro.step"]
    for n, s, e in spans:
        if n in STEP_STAGES:
            assert any(a <= s and e <= b for a, b in steps), n
    # per batch: validate, then the step, then completion
    for i in range(2):
        (v, st, c) = [[x for x in spans if x[0] == n][i] for n in SERVER_SPANS]
        assert v[2] <= st[1] and st[2] <= c[1]


def test_device_ops_carry_the_tower_and_prep_scopes(served):
    srv, batches = served
    p = batches[0]
    batch = {"dense": np.stack([q["dense"] for q in p]),
             "indices": np.stack([q["indices"] for q in p], axis=1)}
    hlo = srv.step_fn.lower(batch).as_text(debug_info=True)
    for scope in ("tower", "lookup_prep"):
        assert f"/{scope}/" in hlo, scope
