"""Fault tolerance, checkpointing, gradient compression, and serving tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import checkpoint as ckpt
from repro.serving.latency import LatencyTracker
from repro.serving.server import Server
from repro.training import compress
from repro.training.loop import LoopConfig, SimulatedFailure, train
from repro.training.optimizer import adagrad, adamw, sgd


# ---------------------------------------------------------------- checkpoint


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(12.0).reshape(3, 4), "b": [jnp.ones(5), jnp.zeros(2)]}
    ckpt.save(tmp_path, 7, tree)
    restored, step = ckpt.restore(tmp_path, None, tree)
    assert step == 7
    jax.tree.map(lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
                 tree, restored)


def test_checkpoint_keeps_last_n(tmp_path):
    tree = {"x": jnp.zeros(3)}
    for s in range(6):
        ckpt.save(tmp_path, s, tree, keep=2)
    assert ckpt.steps(tmp_path) == [4, 5]


def test_torn_checkpoint_ignored(tmp_path):
    tree = {"x": jnp.ones(3)}
    ckpt.save(tmp_path, 1, tree)
    # simulate a torn write: step dir without commit marker
    torn = tmp_path / "step_00000002"
    torn.mkdir()
    (torn / "manifest.json").write_text("{}")
    assert ckpt.latest_step(tmp_path) == 1
    _, step = ckpt.restore(tmp_path, None, tree)
    assert step == 1


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    ckpt.save(tmp_path, 0, {"x": jnp.zeros((2, 2))})
    with pytest.raises(ValueError):
        ckpt.restore(tmp_path, 0, {"x": jnp.zeros((3, 3))})


# ---------------------------------------------------------------- train loop


def _toy_problem():
    w_true = jnp.array([2.0, -1.0, 0.5])

    def init_state():
        params = {"w": jnp.zeros(3)}
        opt = adamw(5e-2)
        return params, opt.init(params)

    opt = adamw(5e-2)

    def step_fn(params, opt_state, batch):
        def loss_fn(p):
            pred = batch["x"] @ p["w"]
            return jnp.mean((pred - batch["y"]) ** 2)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        params, opt_state = opt.update(grads, opt_state, params)
        return params, opt_state, {"loss": loss}

    def batch_fn(step):
        k = jax.random.PRNGKey(step)
        x = jax.random.normal(k, (32, 3))
        return {"x": x, "y": x @ w_true}

    return init_state, step_fn, batch_fn


def test_train_loop_loss_decreases(tmp_path):
    init_state, step_fn, batch_fn = _toy_problem()
    out = train(
        LoopConfig(total_steps=60, checkpoint_every=20, checkpoint_dir=str(tmp_path)),
        init_state=init_state, step_fn=step_fn, batch_fn=batch_fn,
    )
    assert out["final_loss"] < 0.1 * out["first_loss"]


def test_crash_recovery_resumes(tmp_path):
    """Kill mid-run; restart resumes from the checkpoint, not step 0."""
    init_state, step_fn, batch_fn = _toy_problem()
    cfg = LoopConfig(total_steps=60, checkpoint_every=10,
                     checkpoint_dir=str(tmp_path), fail_at_step=35)
    with pytest.raises(SimulatedFailure):
        train(cfg, init_state=init_state, step_fn=step_fn, batch_fn=batch_fn)
    assert ckpt.latest_step(tmp_path) == 30
    cfg.fail_at_step = None
    out = train(cfg, init_state=init_state, step_fn=step_fn, batch_fn=batch_fn)
    assert out["start_step"] == 31  # resumed, not restarted
    assert out["final_loss"] < 0.5


def test_elastic_restore_new_mesh_shapes(tmp_path):
    """Restore re-places leaves (elastic: different device layout is just a
    different sharding arg; shapes must match)."""
    tree = {"w": jnp.arange(16.0).reshape(4, 4)}
    ckpt.save(tmp_path, 3, tree)
    restored, _ = ckpt.restore(tmp_path, None, tree, shardings=None)
    np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(tree["w"]))


# ---------------------------------------------------------- grad compression


def test_int8_compression_error_feedback_converges():
    """Quantized-gradient descent with error feedback reaches the optimum."""
    w_true = jnp.array([1.5, -2.0, 0.25, 3.0])
    params = {"w": jnp.zeros(4)}
    err = compress.init_error_state(params)
    opt = sgd(0.1)
    state = opt.init(params)
    for step in range(300):
        k = jax.random.PRNGKey(step)
        x = jax.random.normal(k, (64, 4))
        y = x @ w_true

        def loss_fn(p):
            return jnp.mean((x @ p["w"] - y) ** 2)

        grads = jax.grad(loss_fn)(params)
        grads, err = compress.compress_grads(grads, err)
        params, state = opt.update(grads, state, params)
    assert float(jnp.abs(params["w"] - w_true).max()) < 0.05


def test_compression_wire_bytes():
    params = {"w": jnp.zeros((1000,)), "b": jnp.zeros((10,))}
    fp32, int8 = compress.wire_bytes(params)
    assert fp32 == 4 * 1010
    assert int8 < fp32 / 3.5


# ----------------------------------------------------------------- optimizer


@pytest.mark.parametrize("opt_fn", [lambda: sgd(0.1), lambda: sgd(0.1, 0.9),
                                    lambda: adagrad(0.5), lambda: adamw(0.05)])
def test_optimizers_minimize_quadratic(opt_fn):
    opt = opt_fn()
    params = {"w": jnp.array([5.0, -3.0])}
    state = opt.init(params)
    for _ in range(200):
        grads = jax.tree.map(lambda w: 2 * w, params)
        params, state = opt.update(grads, state, params)
    assert float(jnp.abs(params["w"]).max()) < 0.05


# ------------------------------------------------------------------- serving


def test_batcher_and_p99():
    calls = []

    def step(payloads):
        calls.append(len(payloads))
        return [p * 2 for p in payloads]

    srv = Server(step, max_batch=8, max_wait_s=0.0)
    for i in range(40):
        srv.submit(i)
        srv.pump()
    srv.drain()
    s = srv.stats()
    assert s["n"] == 40
    assert s["p99_us"] >= s["p50_us"] > 0
    assert max(calls) <= 8


def test_latency_tracker_covers_the_whole_run():
    """Ten batches of 1,000: every query counts, not the last 2,048."""
    t = LatencyTracker(max_batch=1000)
    for k in range(10):
        enq = np.zeros(1000)
        t.record_batch(enq, t_release=0.5e-3 * (k + 1), t_done=1e-3 * (k + 1), depth=k)
    s = t.summary()
    assert s["n"] == 10_000
    assert s["p50_us"] == pytest.approx(5_000, rel=0.02)
    assert s["p99_us"] == pytest.approx(10_000, rel=0.02)
    assert s["queue_wait_p50_us"] == pytest.approx(2_500, rel=0.02)
    assert s["queue_depth_mean"] == 4.5 and s["queue_depth_max"] == 9
    assert s["batch_fill"] == 1.0


def test_queue_wait_never_exceeds_latency():
    rng = np.random.default_rng(0)
    t = LatencyTracker(max_batch=64)
    for _ in range(50):
        enq = np.sort(rng.uniform(0.0, 0.01, 64))
        release = enq[-1] + rng.uniform(0.0, 1e-3)
        t.record_batch(enq, release, release + rng.uniform(1e-4, 1e-2), depth=0)
    s = t.summary()
    for q in ("p50", "p99"):
        assert 0 < s[f"queue_wait_{q}_us"] <= s[f"{q}_us"]


def test_batch_fill_and_no_samples():
    srv = Server(lambda p: list(p), max_batch=8, max_wait_s=10.0)
    s = srv.stats()
    assert s["batch_fill"] is None and s["queue_wait_p99_us"] is None
    assert "queue_depth_mean" not in s and "tps" not in s
    for i in range(12):
        srv.submit(i)
    assert srv.drain() == []  # a batch of 8, then a forced one of 4
    s = srv.stats()
    assert srv.tracker.batches == 2 and s["batch_fill"] == 12 / 16
    assert s["queue_wait_p99_us"] <= s["p99_us"]


def test_latency_tracker_percentiles():
    t = LatencyTracker()
    for v in range(1, 101):
        t.record(v / 1e6)
    assert t.p50 == pytest.approx(50.5e-6, rel=0.05)
    assert t.p99 == pytest.approx(99e-6, rel=0.05)


def test_elastic_replan_k4_to_k8(tmp_path):
    """Elastic scaling: checkpoint raw tables under a K=4 plan, restart with
    a K=8 plan — the re-packed execution is identical (plans are derived
    state; only raw tables are durable)."""
    import dataclasses

    from repro.core import PartitionedEmbeddingBag, TPU_V5E, analytic_model
    from repro.core.tables import make_workload

    hw = dataclasses.replace(TPU_V5E, l1_bytes=4096)
    model = analytic_model(hw)
    wl = make_workload("el", [100, 57, 1000, 8], dim=16, seqs=[1, 2, 1, 4], batch=16)

    bag4 = PartitionedEmbeddingBag(wl, n_cores=4, planner="asymmetric", cost_model=model)
    params = bag4.init(jax.random.PRNGKey(0))
    ckpt.save(tmp_path, 0, params)

    restored, _ = ckpt.restore(tmp_path, None, params)
    bag8 = PartitionedEmbeddingBag(wl, n_cores=8, planner="asymmetric", cost_model=model)
    bag8.plan.validate(wl.tables)  # a valid plan exists for the new K
    # packing under the new K reproduces identical dense semantics
    idx = [jax.random.randint(jax.random.PRNGKey(i), (wl.batch, t.seq), 0, t.rows)
           for i, t in enumerate(wl.tables)]
    ref4 = bag4.reference(params, idx)
    ref8 = bag8.reference(restored, idx)
    np.testing.assert_allclose(np.asarray(ref4), np.asarray(ref8), rtol=1e-6)
    assert bag8.plan.n_cores == 8
    assert bag8.pack(restored).chunk_data.shape[0] == 8  # packed for the new K
