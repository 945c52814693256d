"""``correct`` holds on the sound path and fails where the timed path is
broken underneath, or where the control stands in its place.  The run is
driven as on the chip, past the look for one, at a tiny size."""
import time

import jax
import numpy as np
import pytest

from bench import harness


def _quiet(*a, **k):
    pass


def _run(cell, seed=2**31 + 17):
    return harness.run(cell, seed, 0.3, False, jax.devices(), t_start=time.time(), log=_quiet)


def _altered(step):
    def run(payloads):
        out = np.array(step(payloads))
        out[len(out) // 2] += 1.0  # one answer altered where it is produced
        return out
    return run


def _half(step):
    def run(payloads):
        n = len(payloads)
        half = list(payloads[: n // 2])
        return step(half + half[: n - len(half)])  # half the batch left out
    return run


def _stale(step):
    last = []

    def run(payloads):
        out = step(payloads)
        prev = last[0] if last else out
        last[:] = [out]
        return prev  # the step hands back what it gave before
    return run


def test_a_sound_open_loop_run_is_correct(tiny_cell):
    r = _run(tiny_cell("taobao.zipf.rate", rate_qps=2000.0, max_wait_s=0.2))
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"p50_ms", "p99_ms", "setup_s"}
    assert list(r)[-1] == "checks"


@pytest.fixture(scope="module")
def served():
    from bench.tests.conftest import TINY

    cell = harness.load_cell("taobao.zipf.sat")
    cell.cfg.update(TINY)
    cell.mix.update({"pool_batches": 2, "history_batches": 1})
    return harness.set_up(cell, 2**31 + 23, jax.devices(), log=_quiet)


@pytest.mark.parametrize("fault", [None, _altered, _half, _stale])
def test_planted_fault_is_not_correct(served, fault):
    sound = served.srv.step_fn
    if fault is not None:
        served.srv.step_fn = fault(sound)
    try:
        win = harness.measure(served, 0.2, harness.Tracer(False), log=_quiet)
    finally:
        served.srv.step_fn = sound
    c = harness.verdict(served, win)["logit_gap"]
    assert (c["value"] > c["limit"]) == (fault is not None), c


def test_control_fails_the_limit(served):
    """The reference in bfloat16, put in the program's place."""
    win = harness.measure(served, 0.2, harness.Tracer(False), log=_quiet)
    gap = harness.check_answers(served.cell, served.weights, served.pool, win, served.batch,
                                low=True)
    assert gap > 3 * served.cell.model.LOGIT_GAP_LIMIT
