"""``bench/stages.py`` run as on the chip, past the look for one, at a tiny
size: every program span of the served path reads a wall per traced
batch."""
import time

import jax

from bench import stages


def test_the_host_path_splits_by_the_program_spans(tiny_cell):
    out = stages.split(tiny_cell("kuairec.zipf.sat"), 2**31 + 29, 1.0, jax.devices(),
                       t_start=time.time(), log=lambda *a, **k: None)
    assert out["traced_batches"] > 0
    spans = out["span_ms"]
    assert set(spans) == set(stages.STAGES) and all(v > 0 for v in spans.values())
    assert spans["stage"] + spans["dispatch"] + spans["wait"] + spans["fetch"] <= spans["step"]
    assert out["pump_ms"]["traced_median"] > 0 and out["pump_ms"]["untraced_batches"] > 0
    assert out["metrics"]["host_ms"] > 0
    assert spans["step"] < out["harness_ms"]["pump"] and out["harness_ms"]["submit"] > 0
    assert out["server"]["batch_fill"] == 1.0
    assert 0 < out["server"]["queue_wait_p99_us"] <= out["server"]["p99_us"]
    assert {n for n, _ in out["idle_gaps"]} <= {f"repro.{k}" for k in stages.STAGES} | {
        "bench.submit", "bench.pump", "bench.collect"}
