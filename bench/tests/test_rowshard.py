"""The row-sharded DLRM kind and its metrics: the work counted by hand, the
gather's readers on a synthetic trace, and the Criteo cell at a tiny size
on four CPU devices, sound and with two planted faults."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np

from bench import harness, trace

MODEL = harness.load_module(harness.BENCH / "models" / "dlrm_rowshard.py")
CELL = "criteo-1tb.zipf.sat-4chip"


def test_tower_macs_of_criteo():
    cfg = json.loads((harness.BENCH / "configs" / "dlrm-criteo-1tb-4chip.json").read_text())
    # bottom 13-512-256-64-16, 25*24/2 pairs of 16, top 316-512-256-1
    assert MODEL.tower_macs(cfg) == 155_136 + 4_800 + 293_120 == 453_056
    assert sum(cfg["tables"]["rows"]) == 362_393_513


def test_gather_bytes_on_a_hand_sized_batch():
    cfg = {"embed_dim": 4, "table_dtype": "float32",
           "tables": {"rows": [10, 3, 100], "seq": [2, 1, 1]}}
    # one chip of 800 B over 2 chips: only the 100-row table (1,600 B) is
    # too large to replicate; its 5 distinct rows * 4 * 4 + indices 3 * 4 +
    # pooled 3 * 4 * 4
    assert MODEL.gather_bytes(cfg, [3, 2, 5], 3, 800, 2) == 80 + 12 + 48
    assert MODEL.gather_bytes(cfg, [3, 2, 5], 3, 1e9, 2) == 0.0


def test_the_reference_reads_only_the_rows_a_batch_looks_up():
    cfg = json.loads((harness.BENCH / "configs" / "dlrm-criteo-1tb-4chip.json").read_text())
    cfg.update(name="tiny", bottom_mlp=[8], top_mlp=[8],
               tables={"rows": [50, 9, 700], "seq": [2, 1, 1]})
    w = MODEL.make_weights(cfg, 2**40 + 3)
    assert [t.shape for t in w["tables"]] == [(50, 16), (9, 16), (700, 16)]
    assert all(isinstance(t, np.ndarray) for t in w["tables"])
    rng = np.random.default_rng(0)
    idx = np.stack([rng.integers(-1, 50, (6, 2)),
                    np.pad(rng.integers(0, 9, (6, 1)), ((0, 0), (0, 1)), constant_values=-1),
                    np.pad(rng.integers(0, 700, (6, 1)), ((0, 0), (0, 1)), constant_values=-1)])
    dense = rng.standard_normal((6, 13)).astype(np.float32)
    whole = MODEL._dlrm.reference(cfg, w, idx.astype(np.int32), dense)
    assert np.array_equal(MODEL.reference(cfg, w, idx.astype(np.int32), dense), whole)


def _ctx(ops, n_traced=2):
    red = trace.Reduced(ops=ops, spans=[], n_chips=2, lo=0.0, hi=1e9)
    cell = harness.load_cell(CELL)
    rows = cell.cfg["tables"]["rows"]
    distinct = [min(r, 1000) for r in rows]
    return harness.Context(cell=cell, window={"loop": "closed", "qps": 1.0}, setup_s=1.0,
                           peaks=harness.peaks_for("TPU v5 lite"), trace=red,
                           traced=[{"distinct": distinct}] * n_traced, batch=8192, chips=4)


def test_gather_readers_on_a_synthetic_trace():
    path = "jit(infer)/shard_map/jit(embedding_bag_gm_gather)/pallas_call"
    ops = [trace.Op(0, 0.0, 3e6, "gather", path, "lookup"),
           trace.Op(1, 0.0, 5e6, "gather", path, "lookup"),
           trace.Op(1, 5e6, 9e6, "fused", "jit(multi_embedding_bag_ragged)", "lookup")]
    ms = harness.load_module(harness.BENCH / "metrics" / "gather_ms.py")
    roof = harness.load_module(harness.BENCH / "metrics" / "gather_roofline.py")
    ctx = _ctx(ops)
    assert ms.read(ctx) == 5e6 / 2 * 1e-6  # busiest chip, per traced batch
    cfg = ctx.cell.cfg
    need = MODEL.gather_bytes(cfg, ctx.traced[0]["distinct"], 8192, 16e9, 4) / (4 * 819e9)
    assert need > 0
    assert abs(roof.read(ctx) - 100 * need / 2.5e-3) < 1e-9
    # a program without the row gather: both fall silent
    assert ms.read(_ctx(ops[2:])) is None and roof.read(_ctx(ops[2:])) is None


def test_criteo_cell_on_four_devices_is_sound_and_catches_faults():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    r = subprocess.run([sys.executable, str(Path(__file__).with_name("rowshard_run.py"))],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["row_sharded"] == [3] and out["gathered"] == [3, 3, 3, 3]
    assert max(out["per_chip"]) <= out["budget"]
    assert out["sound"]["value"] <= out["sound"]["limit"]
    assert out["shifted"]["value"] > out["shifted"]["limit"]  # a gathered row off by one
    assert out["no_sum"]["value"] > out["no_sum"]["limit"]  # the shards' sum left out
