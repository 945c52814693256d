"""The work a batch requires, counted by hand."""
import json

import numpy as np

from bench import gen, harness

MODEL = harness.load_module(harness.BENCH / "models" / "dlrm.py")


def test_lookup_bytes_on_a_hand_sized_batch():
    cfg = {"embed_dim": 4, "table_dtype": "float32",
           "tables": {"rows": [10, 3], "seq": [2, 1]}}
    idx = np.array([[[1, 1], [2, -1], [1, 7]],  # table 0: ids 1, 2, 7
                    [[0, -1], [0, -1], [2, -1]]], np.int32)  # table 1: ids 0, 2
    u = gen.distinct_per_table(idx)
    assert u == [3, 2]
    # rows 5 * 4 * 4 + indices 3 * (2 + 1) * 4 + pooled 3 * 2 * 4 * 4
    assert MODEL.lookup_bytes(cfg, u, 3) == 80 + 36 + 96
    assert MODEL.lookup_flops(cfg, 3) == 3 * 3 * 4


def test_tower_macs_of_taobao():
    cfg = json.loads((harness.BENCH / "configs" / "dlrm-taobao.json").read_text())
    # bottom 13-512-256-64-16, 16*15/2 pairs of 16, top 136-512-256-1
    assert MODEL.tower_macs(cfg) == 155_136 + 1_920 + 200_960
    assert MODEL.query_flops(cfg) == 2 * 358_016
