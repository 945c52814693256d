"""On four CPU devices: the Criteo cell at a tiny size, with chips small
enough that the planner row-shards the largest table and the row gather
serves its shards; sound, with a gathered row shifted by one, and with the
shards' sum left out.  Prints one JSON line.  Run by ``test_rowshard.py``
in a process of its own, since the device count is fixed when JAX starts."""
import dataclasses
import json
import os
import sys
from pathlib import Path

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import harness  # noqa: E402
from bench.tests.conftest import TINY  # noqa: E402
from repro.core import cost_model, partition  # noqa: E402

# chips of 4 MB HBM and 512 B of SMEM: the 20,000-row table cannot be
# replicated, and its 5,000-row shards are beyond the fused schedule
cost_model.TPU_V5E = dataclasses.replace(
    cost_model.TPU_V5E, hbm_bytes=4_000_000, smem_bytes=512
)
SEED = 2**31 + 77


def gap():
    cell = harness.load_cell("criteo-1tb.zipf.sat-4chip")
    cell.cfg.update(TINY)
    cell.cfg["tables"] = {"rows": [50, 300, 7, 20000], "seq": [1, 2, 1, 1]}
    cell.mix.update({"pool_batches": 2, "history_batches": 1})
    s = harness.set_up(cell, SEED, jax.devices(), log=lambda *a, **k: None)
    plan = s.srv.step_fn.bag.plan
    win = harness.measure(s, 0.2, harness.Tracer(False), log=lambda *a, **k: None)
    gathered = [r["table"] for chip in plan.meta["gather"]["per_chip"] for r in chip]
    return harness.verdict(s, win)["logit_gap"], plan.meta["capacity"], gathered


def main():
    sound, cap, gathered = gap()
    kernel = partition.embedding_bag_gm_gather
    partition.embedding_bag_gm_gather = lambda lines, ridx, **kw: kernel(
        lines, jnp.where(ridx >= 0, ridx + 1, -1), **kw)
    shifted, _, _ = gap()
    partition.embedding_bag_gm_gather = kernel
    partition._sparse_rejoin = lambda local, packed, axis: local
    no_sum, _, _ = gap()
    print(json.dumps({"sound": sound, "shifted": shifted, "no_sum": no_sum,
                      "row_sharded": cap["row_sharded"], "gathered": gathered,
                      "per_chip": cap["per_chip"], "budget": cap["budget"]}))


if __name__ == "__main__":
    main()
