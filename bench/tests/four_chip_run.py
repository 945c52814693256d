"""On four CPU devices: the four-chip cell at the tiny size, sound and with
the exchange between chips left out.  Prints one JSON line.  Run by
``test_four_chip.py`` in a process of its own, since the device count is
fixed when JAX starts."""
import json
import os
import sys
from pathlib import Path

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

import jax  # noqa: E402

from bench import harness  # noqa: E402
from bench.tests.conftest import TINY  # noqa: E402


def gap(seed):
    cell = harness.load_cell("taobao.zipf.sat-4chip")
    cell.cfg.update(TINY)
    cell.mix.update({"pool_batches": 2, "history_batches": 1})
    s = harness.set_up(cell, seed, jax.devices(), log=lambda *a, **k: None)
    plan = s.srv.step_fn.bag.plan
    win = harness.measure(s, 0.2, harness.Tracer(False), log=lambda *a, **k: None)
    return harness.verdict(s, win)["logit_gap"], sorted({a.core for a in plan.assignments})


def main():
    from repro.core import partition

    sound, cores = gap(2**31 + 41)
    # the exchange left out: each chip keeps its own partial sums
    partition._sparse_rejoin = lambda local, packed, axis: local
    broken, _ = gap(2**31 + 41)
    print(json.dumps({"sound": sound, "broken": broken, "cores": cores}))


if __name__ == "__main__":
    main()
