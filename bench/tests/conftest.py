import os
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

# a cell cut to a size the CPU runs in interpret mode in about a second:
# four tables (one multi-hot), batches of 64, a narrow tower
TINY = {
    "tables": {"rows": [50, 300, 7, 1000], "seq": [1, 2, 1, 1]},
    "max_batch": 64,
    "bottom_mlp": [32, 16],
    "top_mlp": [32],
}


@pytest.fixture
def tiny_cell():
    """``tiny_cell(name)``: the named cell of BENCHMARK.json at the tiny size."""
    from bench import harness

    def make(name, **mix):
        cell = harness.load_cell(name)
        cell.cfg.update(TINY)
        cell.mix.update({"pool_batches": 2, "history_batches": 1, **mix})
        return cell

    return make
