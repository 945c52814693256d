"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload taobao.zipf.sat --seed 7 --seconds 30 --trace 0

The cell, its configuration and its traffic are named in ``BENCHMARK.json``
at the checkout's root.  The last line of standard output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device`` and, last, ``checks``: each number compared with the plain
reference beside its limit.  With no TPU, or fewer chips than the cell
asks for, it exits 2 and prints no result.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# JAX's persistent compile cache lives inside the checkout, at a fixed path
# (the path is part of each entry's key), so two checkouts share nothing.
CACHE = ROOT / ".jax_cache"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    CACHE.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    from repro import compat

    print(f"[bench] compile cache {compat.enable_compilation_cache()}", file=sys.stderr)
    cell = harness.load_cell(args.workload)
    try:
        devices = harness.check_devices(cell.chips)
    except harness.Refused as e:
        print(f"[bench] refused: {e}", file=sys.stderr)
        return 2
    result = harness.run(cell, args.seed, args.seconds, bool(args.trace), devices,
                         t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
