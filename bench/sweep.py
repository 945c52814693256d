"""Find the knee of an open-loop cell: the highest offered rate whose queue
ends the window no deeper than one batch.

    python3 bench/sweep.py --workload taobao.zipf.rate --qps 37000 \\
        --fractions 0.7 0.8 0.9 1.0 --seconds 20

Each rate is a fraction of ``--qps`` (a saturated cell's measured queries
per second) and runs for ``--seconds`` on one set-up, with the mix's
``max_wait_s``, so every batch leaves full.  Prints one line per rate and, last, a
JSON list of ``{rate, backlog, p50_ms, p99_ms}``.
"""
import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--qps", type=float, required=True)
    p.add_argument("--fractions", type=float, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    from repro import compat

    compat.enable_compilation_cache()
    cell = harness.load_cell(args.workload)
    try:
        devices = harness.check_devices(cell.chips)
    except harness.Refused as e:
        print(f"[sweep] refused: {e}", file=sys.stderr)
        return 2
    s = harness.set_up(cell, args.seed, devices)
    out = []
    for f in args.fractions:
        rate = f * args.qps
        win = harness.open_window(s.srv, s.requests, args.seconds, rate,
                                  harness.Tracer(False), args.seed, s.batch)
        lat = win["latency_s"] * 1e3
        out.append({"fraction": f, "rate": rate, "backlog": win["backlog"],
                    "batch": s.batch, "served": win["served"], "attempted": win["attempted"],
                    "p50_ms": float(np.percentile(lat, 50)), "p99_ms": float(np.percentile(lat, 99))})
        print(f"[sweep] {out[-1]}", flush=True)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
