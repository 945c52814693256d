"""Readings that set the limit of ``correct``, on the chip, at a cell's own
size: for each seed, the widest logit gap of the served answers (the
program) and of the control (the reference in bfloat16, put in the
program's place) over the same window.

    python3 bench/control.py --workload taobao.zipf.sat --seconds 5 --seeds 1 2 3

The benchmark's own runs do not run this.  One process reads every seed;
the last line of standard output is a JSON list of the readings.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    args = p.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    from repro import compat

    compat.enable_compilation_cache()
    cell = harness.load_cell(args.workload)
    try:
        devices = harness.check_devices(cell.chips)
    except harness.Refused as e:
        print(f"[control] refused: {e}", file=sys.stderr)
        return 2
    readings = []
    for seed in args.seeds:
        t0 = time.time()
        s = harness.set_up(cell, seed, devices)
        win = harness.measure(s, args.seconds, harness.Tracer(False))
        s.srv = None
        program = harness.check_answers(cell, s.weights, s.pool, win, s.batch)
        control = harness.check_answers(cell, s.weights, s.pool, win, s.batch, low=True)
        readings.append({"seed": seed, "program": program, "control": control,
                         "answers": int(win["served"]), "seconds": time.time() - t0})
        print(f"[control] {readings[-1]}", flush=True)
    print(json.dumps(readings))
    return 0


if __name__ == "__main__":
    sys.exit(main())
