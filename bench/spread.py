"""Spread of a cell's end-to-end metrics over repeated runs, and the bound
it suggests.

    python3 bench/spread.py runs_a.jsonl runs_b.jsonl

Each file holds the result lines (one JSON object per line) of one set of
runs of one cell.  For each metric and each set: the median, and the
spread, the distance between the first and third quartiles
(``statistics.quantiles(values, n=4)``) as a share of the median.  The
suggested bound is five times the widest spread, and never under 1%.
"""
import json
import statistics
import sys


def spread(values) -> float:
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def main(paths) -> None:
    sets = []
    for p in paths:
        with open(p) as f:
            sets.append([json.loads(line) for line in f if line.strip().startswith("{")])
    names = sorted({m for s in sets for r in s for m in r["metrics"]})
    for m in names:
        rows = []
        for s in sets:
            v = [r["metrics"][m]["value"] for r in s if m in r["metrics"]]
            rows.append((statistics.median(v), spread(v) if len(v) >= 2 else float("nan"), len(v)))
        widest = max(r[1] for r in rows)
        print(f"{m}: " + "; ".join(f"median {md!r} spread {sp:.4%} (n={n})" for md, sp, n in rows)
              + f"; bound {max(5 * widest, 0.01):.4f}")


if __name__ == "__main__":
    main(sys.argv[1:])
