"""Spread of each metric over a set of runs, as the bounds are set from it.

    python3 bench/tools/spread.py set1.out set2.out

Each file holds the standard output of one set of runs of one cell (the
result line is the last JSON line of each run; diagnostic lines are
skipped).  For each metric and set it prints the median, the quartiles of
``statistics.quantiles(values, n=4)`` and their distance over the median,
and which runs were correct.
"""
from __future__ import annotations

import json
import statistics
import sys


def results(path: str) -> list:
    out = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line.startswith("{") and '"correct"' in line and '"metrics"' in line:
                out.append(json.loads(line))
    return out


def main() -> None:
    for path in sys.argv[1:]:
        runs = results(path)
        print(f"{path}: {len(runs)} runs, correct {[r['correct'] for r in runs]}")
        names = sorted({m for r in runs for m in r["metrics"]})
        for name in names:
            vals = [r["metrics"][name]["value"] for r in runs if name in r["metrics"]]
            if len(vals) < 2:
                continue
            q1, med, q3 = statistics.quantiles(vals, n=4)
            print(f"  {name}: median {statistics.median(vals)!r} "
                  f"q1 {q1!r} q3 {q3!r} spread {(q3 - q1) / statistics.median(vals)!r}"
                  f" values {vals!r}")


if __name__ == "__main__":
    main()
