"""Spreads and suggested bounds from the result lines of repeated runs.

    python3 benchmark/tools/spread.py setA/*.log -- setB/*.log

Each file is one run's standard output (the last line is the result). Sets
are separated by "--". Prints, per metric, each set's median and spread
(interquartile range over median, statistics.quantiles(n=4): the contract's
definition), the wider of the sets' spreads and five times it.
"""

from __future__ import annotations

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from harness import stats  # noqa: E402


def result_line(path):
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.startswith("{")]
    return json.loads(lines[-1]) if lines else None


def main(argv):
    sets, cur = [], []
    for a in argv:
        if a == "--":
            sets.append(cur)
            cur = []
        else:
            cur.append(a)
    sets.append(cur)
    per_set = []
    for files in sets:
        values = {}
        for path in files:
            line = result_line(path)
            if line is None:
                print(f"{path}: no result line")
                continue
            if not line["correct"] or line["failed"]:
                print(f"{path}: correct={line['correct']} "
                      f"failed={line['failed']}")
            for k, v in line["metrics"].items():
                values.setdefault(k, []).append(v["value"])
        per_set.append(values)
    for name in sorted({k for v in per_set for k in v}):
        spreads = []
        for i, values in enumerate(per_set):
            xs = values.get(name, [])
            if len(xs) < 2:
                continue
            spreads.append(stats.iqr_share(xs))
            print(f"{name} set {i}: n={len(xs)} median="
                  f"{statistics.median(xs):.6g} spread={spreads[-1]:.4%} "
                  f"min={min(xs):.6g} max={max(xs):.6g}")
        if spreads:
            print(f"{name}: widest spread {max(spreads):.4%} -> five times "
                  f"is {5 * max(spreads):.3%}")


if __name__ == "__main__":
    main(sys.argv[1:])
