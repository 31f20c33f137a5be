"""Median and spread of every metric over the runs that tests/sets.sh left.

    python3 chipbench/tests/spreads.py <cell> <label>...

A spread is (Q3 - Q1) / median by ``statistics.quantiles(values, n=4)``,
the form the bounds in BENCHMARK.json are set from (about five times the
widest). With two labels of the same seeds, the second median against the
first is printed too.
"""

import glob
import json
import statistics
import sys


def last_lines(cell: str, label: str) -> list[dict]:
    rows = []
    for path in sorted(glob.glob(f"chiprun_out/sets/{cell}_{label}_*.out")):
        with open(path) as f:
            lines = f.read().strip().splitlines()
        if lines and lines[-1].startswith("{"):
            rows.append(json.loads(lines[-1]))
    return rows


def main(argv: list[str]) -> int:
    cell, labels = argv[0], argv[1:]
    medians: dict[str, list[float]] = {}
    for label in labels:
        rows = last_lines(cell, label)
        print(f"{cell} {label}: {len(rows)} runs, correct "
              f"{sum(r['correct'] for r in rows)}, failed "
              f"{sum(r['failed'] for r in rows)}")
        for name in rows[0]["metrics"] if rows else []:
            values = [r["metrics"][name]["value"] for r in rows]
            q1, _, q3 = statistics.quantiles(values, n=4)
            median = statistics.median(values)
            medians.setdefault(name, []).append(median)
            print(f"  {name}: median {median:.4f} spread "
                  f"{100 * (q3 - q1) / median:.2f}% min {min(values):.4f} "
                  f"max {max(values):.4f}")
    for name, (first, *rest) in medians.items():
        for other in rest:
            print(f"  {name}: later median {100 * (other / first - 1):+.2f}% "
                  "against the first")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
