"""Spreads of a cell's runs, as the contract takes them:

    python benchmark/tests/spread.py <runs_set1.jsonl> <runs_set2.jsonl>

Each file holds one result line per run. A spread is the distance between
the first and the third quartile (`statistics.quantiles(values, n=4)`) as
a share of the median. Prints both sets' medians and spreads per metric,
the wider spread, five times it, and the second median against the first.
"""
import json
import statistics
import sys


def load(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def spread(values):
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(paths):
    sets = [load(p) for p in paths]
    for runs in sets:
        assert all(r["correct"] for r in runs), "a run was not correct"
    for name in sets[0][0]["metrics"]:
        cols = [[r["metrics"][name]["value"] for r in runs] for runs in sets]
        med = [statistics.median(c) for c in cols]
        spr = [spread(c) for c in cols]
        print(
            f"{name}: medians {[round(m, 6) for m in med]} spreads "
            f"{[round(100 * s, 3) for s in spr]} % widest {100 * max(spr):.3f} % "
            f"x5 = {500 * max(spr):.2f} %"
            + (f" second/first median {med[1] / med[0] - 1:+.4%}" if len(med) > 1 else "")
        )
        for c in cols:
            print("   ", [round(v, 5) for v in c])


if __name__ == "__main__":
    main(sys.argv[1:])
