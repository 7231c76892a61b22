"""Quartile spreads of a set of runs, and the bound they give.

    python benchmark/tools/spread.py setA.jsonl [setB.jsonl]

Each file holds one result line per run (the last line of standard output
of `run.py`). For every metric: median, the spread (third minus first
quartile over the median, `statistics.quantiles(values, n=4)`), and, over
the files, the wider spread times five: the bound to write, never under 1 %.
"""

import json
import statistics
import sys


def load(path):
    runs = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line.startswith("{") and '"metrics"' in line:
                runs.append(json.loads(line))
    return runs


def spreads(runs):
    out = {}
    for name in runs[0]["metrics"]:
        values = [r["metrics"][name]["value"] for r in runs
                  if name in r["metrics"]]
        q1, _, q3 = statistics.quantiles(values, n=4)
        median = statistics.median(values)
        out[name] = {"median": median, "spread": (q3 - q1) / median,
                     "n": len(values), "values": values}
    return out


def main(paths):
    runs = [load(p) for p in paths]
    sets = [spreads(r) for r in runs]
    for name in sets[0]:
        widest = max(s[name]["spread"] for s in sets if name in s)
        print(json.dumps({
            "metric": name,
            "medians": [s[name]["median"] for s in sets if name in s],
            "spreads": [s[name]["spread"] for s in sets if name in s],
            "bound_at_five_times": max(5 * widest, 0.01),
            "correct": [all(r["correct"] for r in rs) for rs in runs]}))


if __name__ == "__main__":
    main(sys.argv[1:])
