"""Median and spread of each metric over a set of result lines.

    python3 bench/spread.py runs.jsonl [more.jsonl ...]

Each file holds result lines of one cell (the last stdout line of
``bench/run.py``), one per run. The spread is (Q3 - Q1) / median with
Python's quartiles, the measure the bounds in BENCHMARK.json are set
from; a bound is about five times the widest spread over the cells.
"""
from __future__ import annotations

import json
import statistics
import sys


def spread(values):
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def summary(lines):
    runs = [json.loads(x) for x in lines if x.strip().startswith("{")]
    out = {"runs": len(runs),
           "correct": sum(bool(r["correct"]) for r in runs)}
    names = sorted({k for r in runs for k in r["metrics"]})
    for name in names:
        vals = [r["metrics"][name]["value"] for r in runs
                if name in r["metrics"]]
        out[name] = {"median": statistics.median(vals),
                     "spread": spread(vals) if len(vals) > 1 else None,
                     "values": vals}
    return out


def main(argv=None) -> int:
    for path in (argv if argv is not None else sys.argv[1:]):
        with open(path) as fh:
            print(path, json.dumps(summary(fh.readlines())))
    return 0


if __name__ == "__main__":
    sys.exit(main())
