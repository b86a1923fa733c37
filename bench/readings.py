"""Read a cell's compared numbers over many seeds in one process.

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 \
        [--variant program|control|<fault>] [--out file.jsonl]

``program`` drives the timed path as a run does (a training cell through
its checked rounds, a protocol cell through one episode) and compares it
with the plain reference: the lower readings a limit is set from.
``control`` puts the nearest lower precision in the program's place,
as each driver's ``Cell.reading`` says. A fault name (one of the
driver's ``FAULTS``) plants that fault in the program. One JSON line per
seed; the benchmark's runs never run this.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path.pop(0)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def readings(workload: str, seeds, variant: str = "program", devices=None,
             bench=None):
    """Yield ``{"seed", "variant", "numbers"}`` for each seed."""
    from bench import harness

    bench = bench or harness.BENCH
    wl = harness.workload(workload, bench)
    cfg = harness.config(wl["config"], bench)
    drv = harness.driver(wl["driver"], bench)
    if variant not in ("program", "control") and variant not in drv.FAULTS:
        raise harness.BenchError(f"{wl['driver']} has no fault {variant!r}")
    if devices is None:
        harness.enable_compile_cache()
        devices = harness.tpu_devices(wl["chips"])
    planted = drv.FAULTS.get(variant, contextlib.nullcontext)
    with planted():
        cell = drv.Cell(wl, cfg, seeds[0], devices)
        for seed in seeds:
            numbers = cell.reading(seed, variant)
            yield {"seed": seed, "variant": variant,
                   "numbers": {n["name"]: n["value"] for n in numbers}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--variant", default="program")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    out = open(args.out, "a") if args.out else None
    try:
        for rec in readings(args.workload, seeds, args.variant):
            line = json.dumps(rec)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
