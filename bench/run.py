"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (from process start: the chip, the weights and inputs made from
the seed on the device, compilation or the compile cache, warm-up) is
timed apart; then the cell's driver runs whole steps for ``--seconds``.
With ``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window. After the window the program's state is freed and what the timed
path produced is compared with a plain reference; each compared number
is printed beside its limit, last on stderr and under ``checks`` in the
result. The last line on stdout is the JSON result. Without a TPU, or
with fewer chips than the cell asks for, it exits nonzero and prints no
result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# run as a script, this directory comes first on the path, and
# bench/trace.py would shadow the standard library's trace module
if sys.path and os.path.abspath(sys.path[0]) == HERE:
    sys.path.pop(0)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run(args, devices=None, peaks=None, root=None, start=None):
    """One run; returns the result line. ``devices`` and ``peaks`` stand
    in for the chip check in tests, ``root`` for the checkout."""
    from pathlib import Path

    from bench import harness

    root = Path(root) if root is not None else harness.ROOT
    bench = root / "bench"
    start = T0 if start is None else start
    spec = harness.spec(root)
    entry = harness.cell_entry(spec, args.workload)
    wl = harness.workload(args.workload, bench)
    cfg = harness.config(wl["config"], bench)
    if (entry["config"], entry["chips"]) != (wl["config"], wl["chips"]):
        raise harness.BenchError(f"{args.workload}: BENCHMARK.json and the "
                                 f"workload file disagree")
    e2e = harness.cell_metrics(spec, args.workload, "end_to_end")
    layer = harness.cell_metrics(spec, args.workload, "per_layer")
    readers = {m["name"]: harness.metric_reader(m["name"], bench)
               for m in layer} if args.trace else {}

    if devices is None:
        harness.enable_compile_cache()
        devices = harness.tpu_devices(entry["chips"])
    if peaks is None:
        peaks = harness.peaks(devices[0].device_kind, bench)
    compiles = harness.CompileCounter()
    cell = harness.driver(wl["driver"], bench).Cell(wl, cfg, args.seed,
                                                    devices)
    cell.setup()
    setup_s = time.perf_counter() - start
    before = compiles.count

    with harness.profiler_window(bool(args.trace),
                                 bench / ".trace") as trace_dir:
        with harness.annotate("bench.window"):
            window = harness.run_window(cell.step, args.seconds)
    in_window = compiles.count - before
    print(f"set-up {setup_s:.3f} s, {before} compilations; window "
          f"{window['seconds']:.3f} s, {window['rounds']} rounds, "
          f"{in_window} compilations", file=sys.stderr, flush=True)

    device = dict(harness.device_info(devices),
                  memory_peak_bytes=harness.memory_peak(devices))
    metrics, breakdown = {}, None
    if args.trace:
        from bench.trace import Trace

        tr = Trace.load(str(trace_dir))
        device.update(busy_s=tr.busy_s, window_s=tr.window_s)
        ctx = {"trace": tr, "window": window, "counters": cell.counters(),
               "costs": cell.costs(), "peaks": peaks,
               "chips": len(devices)}
        for m in layer:
            value = readers[m["name"]](ctx)
            if value is None:
                # left out of the line, as a kernel taken off the path
                # leaves its roofline silent; said here so that it shows
                print(f"metric {m['name']}: nothing to read in this run",
                      file=sys.stderr, flush=True)
            else:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = {"device_ops": tr.top_ops(), "idle_gaps": tr.idle_gaps()}
        del tr, ctx
    else:
        taken = {"setup_s": setup_s, "round_ms": window["round_ms"]}
        for m in e2e:
            if m["name"] not in taken:
                raise harness.BenchError(f"no measurement of {m['name']!r}")
            metrics[m["name"]] = {"value": taken[m["name"]],
                                  "unit": m["unit"]}

    failed = cell.failed()
    numbers = cell.check()
    del cell
    gc.collect()
    correct = harness.judge(numbers)
    harness.print_checks(numbers)
    return harness.result_line(correct, window["rounds"], failed, metrics,
                               device, numbers, breakdown)


def main(argv=None) -> int:
    args = parse(argv)
    from bench.harness import BenchError

    try:
        line = run(args)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
