"""What every cell shares: finding its files by name, the chip, the clock,
the profiler window and the result line.

A cell is ``bench/workloads/<cell>.json``. It names its configuration
(``bench/configs/<config>.json``) and its driver
(``bench/drivers/<driver>.py``); each per-layer metric is read by
``bench/metrics/<metric>.py``. Nothing here names a cell, a
configuration or a metric, so a new one is new files and new entries in
``BENCHMARK.json`` alone.
"""
from __future__ import annotations

import contextlib
import importlib
import importlib.util
import json
import os
import re
import shutil
import sys
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
# fixed paths inside the checkout: the cache path is part of the cache key
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = BENCH / ".trace"
RESULT_KEYS = ("correct", "attempted", "failed", "metrics", "device")


class BenchError(RuntimeError):
    """A run that cannot produce a result: it exits nonzero, prints none."""


def _checked(name: str, what: str) -> str:
    if not NAME.match(name):
        raise BenchError(f"bad {what} name {name!r}")
    return name


def load_json(path: Path) -> Dict[str, Any]:
    with open(path) as fh:
        return json.load(fh)


def spec(root: Path = ROOT) -> Dict[str, Any]:
    return load_json(root / "BENCHMARK.json")


def workload(name: str, bench: Path = BENCH) -> Dict[str, Any]:
    path = bench / "workloads" / f"{_checked(name, 'workload')}.json"
    if not path.exists():
        raise BenchError(f"no workload file {path}")
    return load_json(path)


def config(name: str, bench: Path = BENCH) -> Dict[str, Any]:
    path = bench / "configs" / f"{_checked(name, 'config')}.json"
    if not path.exists():
        raise BenchError(f"no config file {path}")
    return load_json(path)


def _load_file(path: Path, modname: str):
    if not path.exists():
        raise BenchError(f"no file {path}")
    sp = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def driver(name: str, bench: Path = BENCH):
    return _load_file(bench / "drivers" / f"{_checked(name, 'driver')}.py",
                      f"bench_driver_{name}")


def metric_reader(name: str, bench: Path = BENCH) -> Callable:
    """``read(ctx) -> float | None`` of one per-layer metric."""
    mod = _load_file(bench / "metrics" / f"{_checked(name, 'metric')}.py",
                     "bench_metric_" + re.sub(r"\W", "_", name))
    return mod.read


def cell_metrics(bench_spec: Dict[str, Any], cell: str, kind: str
                 ) -> List[Dict[str, Any]]:
    """The metrics of ``kind`` (end_to_end | per_layer) this cell reports:
    those with no ``workloads`` key and those that list the cell."""
    return [m for m in bench_spec[kind]
            if cell in m.get("workloads", [cell])]


def cell_entry(bench_spec: Dict[str, Any], cell: str) -> Dict[str, Any]:
    for w in bench_spec["workloads"]:
        if w["name"] == cell:
            return w
    raise BenchError(f"{cell!r} is not a workload of BENCHMARK.json")


# ---------------------------------------------------------------------------
# The chip
# ---------------------------------------------------------------------------


def peaks(kind: str, bench: Path = BENCH) -> Dict[str, Any]:
    """Published peaks of one chip of this ``device_kind``; a kind that
    is not in the table is an error, never a default."""
    table = load_json(bench / "peaks.json")
    if kind not in table:
        raise BenchError(f"no peaks for device_kind {kind!r}; known: "
                         f"{sorted(table)}")
    return table[kind]


def tpu_devices(count: int):
    """The first ``count`` TPU devices, or BenchError: a run never falls
    back to the CPU."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"needs a TPU, jax found {devices[0].platform}")
    if len(devices) < count:
        raise BenchError(f"needs {count} chips, jax found {len(devices)}")
    return devices[:count]


def enable_compile_cache() -> str:
    """Persistent compilation cache at the one path the driver may give
    (``JAX_COMPILATION_CACHE_DIR``) or at a fixed path in the checkout;
    every program is cached, however quickly it compiled."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts backend compilations (a cache hit is not one)."""

    def __init__(self):
        from jax._src import monitoring

        self.count = 0

        def listen(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.count += 1

        monitoring.register_event_duration_secs_listener(listen)


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def device_info(devices) -> Dict[str, Any]:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


@contextlib.contextmanager
def annotate(name: str):
    """A host span on the profiler's clock (free when not tracing)."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------


def run_window(step: Callable[[], int], seconds: float) -> Dict[str, Any]:
    """Call ``step`` (which returns the rounds it completed, its work on
    the device finished) until ``seconds`` have passed; the window is
    whole steps, all of their time and all of their rounds."""
    rounds = steps = 0
    t0 = time.perf_counter()
    while True:
        rounds += step()
        steps += 1
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    return {"rounds": rounds, "steps": steps, "seconds": elapsed,
            "round_ms": 1e3 * elapsed / rounds}


@contextlib.contextmanager
def profiler_window(enabled: bool, path: Path = TRACE_DIR):
    import jax

    if not enabled:
        yield None
        return
    shutil.rmtree(path, ignore_errors=True)
    path.mkdir(parents=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # host spans only, no Python calls
    jax.profiler.start_trace(str(path), profiler_options=opts)
    try:
        yield path
    finally:
        jax.profiler.stop_trace()


# ---------------------------------------------------------------------------
# The result
# ---------------------------------------------------------------------------


def judge(numbers: List[Dict[str, Any]]) -> bool:
    """Correct when every compared number is finite and within its limit."""
    return bool(numbers) and all(
        n["value"] == n["value"] and n["value"] <= n["limit"]
        for n in numbers)


def result_line(correct: bool, attempted: int, failed: int,
                metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
                numbers: List[Dict[str, Any]],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    out: Dict[str, Any] = {"correct": bool(correct),
                           "attempted": int(attempted),
                           "failed": int(failed), "metrics": metrics,
                           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {n["name"]: {"value": n["value"], "limit": n["limit"]}
                     for n in numbers}
    return json.dumps(out)


def print_checks(numbers: List[Dict[str, Any]]) -> None:
    for n in numbers:
        print(f"check {n['name']}: {n['value']!r} limit {n['limit']!r}",
              file=sys.stderr, flush=True)
