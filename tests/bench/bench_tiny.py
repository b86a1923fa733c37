"""A checkout of the benchmark with tiny cells added, for the CPU tests.

``tiny_root(tmp)`` copies ``BENCHMARK.json`` and ``bench/`` into ``tmp``
and adds, as new files and new entries only, a tiny decoder and a tiny
paper setting with one cell each. The cells keep every limit of the real
ones; only sizes shrink.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
for p in (REPO, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

TINY_LM = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               vocab_size=500)
TINY_PAPER = dict(n=12, f=1, n_byz=1, d=4096)


def _derive(root: Path, kind: str, src: str, name: str, **changes):
    d = json.loads((REPO / "bench" / kind / f"{src}.json").read_text())
    d.update(name=name, **changes)
    (root / "bench" / kind / f"{name}.json").write_text(json.dumps(d))


def tiny_root(tmp: Path) -> Path:
    root = Path(tmp) / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns(".trace", "__pycache__"))
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    _derive(root, "configs", "qwen3-0.6b", "tiny-lm", **TINY_LM)
    _derive(root, "workloads", "train-qwen3-0.6b", "train-tiny",
            config="tiny-lm", batch=4, seq=32)
    _derive(root, "configs", "paper-headline", "tiny-paper", **TINY_PAPER)
    _derive(root, "workloads", "protocol-paper-headline", "protocol-tiny",
            config="tiny-paper", episode_rounds=3, wire_rounds=6)
    spec["workloads"] += [
        dict(name="train-tiny", config="tiny-lm", traffic="lm_stream",
             chips=1, why="tiny"),
        dict(name="protocol-tiny", config="tiny-paper", traffic="episodes",
             chips=1, why="tiny")]
    twin = {"train-qwen3-0.6b": "train-tiny",
            "protocol-paper-headline": "protocol-tiny"}
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [twin[w] for w in m["workloads"] if w in twin]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def run_cell(root: Path, cell: str, seed: int = 3000000001,
             seconds: float = 0.3, trace: int = 0) -> dict:
    """One run of ``cell`` on the CPU, the chip check skipped."""
    import time

    import jax

    from bench import harness
    from bench import run as bench_run

    args = bench_run.parse(["--workload", cell, "--seed", str(seed),
                            "--seconds", str(seconds), "--trace", str(trace)])
    peaks = harness.load_json(REPO / "bench" / "peaks.json")["TPU v5 lite"]
    line = bench_run.run(args, devices=jax.devices(), peaks=peaks, root=root,
                         start=time.perf_counter())
    return json.loads(line)
