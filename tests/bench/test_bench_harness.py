"""The benchmark's harness on the CPU: files found by name, the cost
functions against hand counts, the chip and peak checks, the result line,
and BENCHMARK.json against the rules every later PR keeps."""
from __future__ import annotations

import hashlib
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from bench_tiny import REPO, run_cell, tiny_root

from bench import harness
from bench.costs import protocol as pcost
from bench.costs import qwen3 as qcost


def _digest(root: Path):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_cell_config_and_metric_are_files_and_entries_alone(tmp_path):
    root = tiny_root(tmp_path)
    before = _digest(root / "bench")
    # a new per-layer metric: one reader file and one entry
    (root / "bench" / "metrics" / "rounds_seen.py").write_text(
        "def read(ctx):\n    return float(ctx['counters']['rounds'])\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["per_layer"].append(
        {"name": "rounds_seen", "unit": "rounds", "better": "higher",
         "source": "program_counter", "layer": "device", "moves": "round_ms",
         "workloads": ["protocol-tiny"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    after = _digest(root / "bench")
    assert {k: v for k, v in after.items() if k in before} == before

    bench = root / "bench"
    assert harness.workload("protocol-tiny", bench)["config"] == "tiny-paper"
    assert harness.config("tiny-paper", bench)["n"] == 12
    assert harness.metric_reader("rounds_seen", bench)(
        {"counters": {"rounds": 3}}) == 3.0
    out = run_cell(root, "protocol-tiny", trace=1)
    assert out["metrics"]["rounds_seen"]["value"] == out["attempted"]
    assert set(out["metrics"]) == {"rounds_seen", "slot_echo_rate",
                                   "wire_bits_ratio", "protocol_mfu"}


def test_unknown_names_fail(tmp_path):
    bench = tiny_root(tmp_path) / "bench"
    for fn in (harness.workload, harness.config, harness.driver,
               harness.metric_reader):
        with pytest.raises(harness.BenchError):
            fn("no-such-thing", bench)
    with pytest.raises(harness.BenchError):
        harness.workload("../BENCHMARK", bench)


def test_qwen3_flops_match_a_hand_count():
    cfg = harness.config("qwen3-0.6b")
    # per layer: q, k, v, o projections 2*1024*128*(2*16 + 2*8); scores
    # and PV 2*16*128*256*2 at a causal mean of 256 keys; SwiGLU
    # 2*3*1024*3072. Then the tied LM head 2*1024*151936.
    layer = 2 * 1024 * 128 * 48 + 2 * 16 * 128 * 256 * 2 + 6 * 1024 * 3072
    assert layer == 2 ** 25
    assert qcost.fwd_flops_per_token(cfg, 256) == 28 * layer + 311164928
    assert qcost.fwd_flops_per_token(cfg, 256) == 1250689024


def test_protocol_and_cgc_byte_counts():
    n, d = 100, 2 ** 21
    assert pcost.cgc_bytes(n, d) == 4 * (n * d + d)
    assert pcost.cgc_flops(n, d) == 4 * n * d
    assert pcost.round_bytes(n, d) == 4 * n * d + 8 * d
    assert pcost.round_flops(n, d) == pcost.cgc_flops(n, d)


def test_unknown_device_kind_fails():
    assert harness.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(harness.BenchError):
        harness.peaks("TPU v99")
    with pytest.raises(harness.BenchError):
        harness.peaks("cpu")


def test_no_tpu_fails_and_prints_no_result():
    with pytest.raises(harness.BenchError):
        harness.tpu_devices(1)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "train-qwen3-0.6b",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_result_line_has_the_contract_keys(tmp_path):
    numbers = [{"name": "w_gap", "value": 1e-7, "limit": 1e-6}]
    line = json.loads(harness.result_line(
        True, 3, 0, {"round_ms": {"value": 1.5, "unit": "ms"}},
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
         "memory_peak_bytes": 1}, numbers))
    assert list(line) == list(harness.RESULT_KEYS) + ["checks"]
    assert line["checks"] == {"w_gap": {"value": 1e-7, "limit": 1e-6}}
    assert not harness.judge([{"name": "x", "value": float("nan"),
                               "limit": 1.0}])
    out = run_cell(tiny_root(tmp_path), "protocol-tiny")
    assert list(out) == list(harness.RESULT_KEYS) + ["checks"]
    assert set(out["metrics"]) == {"round_ms", "setup_s"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        out["device"])
    assert out["correct"] is True and out["failed"] == 0


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_json_keeps_the_rules():
    spec = json.loads((REPO / "BENCHMARK.json").read_text())
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    for p in spec["paths"]:
        assert (REPO / p).is_dir() and not p.startswith("/") and ".." not in p
    names = set()
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] not in names
        names.add(c["name"])
        assert any(c["file"].startswith(p + "/") for p in spec["paths"])
        assert json.loads((REPO / c["file"]).read_text())["reduced"] \
            == c["reduced"]
    cells = {}
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in names
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        wl = harness.workload(w["name"])
        assert (wl["config"], wl["chips"], wl["traffic"]) == (
            w["config"], w["chips"], w["traffic"])
        cells[w["name"]] = w
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= set(cells)
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert callable(harness.metric_reader(m["name"]))
    for cell in cells:
        reports = [m["name"] for m in harness.cell_metrics(spec, cell,
                                                           "end_to_end")]
        assert "setup_s" in reports and len(reports) >= 2
        assert harness.cell_metrics(spec, cell, "per_layer")
