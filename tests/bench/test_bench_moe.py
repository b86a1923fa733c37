"""The MoE share's cell on the CPU at a tiny size: a sound run is
correct, the control and every planted fault of its driver make it
false, and the per-layer metrics that need no device trace read. The
runs go through the harness with its look for a chip skipped
(``bench_tiny.run_cell``); limits are the real cell's limits."""
from __future__ import annotations

import pytest

from bench_tiny import run_cell
from bench_tiny_moe import tiny_moe_root

from bench import harness, readings
from bench.costs import deepseek_v2 as dcost

CELL = "train-tiny-moe"
FAULTS = sorted(harness.driver("trainer_moe").FAULTS)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_moe_root(tmp_path_factory.mktemp("bench"))


def test_sound_run_is_correct_and_counts_held_copies(root):
    out = run_cell(root, CELL, trace=1)
    assert out["correct"] is True, out["checks"]
    # train_mfu counts the routed experts by the held-copy counter
    assert out["metrics"]["train_mfu"]["value"] > 0
    assert out["metrics"]["moe_load_ratio"]["value"] >= 1.0


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(root, fault):
    with harness.driver("trainer_moe", root / "bench").FAULTS[fault]():
        out = run_cell(root, CELL)
    assert out["correct"] is False, (fault, out["checks"])


def test_control_fails_the_moe_cell(root):
    import jax

    wl = harness.workload(CELL, root / "bench")
    for rec in readings.readings(CELL, [5], "control",
                                 devices=jax.devices(),
                                 bench=root / "bench"):
        assert any(v > wl["limits"][k] for k, v in rec["numbers"].items()), \
            rec


def test_parent_program_fails_cleanly(root, monkeypatch):
    """A program without the held-expert fields stops at once with a
    BenchError, not a hang or a run of another model."""
    from repro.configs import base

    drv = harness.driver("trainer_moe", root / "bench")
    fields = {f.name for f in base.dataclasses.fields(base.ModelConfig)}
    assert {"experts_held", "yarn_factor", "norm_topk_prob"} <= fields
    cfg = harness.config("tiny-moe", root / "bench")
    with pytest.raises(harness.BenchError):
        drv.model_config(dict(cfg, model_type="qwen3"))


def test_deepseek_flops_match_a_hand_count():
    cfg = harness.config("deepseek-v2-lite-ep8")
    # per layer, MLA projections: wq 2*2048*16*192, wkv_a 2*2048*576,
    # wk_b and wv_b 2*512*16*256, wo 2*16*128*2048; scores and PV
    # 2*16*(192 + 128) per key
    proj = 2 * (2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256
                + 16 * 128 * 2048)
    assert dcost.mla_flops_per_token(cfg, 1) == proj + 2 * 16 * 320
    assert round(6 * proj / 1e6) == 165
    # the dense layer, five MoE layers (router over 64, two shared
    # experts of 1408) and six held copies a token, the 12800-row head
    want = (6 * (proj + 2 * 16 * 320 * 2048) + 6 * 2048 * 10944
            + 5 * (2 * 2048 * 64 + 6 * 2048 * 2816) + 6 * 6 * 2048 * 1408
            + 2 * 2048 * 12800)
    assert dcost.fwd_flops_per_token(cfg, 2048, 6) == want
    assert dcost.experts_flops_executed(cfg, 10) == 5 * 10 * 6 * 2048 * 1408
    # five executions: 5 layers x 3 bf16 matrices of 8 held experts, and
    # per copy the bf16 rows in and float32 rows out of the three products
    row = 2 * (2 * 2048 + 4 * 1408) + (2 * 1408 + 4 * 2048)
    assert dcost.experts_bytes_executed(cfg, 10) == 5 * (
        5 * 3 * 8 * 2048 * 1408 * 2 + 10 * row)
    # at the cell's mean load the products are bound by compute
    copies = 5 * 8192 * 6 / 8
    assert dcost.experts_flops_executed(cfg, copies) / 197e12 > \
        dcost.experts_bytes_executed(cfg, copies) / 819e9
