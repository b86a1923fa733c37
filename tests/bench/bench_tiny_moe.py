"""Tiny twin of the MoE share's cell, for the CPU tests.

``tiny_moe_root(tmp)`` is ``bench_tiny.tiny_root`` with, as new files and
entries only, a tiny DeepSeek-V2 share (16 routed experts of which 4 are
held, MLA with YaRN over a 16-position original context) and its cell.
Limits are the real cell's limits; only sizes shrink.
"""
from __future__ import annotations

import json
from pathlib import Path

from bench_tiny import REPO, tiny_root

TINY_MOE = dict(hidden_size=64, intermediate_size=128,
                moe_intermediate_size=32, num_hidden_layers=3,
                num_attention_heads=4, num_key_value_heads=4,
                kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                v_head_dim=16, n_routed_experts=4,
                n_routed_experts_published=16, expert_offset=4,
                compute_dtype="float32",
                num_experts_per_tok=3, n_shared_experts=1, vocab_size=500,
                rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40,
                              "mscale": 0.707, "mscale_all_dim": 0.707,
                              "original_max_position_embeddings": 16,
                              "type": "yarn"})
TWINS = {"train-deepseek-v2-lite-ep8": "train-tiny-moe"}


def _derive(root: Path, kind: str, src: str, name: str, **changes):
    d = json.loads((REPO / "bench" / kind / f"{src}.json").read_text())
    d.update(name=name, **changes)
    (root / "bench" / kind / f"{name}.json").write_text(json.dumps(d))


def tiny_moe_root(tmp: Path) -> Path:
    root = tiny_root(tmp)
    _derive(root, "configs", "deepseek-v2-lite-ep8", "tiny-moe", **TINY_MOE)
    _derive(root, "workloads", "train-deepseek-v2-lite-ep8",
            "train-tiny-moe", config="tiny-moe", batch=2, seq=32)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"] += [
        dict(name="train-tiny-moe", config="tiny-moe", traffic="lm_stream",
             chips=1, why="tiny")]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] += [TWINS[w] for w in m["workloads"] if w in TWINS]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
