"""Config system: model architecture configs, input shapes, and the registry.

Every assigned architecture lives in ``repro/configs/<id>.py`` exposing
``CONFIG`` (the exact published configuration) built on :class:`ModelConfig`.
``reduced()`` derives the CPU smoke-test variant (<=2 layers, d_model<=512,
<=4 experts) from the same family so smoke tests exercise identical code
paths as the full dry-run configs.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, Optional, Tuple


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture description. Defaults suit a dense GQA decoder."""

    name: str
    family: str                      # dense | moe | ssm | hybrid | audio | vlm
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    source: str = ""                 # citation (paper / model card)

    # --- attention ---
    attn_type: str = "gqa"           # gqa | mla | none
    head_dim: Optional[int] = None   # default d_model // num_heads
    qk_norm: bool = False
    rope_theta: float = 1e4
    # YaRN RoPE scaling (DeepSeek-V2): 0 = plain RoPE
    yarn_factor: float = 0.0
    yarn_original_max_pos: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    mrope: bool = False              # Qwen2-VL M-RoPE
    mrope_sections: Tuple[int, ...] = (16, 24, 24)
    sliding_window: Optional[int] = None
    use_bias: bool = False
    causal: bool = True

    # --- MLA (deepseek-v2 / minicpm3) ---
    q_lora_rank: int = 0             # 0 = full-rank q projection
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0

    # --- MoE ---
    num_experts: int = 0
    num_shared_experts: int = 0
    top_k: int = 0
    moe_d_ff: int = 0                # per-expert hidden size
    first_dense_layers: int = 0      # leading dense layers (deepseek-v2)
    router_aux_coef: float = 0.01    # load-balance loss weight
    router_aux: str = "batch"        # batch (Switch) | seq (DeepSeek-V2)
    norm_topk_prob: bool = True      # renormalise the top-k weights
    routed_scaling_factor: float = 1.0   # times un-normalised weights
    # expert parallelism's share: this chip holds routed experts
    # [expert_offset, expert_offset + experts_held); 0 = all of them
    experts_held: int = 0
    expert_offset: int = 0

    # --- SSM (mamba2) ---
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    ssm_chunk: int = 128
    conv_width: int = 4

    # --- hybrid (zamba2): shared attention block every k mamba layers ---
    shared_attn_every: int = 0

    # --- xLSTM ---
    xlstm_pattern: Tuple[str, ...] = ()   # per-layer 'm' (mLSTM) / 's' (sLSTM)

    # --- encoder-only (hubert) ---
    is_encoder: bool = False

    # --- modality frontend stubs ---
    frontend: Optional[str] = None   # None | "audio" | "vision"
    num_vision_tokens: int = 1024    # VLM: leading positions fed by stub

    # --- misc ---
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"          # compute dtype
    param_dtype: str = "float32"
    vocab_round: int = 256           # pad vocab to a multiple (sharding)
    tie_embeddings: bool = False

    # -----------------------------------------------------------------
    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim is not None:
            return self.head_dim
        return self.d_model // self.num_heads

    @property
    def held_experts(self) -> int:
        """Routed experts whose weights this chip holds."""
        return self.experts_held or self.num_experts

    @property
    def padded_vocab(self) -> int:
        r = self.vocab_round
        return (self.vocab_size + r - 1) // r * r

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_num_heads(self) -> int:
        return self.ssm_d_inner // self.ssm_head_dim

    @property
    def has_decode(self) -> bool:
        """Encoder-only models have no autoregressive decode step."""
        return not self.is_encoder

    def supports_long_context(self) -> bool:
        """Sub-quadratic in sequence length (native or via sliding window)."""
        if self.family in ("ssm", "hybrid"):
            return True
        if self.attn_type == "none":
            return True
        return self.sliding_window is not None

    def with_sliding_window(self, window: int = 8192) -> "ModelConfig":
        """Explicit long-context variant (DESIGN.md §4): windowed attention."""
        return dataclasses.replace(self, sliding_window=window)


def reduced(cfg: ModelConfig, layers: int = 2, d_model: int = 256,
            seq_friendly: bool = True) -> ModelConfig:
    """Smoke-test variant of the same family: tiny but same code paths."""
    heads = max(min(cfg.num_heads, 4), 1)
    kv = max(min(cfg.num_kv_heads, heads), 1)
    hd = max(d_model // heads, 32)
    changes: Dict = dict(
        name=cfg.name + "-smoke",
        num_layers=layers,
        d_model=d_model,
        num_heads=heads,
        num_kv_heads=kv,
        head_dim=hd,
        d_ff=min(cfg.d_ff, 4 * d_model) if cfg.d_ff else 0,
        vocab_size=min(cfg.vocab_size, 512),
        vocab_round=64,
        num_vision_tokens=min(cfg.num_vision_tokens, 8),
        dtype="float32",
    )
    if cfg.num_experts:
        changes.update(
            num_experts=min(cfg.num_experts, 4),
            top_k=min(cfg.top_k, 2),
            moe_d_ff=min(cfg.moe_d_ff, d_model),
            num_shared_experts=min(cfg.num_shared_experts, 1),
            first_dense_layers=min(cfg.first_dense_layers, 1),
            experts_held=min(cfg.experts_held, 4),
            expert_offset=0,
        )
    if cfg.attn_type == "mla":
        changes.update(
            q_lora_rank=min(cfg.q_lora_rank, 128) if cfg.q_lora_rank else 0,
            kv_lora_rank=min(cfg.kv_lora_rank, 64),
            qk_nope_head_dim=32,
            qk_rope_head_dim=16,
            v_head_dim=hd,
        )
    if cfg.ssm_state:
        changes.update(ssm_state=min(cfg.ssm_state, 16), ssm_head_dim=32,
                       ssm_chunk=32)
    if cfg.shared_attn_every:
        changes.update(shared_attn_every=min(cfg.shared_attn_every, layers))
    if cfg.mrope:
        half = hd // 2
        tot = sum(cfg.mrope_sections)
        secs = [s * half // tot for s in cfg.mrope_sections]
        secs[0] += half - sum(secs)
        changes.update(mrope_sections=tuple(secs))
    if cfg.xlstm_pattern:
        changes.update(xlstm_pattern=cfg.xlstm_pattern[:layers] or
                       tuple("ms"[: layers]))
    if cfg.sliding_window:
        changes.update(sliding_window=min(cfg.sliding_window, 64))
    return dataclasses.replace(cfg, **changes)


# ---------------------------------------------------------------------------
# Input shapes (assigned)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str                        # "train" | "prefill" | "decode"


INPUT_SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Architecture registry
# ---------------------------------------------------------------------------

ARCH_IDS = [
    "deepseek-v2-236b",
    "zamba2-2.7b",
    "minicpm3-4b",
    "codeqwen1.5-7b",
    "hubert-xlarge",
    "command-r-plus-104b",
    "xlstm-125m",
    "qwen2-vl-72b",
    "qwen3-moe-30b-a3b",
    "qwen3-0.6b",
    "deepseek-v2-lite",
]

_MODULE_FOR = {a: "repro.configs." + a.replace("-", "_").replace(".", "p")
               for a in ARCH_IDS}


def get_config(arch: str) -> ModelConfig:
    if arch not in _MODULE_FOR:
        raise KeyError(f"unknown arch {arch!r}; known: {sorted(_MODULE_FOR)}")
    mod = importlib.import_module(_MODULE_FOR[arch])
    return mod.CONFIG


def all_configs() -> Dict[str, ModelConfig]:
    return {a: get_config(a) for a in ARCH_IDS}
