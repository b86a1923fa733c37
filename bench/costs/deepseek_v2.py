"""Forward FLOPs per token of a DeepSeek-V2 decoder's share, from its
config, and the FLOPs and least bytes its held experts' grouped products
execute.

2*m*n*k per matmul. ``ctx`` is the mean number of keys a query attends
to: S/2 under a causal mask, the work the step requires. Routed experts
count the copies the held experts computed (``copies_per_token``, from
the program's held-copy counter, summed over the MoE layers); the router
scores every routed expert; the head counts the vocabulary the
configuration holds.
"""
from typing import Any, Dict


def mla_flops_per_token(cfg: Dict[str, Any], ctx: float) -> float:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nd, rd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, lora = cfg["v_head_dim"], cfg["kv_lora_rank"]
    proj = 2 * (D * H * (nd + rd) + D * (lora + rd) + lora * H * (nd + vd)
                + H * vd * D)
    scores = 2 * H * (nd + rd) * ctx + 2 * H * vd * ctx
    return proj + scores


def expert_flops_per_copy(cfg: Dict[str, Any]) -> float:
    """One token copy through one routed expert (SwiGLU), forward."""
    return 2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def fwd_flops_per_token(cfg: Dict[str, Any], ctx: float,
                        copies_per_token: float) -> float:
    D = cfg["hidden_size"]
    L, nd = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    dense = 2 * 3 * D * cfg["intermediate_size"]
    moe = (2 * D * cfg["n_routed_experts_published"]
           + 2 * 3 * D * cfg["n_shared_experts"]
           * cfg["moe_intermediate_size"])
    return (L * mla_flops_per_token(cfg, ctx) + nd * dense + (L - nd) * moe
            + copies_per_token * expert_flops_per_copy(cfg)
            + 2 * D * cfg["vocab_size"])


def experts_flops_executed(cfg: Dict[str, Any], copies: float) -> float:
    """FLOPs the held experts' grouped products execute in one training
    step for ``copies`` held copies (summed over the layers): the forward
    runs three times (the step, the layer's rematerialisation, the
    chunk's) and the backward twice the forward's products."""
    return (3 + 2) * copies * expert_flops_per_copy(cfg)


def experts_bytes_executed(cfg: Dict[str, Any], copies: float) -> float:
    """Least HBM bytes of the same products: each of the five executions
    reads every held expert's three bf16 matrices once a layer, and each
    copy's bf16 input row and float32 output row of each product."""
    D, F = cfg["hidden_size"], cfg["moe_intermediate_size"]
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    weights = layers * 3 * cfg["n_routed_experts"] * D * F * 2
    rows = copies * (2 * (2 * D + 4 * F) + (2 * F + 4 * D))
    return (3 + 2) * (weights + rows)
