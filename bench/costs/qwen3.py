"""Forward FLOPs per token of a dense GQA decoder, from its config.

Copied from the program's ``launch/roofline.fwd_flops_per_token`` (dense
family, 2*m*n*k per matmul) so that a later change to the program cannot
change the yardstick. ``ctx`` is the mean number of keys a query attends
to: S/2 under a causal mask, the work the step requires. The LM head
counts the published vocabulary, not the program's padding.
"""
from typing import Any, Dict


def attn_flops_per_token(cfg: Dict[str, Any], ctx: float) -> float:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    proj = 2 * d * hd * (2 * H + 2 * K)
    scores = 2 * H * hd * ctx * 2
    return proj + scores


def ffn_flops_per_token(cfg: Dict[str, Any]) -> float:
    return 2 * 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def fwd_flops_per_token(cfg: Dict[str, Any], ctx: float) -> float:
    layers = cfg["num_hidden_layers"] * (attn_flops_per_token(cfg, ctx)
                                         + ffn_flops_per_token(cfg))
    return layers + 2 * cfg["hidden_size"] * cfg["vocab_size"]
