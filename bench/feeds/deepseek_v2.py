"""Weights of a DeepSeek-V2 decoder's share of one chip, made from a seed.

``weights`` lays the tree out as the repository's models take it (the
leading dense layers in a list, the MoE layers stacked on a leading axis,
RMSNorm scales stored as ``scale - 1``, the held experts' slabs stacked on
an expert axis, the router at its published width) and fills it from the
seed in one jitted call on the device: matrices N(0, initializer_range),
norm scales at 1. ``batch`` draws token ids uniformly over the vocabulary
the configuration holds. A trained DeepSeek-V2 router is held near an
even load by its expert- and device-level balance losses (DeepSeek-V2,
arXiv:2405.04434, Sec. 2.2.3). Uniform ids keep this untrained router
near an even load too: in the first MoE layer the busiest held expert
takes 1.08 to 1.37 times the held mean over six seeds (``moe_load_ratio``).
``lm.batch``'s Zipf stream (one id is over half of its tokens) instead
pins most copies to the six experts that id picks: 3.8 and 5.7 times the
mean on two seeds, whose held shares of the layer's 49152 copies were
9598 and 5308, so the round's time would follow the seed.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp

from bench.feeds.lm import padded_vocab

NORMS = ("ln_attn", "ln_mlp", "kv_norm", "final_norm")


def _attn(cfg: Dict[str, Any], lead=()) -> Dict[str, Any]:
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    nd, rd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    vd, lora = cfg["v_head_dim"], cfg["kv_lora_rank"]
    return {"wq": lead + (D, H, nd + rd), "wkv_a": lead + (D, lora + rd),
            "kv_norm": lead + (lora,), "wk_b": lead + (lora, H, nd),
            "wv_b": lead + (lora, H, vd), "wo": lead + (H, vd, D)}


def _mlp(D: int, F: int, lead=()) -> Dict[str, Any]:
    return {"w_gate": lead + (D, F), "w_up": lead + (D, F),
            "w_down": lead + (F, D)}


def shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The tree of leaf shapes."""
    D = cfg["hidden_size"]
    nd = cfg["first_k_dense_replace"]
    L = (cfg["num_hidden_layers"] - nd,)
    E, Fm = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    V = padded_vocab(cfg)
    dense = {"ln_attn": (D,), "ln_mlp": (D,), "attn": _attn(cfg),
             "mlp": _mlp(D, cfg["intermediate_size"])}
    return {
        "embed": (V, D),
        "head_layers": [dense] * nd,
        "layers": {
            "ln_attn": L + (D,), "ln_mlp": L + (D,), "attn": _attn(cfg, L),
            "moe": {"router": L + (D, cfg["n_routed_experts_published"]),
                    "w_gate": L + (E, D, Fm), "w_up": L + (E, D, Fm),
                    "w_down": L + (E, Fm, D),
                    "shared": _mlp(D, cfg["n_shared_experts"] * Fm, L)},
        },
        "final_norm": (D,),
        "lm_head": (D, V),
    }


@partial(jax.jit, static_argnums=(1, 2, 3))
def _weights(key, tree_def, flat_shapes, std):
    leaves = []
    for i, (name, shape) in enumerate(flat_shapes):
        if name in NORMS:
            leaves.append(jnp.zeros(shape, jnp.float32))
        else:
            leaves.append(std * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32))
    return jax.tree_util.tree_unflatten(tree_def, leaves)


def weights(key: jax.Array, cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The float32 weights of ``cfg`` from ``key``."""
    paths, tree_def = jax.tree_util.tree_flatten_with_path(
        shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    flat = tuple((p[-1].key, s) for p, s in paths)
    return _weights(key, tree_def, flat, float(cfg["initializer_range"]))


@partial(jax.jit, static_argnums=(2, 3, 4))
def batch(key, step, vocab: int, batch_size: int, seq: int):
    """Batch ``step`` of the stream: (batch, seq) token ids and the next
    id of each as its label, drawn from ``fold_in(key, step)``."""
    toks = jax.random.randint(jax.random.fold_in(key, step),
                              (batch_size, seq + 1), 0, vocab, jnp.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
