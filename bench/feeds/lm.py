"""Weights and token batches of a dense GQA decoder, made from a seed.

``weights`` lays the tree out as the repository's models take it (layers
stacked on a leading axis, RMSNorm scales stored as ``scale - 1``) and
fills it from the seed in one jitted call on the device: matrices
N(0, initializer_range), norm scales at 1. ``batch`` is the seeded
synthetic stream the repository trains on (a Zipf-like marginal with a
learnable bigram rule), copied here so that the plain reference and the
program read the same rows: batch s is drawn from ``fold_in(key, s)``.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 32),
                              seed // 2 ** 32)


def padded_vocab(cfg: Dict[str, Any], multiple: int = 256) -> int:
    return -(-cfg["vocab_size"] // multiple) * multiple


def shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """The tree of leaf shapes; ``None`` marks a norm scale."""
    D, F = cfg["hidden_size"], cfg["intermediate_size"]
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, L = cfg["head_dim"], cfg["num_hidden_layers"]
    return {
        "embed": (padded_vocab(cfg), D),
        "layers": {
            "ln_attn": (L, D), "ln_mlp": (L, D),
            "attn": {"wq": (L, D, H, hd), "wk": (L, D, K, hd),
                     "wv": (L, D, K, hd), "wo": (L, H, hd, D),
                     "q_norm": (L, hd), "k_norm": (L, hd)},
            "mlp": {"w_gate": (L, D, F), "w_up": (L, D, F),
                    "w_down": (L, F, D)},
        },
        "final_norm": (D,),
    }


NORMS = ("ln_attn", "ln_mlp", "q_norm", "k_norm", "final_norm")


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
    tree = shapes(cfg)
    paths, tree_def = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    flat = tuple((p[-1].key, s) for p, s in paths)
    return _weights(key, tree_def, flat, float(cfg["initializer_range"]))


def _token_stream(key, vocab: int, batch: int, seq: int,
                  zipf_a: float = 1.2, copy_prob: float = 0.3):
    """(batch, seq + 1) int32 tokens."""
    k1, k2 = jax.random.split(key)
    u = jax.random.uniform(k1, (batch, seq + 1), minval=1e-6)
    base = jnp.clip((u ** (-1.0 / zipf_a) - 1.0).astype(jnp.int32), 0,
                    vocab - 1)
    coin = jax.random.uniform(k2, (batch, seq)) < copy_prob

    def step(tok, inp):
        c, b = inp
        nxt = jnp.where(c, (7 * tok + 13) % vocab, b)
        return nxt, nxt

    _, rest = jax.lax.scan(step, base[:, 0], (jnp.moveaxis(coin, 1, 0),
                                              jnp.moveaxis(base[:, 1:], 1, 0)))
    return jnp.concatenate([base[:, :1], jnp.moveaxis(rest, 0, 1)], axis=1)


@partial(jax.jit, static_argnums=(2, 3, 4))
def batch(key, step, vocab: int, batch_size: int, seq: int):
    toks = _token_stream(jax.random.fold_in(key, step), vocab, batch_size,
                         seq)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
