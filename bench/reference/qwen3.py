"""Plain reference of a Qwen3 decoder's training steps, independent of the
program: forward, next-token cross-entropy, gradients and AdamW, all in
float32 with every matmul at 'highest'.

It follows Qwen3 (pre-norm RMSNorm blocks; GQA with RMSNorm on each
head's queries and keys before rotate-half RoPE; SwiGLU MLP; tied
embeddings). Departures, each the program's and mirrored so that the two
compute the same function: RMSNorm weights are stored as ``scale - 1``;
the vocabulary is padded to a multiple of 256 rows, whose logits take
part in the softmax. Layers are rematerialised one at a time and the
loss is taken one sequence at a time, so that a whole model's steps fit
beside nothing else on one chip.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from bench.feeds import lm


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def _rope(x, theta):
    S, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _layer(cfg, x, p):
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    B, S, _ = x.shape
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    a = p["attn"]
    h = _rms(x, p["ln_attn"], eps)
    q = _rms(jnp.einsum("bsd,dhk->bshk", h, a["wq"]), a["q_norm"], eps)
    k = _rms(jnp.einsum("bsd,dhk->bshk", h, a["wk"]), a["k_norm"], eps)
    v = jnp.einsum("bsd,dhk->bshk", h, a["wv"])
    q, k = _rope(q, theta), _rope(k, theta)
    q = q.reshape(B, S, K, H // K, hd)
    s = jnp.einsum("bqkgh,btkh->bkgqt", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("bkgqt,btkh->bqkgh", jax.nn.softmax(s, -1), v)
    x = x + jnp.einsum("bshk,hkd->bsd", o.reshape(B, S, H, hd), a["wo"])
    m = p["mlp"]
    h = _rms(x, p["ln_mlp"], eps)
    y = jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])
    return x + y @ m["w_down"]


def loss(params, batch, cfg):
    x = params["embed"][batch["tokens"]]
    x, _ = jax.lax.scan(
        jax.checkpoint(lambda x, p: (_layer(cfg, x, p), None)), x,
        params["layers"])
    x = _rms(x, params["final_norm"], cfg["rms_norm_eps"])

    @jax.checkpoint
    def seq_nll(xs_ls):
        xs, ls = xs_ls
        logits = xs @ params["embed"].T
        z = jax.nn.logsumexp(logits, -1)
        return jnp.sum(z - jnp.take_along_axis(logits, ls[:, None], -1)[:, 0])

    nll = jax.lax.map(seq_nll, (x, batch["labels"]))
    return jnp.sum(nll) / batch["labels"].size


def leaf_norms(tree) -> List[jax.Array]:
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


@partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
def _adamw_step(state, batch, hyper):
    params, mu, nu, t = state
    lr, b1, b2, eps, cfg_items = hyper
    cfg = dict(cfg_items)
    value, grads = jax.value_and_grad(loss)(params, batch, cfg)
    t = t + 1.0
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        params, mu, nu)
    return (params, mu, nu, t), value, leaf_norms(grads)


@partial(jax.jit, static_argnums=(2,))
def _delta_norms(params, key, cfg_items):
    start = lm.weights(key, dict(cfg_items))
    return leaf_norms(jax.tree.map(jnp.subtract, params, start))


def delta_norms(params, key, cfg: Dict[str, Any]) -> List[float]:
    """Each leaf's distance from the weights ``key`` made."""
    return [float(x) for x in _delta_norms(params, key, cfg_items(cfg))]


def cfg_items(cfg: Dict[str, Any]):
    """The sizes the model's functions read, as a hashable static arg."""
    keys = ("hidden_size", "intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size", "rope_theta", "rms_norm_eps", "initializer_range")
    return tuple((k, cfg[k]) for k in keys)


def train_steps(cfg: Dict[str, Any], weight_key, data_key, opt: Dict,
                steps: int, batch_size: int, seq: int) -> Dict[str, Any]:
    """Losses of ``steps`` AdamW steps from the seeded weights, each
    leaf's first gradient norm, and each leaf's change after them."""
    hyper = (float(opt["lr"]), float(opt["b1"]), float(opt["b2"]),
             float(opt["eps"]), cfg_items(cfg))
    with jax.default_matmul_precision("highest"):
        params = lm.weights(weight_key, cfg)
        zeros = jax.tree.map(jnp.zeros_like, params)
        state = (params, zeros, jax.tree.map(jnp.zeros_like, params),
                 jnp.float32(0))
        losses, grad_norms = [], None
        for s in range(steps):
            b = lm.batch(data_key, s, cfg["vocab_size"], batch_size, seq)
            state, value, gn = _adamw_step(state, b, hyper)
            losses.append(float(value))
            if grad_norms is None:
                grad_norms = [float(g) for g in gn]
        params = state[0]
        del state
        delta = delta_norms(params, weight_key, cfg)
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta}
