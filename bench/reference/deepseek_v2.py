"""Plain reference of a DeepSeek-V2 decoder's training steps on one chip's
expert-parallel share, independent of the program: forward, next-token
cross-entropy, the sequence-wise aux loss, gradients and AdamW, all in
float32 with every matmul at 'highest'.

It follows DeepSeek-V2 (``modeling_deepseek.py``): pre-norm RMSNorm
blocks; MLA with no query LoRA (q = x Wq; the latent c = RMSNorm(x W_kv_a
[:lora]) gives each head's no-rope key and its value, the last rope
columns of x W_kv_a one rope key shared by the heads), YaRN rotary
frequencies and the softmax scale (nd + rd)^-1/2 mscale(factor,
mscale_all_dim)^2; a dense SwiGLU MLP in the leading layers; in the
others a softmax router over every routed expert, its greedy top-k
weights kept as they are (``norm_topk_prob`` false) times
``routed_scaling_factor``, the routed experts held here (a loop over them,
each applied to every token and weighted by its routing weight: no
sorting, no capacity), and the shared experts; the aux loss per
sequence and layer, E / (k S) times each expert's copy count dotted with
the sequence's mean router probability, averaged over the sequences and
summed over the layers, times ``aux_loss_alpha``; an untied head.

The share: the layer holds routed experts [expert_offset, expert_offset +
n_routed_experts) of ``n_routed_experts_published``; what the others
would add is left out, as in the program. Departures, each the program's
and mirrored so that the two compute the same function: RMSNorm weights
are stored as ``scale - 1``; rotate-half RoPE acts on the rope columns as
stored, where DeepSeek-V2 first de-interleaves them (a fixed permutation
of the rope columns of Wq and W_kv_a, which random weights cannot tell
apart); the vocabulary is padded to a multiple of 256 rows. Layers,
query blocks and routed experts are rematerialised one at a time and the
loss is taken one sequence at a time, so that a whole share's steps fit
on one chip.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.feeds import deepseek_v2 as feed

Q_BLOCK = 512


def yarn_inv_freq(dim: int, theta: float, factor: float, orig: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    i = np.arange(dim // 2, dtype=np.float64)
    extra = theta ** (-2.0 * i / dim)
    inter = extra / factor

    def corr(b):
        return dim * math.log(orig / (2 * math.pi * b)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def yarn_m(scale: float, m: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def rope_tables(cfg: Dict[str, Any], S: int):
    """cos, sin (S, rd/2) and the softmax scale."""
    rs, rd = cfg["rope_scaling"], cfg["qk_rope_head_dim"]
    inv = yarn_inv_freq(rd, float(cfg["rope_theta"]), rs["factor"],
                        rs["original_max_position_embeddings"],
                        rs["beta_fast"], rs["beta_slow"])
    m = yarn_m(rs["factor"], rs["mscale"]) \
        / yarn_m(rs["factor"], rs["mscale_all_dim"])
    ang = np.arange(S, dtype=np.float64)[:, None] * inv[None, :]
    scale = (cfg["qk_nope_head_dim"] + rd) ** -0.5 \
        * yarn_m(rs["factor"], rs["mscale_all_dim"]) ** 2
    return (jnp.asarray(np.cos(ang) * m, jnp.float32),
            jnp.asarray(np.sin(ang) * m, jnp.float32), scale)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def _rope(x, cos, sin):
    """x (B, S, H, rd): rotate-half on the columns as stored."""
    half = x.shape[-1] // 2
    c, s = cos[:, None, :], sin[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _swiglu(h, m):
    return (jax.nn.silu(h @ m["w_gate"]) * (h @ m["w_up"])) @ m["w_down"]


def _attention(cfg, h, a, tables):
    """h (B, S, D) through MLA."""
    S = h.shape[1]
    nd, lora = cfg["qk_nope_head_dim"], cfg["kv_lora_rank"]
    cos, sin, scale = tables
    q = jnp.einsum("bsd,dhk->bshk", h, a["wq"])
    q_nope, q_pe = q[..., :nd], _rope(q[..., nd:], cos, sin)
    kv = h @ a["wkv_a"]
    c = _rms(kv[..., :lora], a["kv_norm"], cfg["rms_norm_eps"])
    k_pe = _rope(kv[:, :, None, lora:], cos, sin)[:, :, 0]     # (B, S, rd)
    k_nope = jnp.einsum("bsl,lhn->bshn", c, a["wk_b"])
    v = jnp.einsum("bsl,lhv->bshv", c, a["wv_b"])
    qb = min(Q_BLOCK, S)

    @jax.checkpoint
    def block(i):
        rows = i * qb + jnp.arange(qb)
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i * qb, qb, 1)
        qp = jax.lax.dynamic_slice_in_dim(q_pe, i * qb, qb, 1)
        s = (jnp.einsum("bqhn,bthn->bhqt", qn, k_nope)
             + jnp.einsum("bqhr,btr->bhqt", qp, k_pe)) * scale
        s = jnp.where(rows[:, None] >= jnp.arange(S)[None, :], s, -jnp.inf)
        return jnp.einsum("bhqt,bthv->bqhv", jax.nn.softmax(s, -1), v)

    o = jax.lax.map(block, jnp.arange(S // qb))            # (n, B, qb, H, vd)
    o = jnp.moveaxis(o, 0, 1).reshape(h.shape[0], S, *v.shape[2:])
    return jnp.einsum("bshv,hvd->bsd", o, a["wo"])


def _moe(cfg, h, m):
    """h (B, S, D) through the MoE layer's share: (y, aux)."""
    S = h.shape[1]
    E, k = cfg["n_routed_experts_published"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(h @ m["router"], -1)                # (B, S, E)
    top_p, top_i = jax.lax.top_k(probs, k)
    if cfg["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    else:
        top_p = top_p * cfg["routed_scaling_factor"]
    counts = jnp.sum(top_i[..., None] == jnp.arange(E), axis=(1, 2))
    aux = jnp.mean(jnp.sum(counts * (E / (S * k)) * jnp.mean(probs, 1), -1))
    first = cfg["expert_offset"]

    @jax.checkpoint
    def expert(y, e):
        w = jnp.sum(jnp.where(top_i == first + e, top_p, 0.0), -1)
        we = {n: m[n][e] for n in ("w_gate", "w_up", "w_down")}
        return y + w[..., None] * _swiglu(h, we), None

    y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                        jnp.arange(cfg["n_routed_experts"]))
    return y + _swiglu(h, m["shared"]), aux


def _layer(cfg, x, p, tables, moe: bool):
    eps = cfg["rms_norm_eps"]
    x = x + _attention(cfg, _rms(x, p["ln_attn"], eps), p["attn"], tables)
    h = _rms(x, p["ln_mlp"], eps)
    if moe:
        y, aux = _moe(cfg, h, p["moe"])
    else:
        y, aux = _swiglu(h, p["mlp"]), jnp.float32(0)
    return x + y, aux


def loss(params, batch, cfg):
    """Mean next-token loss plus alpha times the aux loss summed over the
    MoE layers."""
    tables = rope_tables(cfg, batch["tokens"].shape[1])
    x = params["embed"][batch["tokens"]]
    for p in params["head_layers"]:
        x, _ = jax.checkpoint(
            lambda x, p: _layer(cfg, x, p, tables, False))(x, p)
    x, auxes = jax.lax.scan(
        jax.checkpoint(lambda x, p: _layer(cfg, x, p, tables, True)), x,
        params["layers"])
    x = _rms(x, params["final_norm"], cfg["rms_norm_eps"])

    @jax.checkpoint
    def seq_nll(xs_ls):
        xs, ls = xs_ls
        logits = xs @ params["lm_head"]
        z = jax.nn.logsumexp(logits, -1)
        return jnp.sum(z - jnp.take_along_axis(logits, ls[:, None], -1)[:, 0])

    nll = jax.lax.map(seq_nll, (x, batch["labels"]))
    return jnp.sum(nll) / batch["labels"].size \
        + cfg["aux_loss_alpha"] * jnp.sum(auxes)


def leaf_norms(tree) -> List[jax.Array]:
    return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for x in jax.tree.leaves(tree)]


def expert_norms(tree) -> jax.Array:
    """The norm of each (layer, held expert) slab of the routed experts'
    three matrices, flattened: (3 L E,)."""
    moe = tree["layers"]["moe"]
    return jnp.concatenate([
        jnp.sqrt(jnp.sum(jnp.square(moe[n].astype(jnp.float32)),
                         axis=(2, 3))).reshape(-1)
        for n in ("w_gate", "w_up", "w_down")])


def _thaw(cfg_items):
    cfg = dict(cfg_items)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    return cfg


@partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
def _adamw_step(state, batch, hyper):
    params, mu, nu, t = state
    lr, b1, b2, eps, cfg_items = hyper
    value, grads = jax.value_and_grad(loss)(params, batch, _thaw(cfg_items))
    t = t + 1.0
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        params, mu, nu)
    return (params, mu, nu, t), value, leaf_norms(grads), expert_norms(grads)


@partial(jax.jit, static_argnums=(2,))
def _delta_norms(params, key, cfg_items):
    start = feed.weights(key, _thaw(cfg_items))
    return leaf_norms(jax.tree.map(jnp.subtract, params, start))


def delta_norms(params, key, cfg: Dict[str, Any]) -> List[float]:
    """Each leaf's distance from the weights ``key`` made."""
    return [float(x) for x in _delta_norms(params, key, cfg_items(cfg))]


KEYS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
        "num_hidden_layers", "first_k_dense_replace",
        "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "n_routed_experts",
        "n_routed_experts_published", "expert_offset", "n_shared_experts",
        "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
        "aux_loss_alpha", "vocab_size", "rope_theta", "rms_norm_eps",
        "initializer_range")


def cfg_items(cfg: Dict[str, Any]):
    """The sizes the model's functions read, as a hashable static arg."""
    return tuple((k, cfg[k]) for k in KEYS) + (
        ("rope_scaling", tuple(sorted(cfg["rope_scaling"].items()))),)


def train_steps(cfg: Dict[str, Any], weight_key, data_key, opt: Dict,
                steps: int, batch_size: int, seq: int) -> Dict[str, Any]:
    """Losses of ``steps`` AdamW steps from the seeded weights, each
    leaf's first gradient norm and each held expert slab's, and each
    leaf's change after them."""
    hyper = (float(opt["lr"]), float(opt["b1"]), float(opt["b2"]),
             float(opt["eps"]), cfg_items(cfg))
    with jax.default_matmul_precision("highest"):
        params = feed.weights(weight_key, cfg)
        zeros = jax.tree.map(jnp.zeros_like, params)
        state = (params, zeros, jax.tree.map(jnp.zeros_like, params),
                 jnp.float32(0))
        losses, grad_norms, expert = [], None, None
        for s in range(steps):
            b = feed.batch(data_key, s, cfg["vocab_size"], batch_size, seq)
            state, value, gn, en = _adamw_step(state, b, hyper)
            losses.append(float(value))
            if grad_norms is None:
                grad_norms = [float(g) for g in gn]
                expert = [float(e) for e in en]
        params = state[0]
        del state
        delta = delta_norms(params, weight_key, cfg)
    return {"losses": losses, "grad_norms": grad_norms,
            "expert_grad_norms": expert, "delta_norms": delta}
