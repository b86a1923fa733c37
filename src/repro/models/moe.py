"""Mixture-of-Experts layer: top-k token-choice routing, dropless over the
held experts.

The layer holds routed experts ``[offset, offset + held)`` of the
``num_experts`` its router scores (expert parallelism's share of the
layer; all of them by default). Every copy routed to a held expert is
computed and copies routed elsewhere add nothing (:func:`moe_held`): the
held copies are sorted by expert and run through ``jax.lax.ragged_dot``
a chunk of rows at a time, the chunks past the held count skipped, so the
work and the memory follow the held copies, with no capacity and no drop.

Sharding modes (DESIGN.md §3):
  * ``local``  — single-device; used by smoke tests and inside shard_map.
  * ``tp``     — expert weights tensor-parallel over the model axis (every
    device holds all experts with a 1/M slice of d_ff); dispatch stays local
    to the device's tokens, one psum over 'model' combines. Robust default.
  * ``ep``     — expert-parallel: experts sharded over the model axis,
    each shard computing its own experts' share with :func:`moe_held` and
    one psum over 'model' summing the shares. Implemented in
    ``repro.dist.moe_sharding`` (``moe_sharded`` dispatches on
    ``ShardCtx.moe_impl``) and enabled per-config for the §Perf hillclimb.

The router always runs in fp32.
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import nn
from repro.models.mlp import mlp_forward, mlp_params
from repro.obs.scopes import MOE_EXPERTS, MOE_ROUTE, MOE_SHARED

F32 = jnp.float32


class MoEStats(NamedTuple):
    aux_loss: jax.Array        # load-balance loss (scalar)
    dropped_frac: jax.Array    # fraction of token-copies dropped (0)
    # MoE layers only (None in dense ones): token copies the held experts
    # computed, and the largest held expert's count over the held mean
    held_copies: Optional[jax.Array] = None
    load_ratio: Optional[jax.Array] = None


def moe_params(cfg: ModelConfig, kg: nn.KeyGen, pdtype) -> Dict[str, Any]:
    D, E, F = cfg.d_model, cfg.held_experts, cfg.moe_d_ff
    p: Dict[str, Any] = {
        # the router scores every routed expert, held here or not
        "router": nn.param(kg(), (D, cfg.num_experts), ("embed", None),
                           jnp.float32, stddev=D ** -0.5),
        "w_gate": nn.param(kg(), (E, D, F), ("expert", "embed", "mlp"),
                           pdtype),
        "w_up": nn.param(kg(), (E, D, F), ("expert", "embed", "mlp"),
                         pdtype),
        "w_down": nn.param(kg(), (E, F, D), ("expert", "mlp", "embed"),
                           pdtype),
    }
    if cfg.num_shared_experts:
        p["shared"] = mlp_params(
            cfg, kg, pdtype, d_ff=cfg.num_shared_experts * cfg.moe_d_ff)
    return p


def router_topk(logits: jax.Array, top_k: int, normalise: bool = True,
                scaling: float = 1.0
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """fp32 softmax -> greedy top-k. Returns (weights, ids, probs).

    The weights are renormalised to sum to 1 (``normalise``) or kept as
    the softmax gave them, times ``scaling`` (DeepSeek-V2's
    ``norm_topk_prob`` false, ``routed_scaling_factor``)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_w, top_i = jax.lax.top_k(probs, top_k)
    if normalise:
        top_w = top_w / jnp.maximum(jnp.sum(top_w, -1, keepdims=True), 1e-9)
    elif scaling != 1.0:
        top_w = top_w * scaling
    return top_w, top_i, probs


def load_balance_loss(probs: jax.Array, top_i: jax.Array, E: int
                      ) -> jax.Array:
    """Switch-style aux loss: E * sum_e f_e * P_e."""
    T, k = top_i.shape
    counts = jnp.zeros((E,), jnp.float32).at[top_i.reshape(-1)].add(1.0)
    f = counts / (T * k)
    P = jnp.mean(probs, axis=0)
    return E * jnp.sum(f * P)


def seq_aux_loss(probs: jax.Array, top_i: jax.Array, E: int, seq_len: int
                 ) -> jax.Array:
    """DeepSeek-V2's sequence-wise aux loss: per sequence, E / (k S) times
    each expert's copy count dotted with the sequence's mean router
    probability, averaged over the sequences. probs (T, E), top_i (T, k)
    with T = B * seq_len, tokens in sequence order."""
    T, k = top_i.shape
    B = T // seq_len
    counts = jnp.sum(jax.nn.one_hot(top_i.reshape(B, seq_len * k), E,
                                    dtype=F32), axis=1)          # (B, E)
    ce = counts * (E / (seq_len * k))
    p_mean = jnp.mean(probs.reshape(B, seq_len, E), axis=1)
    return jnp.mean(jnp.sum(ce * p_mean, axis=-1))


def route(p, cfg: ModelConfig, x: jax.Array, seq_len: Optional[int] = None
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The router over all ``num_experts`` on flattened tokens x (T, D):
    (top-k weights, top-k ids, aux loss). ``seq_len`` splits T into
    sequences for the sequence-wise aux loss."""
    E, k = cfg.num_experts, cfg.top_k
    logits = x.astype(jnp.float32) @ p["router"]
    top_w, top_i, probs = router_topk(logits, k, cfg.norm_topk_prob,
                                      cfg.routed_scaling_factor)
    if cfg.router_aux == "seq":
        aux = seq_aux_loss(probs, top_i, E, seq_len or x.shape[0])
    else:
        aux = load_balance_loss(probs, top_i, E)
    return top_w, top_i, aux


# rows of held copies computed at a time. Every step of the scan, run or
# skipped, carries the (T, D) float32 sum, so fewer and larger chunks cost
# less: on a TPU v5e, 2 x 4096 tokens of DeepSeek-V2-Lite's share train in
# 588 ms a round with 4096 rows against 671 ms with 2048
HELD_CHUNK = 4096


def moe_held(p, cfg: ModelConfig, x: jax.Array, top_w: jax.Array,
             top_i: jax.Array, offset=None
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Dropless output of the held experts on flattened tokens x (T, D).

    ``p`` holds ``held`` expert slabs (E_held, D, F) for the global
    experts ``[offset, offset + held)`` (``offset`` may be traced, as
    under shard_map); copies routed to other experts add nothing.
    Returns (y (T, D), held copy count, largest held expert's count over
    the held mean).

    The held copies, sorted by expert, are computed ``HELD_CHUNK`` rows at
    a time by a scan whose steps past the held count do nothing, so the
    work and the live memory follow the held copies: no capacity, no drop,
    and no buffer sized to every copy. Each step is rematerialised in the
    backward pass.
    """
    T, D = x.shape
    k = top_i.shape[1]
    H = p["w_gate"].shape[0]
    C = min(HELD_CHUNK, T * min(k, H))
    n_chunks = -(-T * min(k, H) // C)       # every copy that can be held
    offset = cfg.expert_offset if offset is None else offset
    with jax.named_scope(MOE_ROUTE):
        local = top_i.reshape(-1) - offset
        key = jnp.where((local >= 0) & (local < H), local, H)
        order = jnp.argsort(key, stable=True)
        group_sizes = jnp.zeros((H + 1,), jnp.int32).at[key].add(1)[:H]
        ends = jnp.cumsum(group_sizes)
        starts = ends - group_sizes
        n_held = ends[-1]
        pad = n_chunks * C - T * k
        rows_all = order // k
        w_all = top_w.reshape(-1)[order]
        if pad > 0:
            rows_all = jnp.pad(rows_all, (0, pad))
            w_all = jnp.pad(w_all, (0, pad))
    wg = p["w_gate"].astype(x.dtype)
    wu = p["w_up"].astype(x.dtype)
    wd = p["w_down"].astype(x.dtype)

    def compute(a, acc):
        with jax.named_scope(MOE_ROUTE):
            rows = jax.lax.dynamic_slice_in_dim(rows_all, a, C)
            w = jax.lax.dynamic_slice_in_dim(w_all, a, C)
            # the rows of each held expert that fall in [a, a + C)
            gs = (jnp.clip(ends, a, a + C) - jnp.clip(starts, a, a + C))
            # rows past the last group: the TPU's ragged_dot leaves them
            # uninitialised in its output and in the cotangent it sends
            # back, so they are masked on the way in and on the way out
            live = (a + jnp.arange(C) < n_held)[:, None]
            xs = jnp.where(live, x[rows], 0)                   # (C, D)
        with jax.named_scope(MOE_EXPERTS):
            g = jnp.where(live, jax.lax.ragged_dot(
                xs, wg, gs, preferred_element_type=F32), 0.0)
            u = jnp.where(live, jax.lax.ragged_dot(
                xs, wu, gs, preferred_element_type=F32), 0.0)
            h = nn.swiglu(g, u).astype(x.dtype)
            out = jnp.where(live, jax.lax.ragged_dot(
                h, wd, gs, preferred_element_type=F32), 0.0)
        with jax.named_scope(MOE_ROUTE):
            return acc.at[rows].add(out * w[:, None])

    @jax.checkpoint
    def step(acc, c):
        a = c * C
        return jax.lax.cond(a < n_held, compute, lambda a, acc: acc,
                            a, acc), None

    y, _ = jax.lax.scan(step, jnp.zeros((T, D), F32), jnp.arange(n_chunks))
    load = jnp.max(group_sizes) * H / jnp.maximum(n_held, 1)
    return y.astype(x.dtype), n_held.astype(F32), load.astype(F32)


def moe_local(p, cfg: ModelConfig, x: jax.Array,
              seq_len: Optional[int] = None) -> Tuple[jax.Array, MoEStats]:
    """Single-device MoE on flattened tokens x: (T, D) -> (T, D), over
    the experts ``p`` holds. ``seq_len`` splits T into sequences for the
    sequence-wise aux loss."""
    with jax.named_scope(MOE_ROUTE):
        top_w, top_i, aux = route(p, cfg, x, seq_len)
    y, copies, load = moe_held(p, cfg, x, top_w, top_i)
    return y, MoEStats(aux, jnp.zeros((), F32), copies, load)


def moe_forward(p, cfg: ModelConfig, x: jax.Array, shard_ctx=None
                ) -> Tuple[jax.Array, MoEStats]:
    """MoE layer on (B, S, D). Routed experts + optional shared experts.

    ``shard_ctx`` (repro.dist.ShardCtx) selects the distributed impl; None
    runs the pure-local path (smoke tests / single device).
    """
    B, S, D = x.shape
    x_flat = x.reshape(B * S, D)
    if shard_ctx is None or shard_ctx.mesh is None:
        y_flat, stats = moe_local(p, cfg, x_flat, seq_len=S)
    else:
        from repro.dist import moe_sharded  # local import: avoid cycle
        y_flat, stats = moe_sharded(p, cfg, x_flat, shard_ctx, seq_len=S)
    y = y_flat.reshape(B, S, D)
    if cfg.num_shared_experts:
        with jax.named_scope(MOE_SHARED):
            y = y + mlp_forward(p["shared"], x)
    return y, stats
