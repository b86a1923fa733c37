"""Distributed MoE: tensor-parallel and expert-parallel shard_map wrappers.

``moe_sharded(p, cfg, x_flat, ctx)`` is what ``models/moe.moe_forward``
dispatches to when a ShardCtx with a mesh is supplied. Two layouts:

  * ``tp`` — every device holds all experts with a 1/M slice of the
    expert hidden dim (DEFAULT_RULES: "mlp" -> model axis). Routing and
    dispatch run locally on each data shard's tokens (the fp32 router is
    replicated, so all model shards agree); expert matmuls produce
    partial outputs that one psum over the model axis completes. Robust
    default: no divisibility constraint on the expert count.
  * ``ep`` — experts themselves are sharded over the model axis
    (EP_RULES: "expert" -> model, full d_ff per expert). Each shard
    dispatches its local tokens into per-expert capacity buffers, an
    all_to_all ships each buffer to the owning shard, experts run on the
    union of all shards' tokens, and a second all_to_all returns the
    outputs to the tokens' home shards.

Both run as one fully-manual shard_map over the mesh. Inside the
trainer's worker shard_map, which is manual over the data axes only,
the EP region nests over the model axis.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models import nn
from .context import ShardCtx


def _batch_spec(ctx: ShardCtx):
    if not ctx.batch_axes:
        return None
    return ctx.batch_axes if len(ctx.batch_axes) > 1 else ctx.batch_axes[0]


def _pmean_stats(stats, ctx: ShardCtx):
    if not ctx.batch_axes:
        return stats
    return jax.tree.map(lambda s: jax.lax.pmean(s, ctx.batch_axes), stats)


def _weight_specs(p: Dict[str, Any], expert_entry, mlp_gate_entry,
                  mlp_down_entry) -> Dict[str, Any]:
    """in_specs tree for the MoE param dict (router fp32 stays replicated)."""
    specs = jax.tree.map(lambda _: P(), p)
    specs["w_gate"] = P(expert_entry, None, mlp_gate_entry)
    specs["w_up"] = P(expert_entry, None, mlp_gate_entry)
    specs["w_down"] = P(expert_entry, mlp_down_entry, None)
    return specs


def moe_sharded(p, cfg: ModelConfig, x_flat: jax.Array, ctx: ShardCtx
                ) -> Tuple[jax.Array, Any]:
    """Distributed MoE on flattened tokens (T, D); see module docstring."""
    if ctx.moe_impl == "ep":
        return _moe_ep(p, cfg, x_flat, ctx)
    if ctx.moe_impl == "tp":
        return _moe_tp(p, cfg, x_flat, ctx)
    if ctx.moe_impl == "local":
        from repro.models.moe import moe_local
        return moe_local(p, cfg, x_flat)
    raise ValueError(f"unknown moe_impl {ctx.moe_impl!r}; "
                     f"known: 'tp', 'ep', 'local'")


# ---------------------------------------------------------------------------
# Tensor-parallel experts (d_ff sliced over the model axis)
# ---------------------------------------------------------------------------


def _moe_tp(p, cfg: ModelConfig, x: jax.Array, ctx: ShardCtx):
    from repro.models.moe import moe_local

    m_ax = ctx.model_axis
    sizes = ctx.mesh.shape
    M = sizes.get(m_ax, 1) if m_ax else 1
    if M > 1 and cfg.moe_d_ff % M:
        raise ValueError(f"tp MoE needs moe_d_ff % model axis == 0 "
                         f"(moe_d_ff={cfg.moe_d_ff}, model={M})")
    bspec = _batch_spec(ctx)

    def fn(p_sh, x_loc):
        y, stats = moe_local(p_sh, cfg, x_loc)
        if m_ax and M > 1:
            y = jax.lax.psum(y, m_ax)
        return y, _pmean_stats(stats, ctx)

    in_specs = (_weight_specs(p, None, m_ax if M > 1 else None,
                              m_ax if M > 1 else None),
                P(bspec, None))
    out_specs = (P(bspec, None), jax.tree.map(lambda _: P(), _abs_stats()))
    sm = jax.shard_map(fn, mesh=ctx.mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return sm(p, x)


# ---------------------------------------------------------------------------
# Expert parallelism (experts sharded, all_to_all token exchange)
# ---------------------------------------------------------------------------


def _moe_ep(p, cfg: ModelConfig, x: jax.Array, ctx: ShardCtx):
    from repro.models.moe import (MoEStats, dispatch_indices,
                                  load_balance_loss, router_topk)

    m_ax = ctx.model_axis
    sizes = ctx.mesh.shape
    M = sizes.get(m_ax, 1) if m_ax else 1
    E, k = cfg.num_experts, cfg.top_k
    if M > 1 and E % M:
        raise ValueError(f"ep MoE needs num_experts % model axis == 0 "
                         f"(experts={E}, model={M})")
    E_loc = E // M
    bspec = _batch_spec(ctx)

    def fn(p_sh, x_loc):
        # p_sh: full router, (E_loc, D, F) expert slabs
        T, D = x_loc.shape
        C = int(max(8, round(T * k / E * cfg.capacity_factor)))

        logits = x_loc.astype(jnp.float32) @ p_sh["router"]
        top_w, top_i, probs = router_topk(logits, k)
        aux = load_balance_loss(probs, top_i, E)

        st, se, pos, keep, order = dispatch_indices(top_i, C, E)
        flat_w = top_w.reshape(-1)[order]
        idx = jnp.where(keep, se * C + pos, E * C)
        buf = jnp.zeros((E * C + 1, D), x_loc.dtype).at[idx].set(x_loc[st])
        buf = buf[:-1].reshape(E, C, D)

        if M > 1:
            # ship each expert's buffer to its owner shard; receive the
            # buffers every shard built for *my* experts.
            recv = jax.lax.all_to_all(buf, m_ax, split_axis=0,
                                      concat_axis=0, tiled=True)
        else:
            recv = buf
        # rows of recv: (source shard, local expert) -> regroup per expert
        xe = recv.reshape(M, E_loc, C, D).transpose(1, 0, 2, 3)
        xe = xe.reshape(E_loc, M * C, D)

        wg = p_sh["w_gate"].astype(x_loc.dtype)
        wu = p_sh["w_up"].astype(x_loc.dtype)
        wd = p_sh["w_down"].astype(x_loc.dtype)
        g = jnp.einsum("ecd,edf->ecf", xe, wg)
        u = jnp.einsum("ecd,edf->ecf", xe, wu)
        h = nn.swiglu(g, u)
        out = jnp.einsum("ecf,efd->ecd", h, wd)           # (E_loc, M*C, D)

        back = out.reshape(E_loc, M, C, D).transpose(1, 0, 2, 3)
        back = back.reshape(E, C, D)
        if M > 1:
            out_buf = jax.lax.all_to_all(back, m_ax, split_axis=0,
                                         concat_axis=0, tiled=True)
        else:
            out_buf = back
        # rows back in global-expert order (owner-major == expert id)
        out_flat = out_buf.reshape(E * C, D)
        y_copies = jnp.where(
            keep[:, None], out_flat[jnp.where(keep, se * C + pos, 0)], 0.0)
        y_copies = y_copies * flat_w[:, None].astype(x_loc.dtype)
        y = jnp.zeros((T, D), x_loc.dtype).at[st].add(y_copies)

        dropped = 1.0 - jnp.sum(keep.astype(jnp.float32)) / (T * k)
        stats = MoEStats(aux_loss=aux, dropped_frac=dropped)
        return y, _pmean_stats(stats, ctx)

    in_specs = (_weight_specs(p, m_ax if M > 1 else None, None, None),
                P(bspec, None))
    out_specs = (P(bspec, None), jax.tree.map(lambda _: P(), _abs_stats()))
    sm = jax.shard_map(fn, mesh=ctx.mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return sm(p, x)


def _abs_stats():
    from repro.models.moe import MoEStats
    return MoEStats(aux_loss=0, dropped_frac=0)
