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
    (EP_RULES: "expert" -> model, full d_ff per expert). The tokens are
    the same on every model shard, so each shard routes them over all
    experts (the fp32 router is replicated) and computes, dropless, the
    part of the output its own experts give (``models.moe.moe_held`` at
    offset shard x E/M: the share a one-chip cut of the layer runs); one
    psum over the model axis sums the shares.

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


def moe_sharded(p, cfg: ModelConfig, x_flat: jax.Array, ctx: ShardCtx,
                seq_len=None) -> Tuple[jax.Array, Any]:
    """Distributed MoE on flattened tokens (T, D); see module docstring.
    ``seq_len`` splits the tokens into sequences for a sequence-wise aux
    loss."""
    if ctx.moe_impl == "ep":
        return _moe_ep(p, cfg, x_flat, ctx, seq_len)
    if ctx.moe_impl == "tp":
        return _moe_tp(p, cfg, x_flat, ctx, seq_len)
    if ctx.moe_impl == "local":
        from repro.models.moe import moe_local
        return moe_local(p, cfg, x_flat, seq_len=seq_len)
    raise ValueError(f"unknown moe_impl {ctx.moe_impl!r}; "
                     f"known: 'tp', 'ep', 'local'")


# ---------------------------------------------------------------------------
# Tensor-parallel experts (d_ff sliced over the model axis)
# ---------------------------------------------------------------------------


def _moe_tp(p, cfg: ModelConfig, x: jax.Array, ctx: ShardCtx, seq_len):
    from repro.models.moe import MoEStats, moe_local

    m_ax = ctx.model_axis
    sizes = ctx.mesh.shape
    M = sizes.get(m_ax, 1) if m_ax else 1
    if M > 1 and cfg.moe_d_ff % M:
        raise ValueError(f"tp MoE needs moe_d_ff % model axis == 0 "
                         f"(moe_d_ff={cfg.moe_d_ff}, model={M})")
    bspec = _batch_spec(ctx)

    def fn(p_sh, x_loc):
        y, stats = moe_local(p_sh, cfg, x_loc, seq_len=seq_len)
        if m_ax and M > 1:
            y = jax.lax.psum(y, m_ax)
        return y, _pmean_stats(stats, ctx)

    in_specs = (_weight_specs(p, None, m_ax if M > 1 else None,
                              m_ax if M > 1 else None),
                P(bspec, None))
    out_specs = (P(bspec, None),
                 jax.tree.map(lambda _: P(), MoEStats(0, 0, 0, 0)))
    sm = jax.shard_map(fn, mesh=ctx.mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return sm(p, x)


# ---------------------------------------------------------------------------
# Expert parallelism (experts sharded, each shard's share summed by psum)
# ---------------------------------------------------------------------------


def _moe_ep(p, cfg: ModelConfig, x: jax.Array, ctx: ShardCtx, seq_len):
    from repro.models.moe import MoEStats, moe_held, route

    m_ax = ctx.model_axis
    sizes = ctx.mesh.shape
    M = sizes.get(m_ax, 1) if m_ax else 1
    E = cfg.num_experts
    if M > 1 and E % M:
        raise ValueError(f"ep MoE needs num_experts % model axis == 0 "
                         f"(experts={E}, model={M})")
    E_loc = E // M
    bspec = _batch_spec(ctx)

    def fn(p_sh, x_loc):
        # p_sh: full router, (E_loc, D, F) expert slabs
        top_w, top_i, aux = route(p_sh, cfg, x_loc, seq_len)
        offset = jax.lax.axis_index(m_ax) * E_loc if M > 1 else 0
        y, copies, load = moe_held(p_sh, cfg, x_loc, top_w, top_i, offset)
        if M > 1:
            y = jax.lax.psum(y, m_ax)
            copies = jax.lax.psum(copies, m_ax)
            load = jax.lax.pmax(load, m_ax)
        stats = MoEStats(aux, jnp.zeros((), jnp.float32), copies, load)
        return y, _pmean_stats(stats, ctx)

    in_specs = (_weight_specs(p, m_ax if M > 1 else None, None, None),
                P(bspec, None))
    out_specs = (P(bspec, None),
                 jax.tree.map(lambda _: P(), MoEStats(0, 0, 0, 0)))
    sm = jax.shard_map(fn, mesh=ctx.mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return sm(p, x)

