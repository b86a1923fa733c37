"""FSDP over the data axes with blockwise-CGC gradient reduction.

Parameters (and, mirrored by the trainer, optimizer state) are sharded
over the data-parallel worker axes along one planned dimension per leaf.
Inside the worker shard_map each leaf is all-gathered just in time for
the forward; the gather's custom VJP is where Byzantine robustness
happens: the full-size cotangent each worker produces for a leaf is its
per-worker *block* gradient, so the VJP

  1. clips blockwise with the CGC filter (an n-scalar norm all-gather +
     ``cgc_scales``, exactly ``core.cgc`` semantics per block),
  2. psums the clipped blocks (the filtered sum, paper line 44), and
  3. slices this worker's shard back out (a reduce-scatter).

Per-worker full gradients therefore never materialise — the memory point
of FSDP survives the robust aggregation. Blockwise clipping is an
approximation of the replicated trainer's whole-gradient clipping: the
two agree to rounding when the workers' norms tie (identical worker
batches), and otherwise by a margin that depends on the data
(tests/test_dist.py::test_fsdp_matches_replicated_trainer checks both
that and the blockwise clip itself against a plain jnp reference).

Leaves too small to be worth sharding (< ``MIN_FSDP_ELEMS`` elements, a
module global so tests can lower it) stay replicated and are aggregated
exactly by ``aggregate_rest_cgc``.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.cgc import cgc_scales
from repro.models.nn import Param
from .collectives import _gather_scalar, tree_norm, worker_index

MIN_FSDP_ELEMS = 1 << 16        # below this a leaf stays replicated

# Logical axes the TP layout may claim (DEFAULT_RULES targets the model
# axis for them) — the FSDP plan must not collide with those dims.
_MODEL_LOGICAL = {"mlp", "heads", "kv_heads", "vocab", "expert"}


def _is_param(x) -> bool:
    return isinstance(x, Param)


def _dp_total(mesh, dp_axes: Sequence[str]) -> int:
    sizes = mesh.shape
    n = 1
    for a in dp_axes:
        n *= sizes[a]
    return n


def plan_fsdp(params: Any, mesh, dp_axes: Sequence[str] = ("data",)):
    """Param tree -> matching tree of shard-dimension indices (or None).

    Picks, per leaf, the largest dimension that (a) is not the scanned
    "layers" axis, (b) is not a dim the TP rules map to the model axis,
    and (c) divides by the total data-parallel width. Small leaves
    (< MIN_FSDP_ELEMS) are never planned.
    """
    dp = _dp_total(mesh, dp_axes)

    def choose(p: Param) -> Optional[int]:
        shape = tuple(p.value.shape)
        n_elems = 1
        for s in shape:
            n_elems *= int(s)
        if n_elems < MIN_FSDP_ELEMS:
            return None
        best, best_size = None, 0
        for d, (sz, name) in enumerate(zip(shape, p.axes)):
            if name == "layers" or name in _MODEL_LOGICAL:
                continue
            if sz % dp or sz <= best_size:
                continue
            best, best_size = d, sz
        return best

    return jax.tree.map(choose, params, is_leaf=_is_param)


# ---------------------------------------------------------------------------
# Spec / sharding trees for the planned layout
# ---------------------------------------------------------------------------


def _spec_for_plan(shape_len: int, d: Optional[int],
                   dp_axes: Sequence[str]) -> P:
    if d is None:
        return P()
    entry = dp_axes[0] if len(dp_axes) == 1 else tuple(dp_axes)
    entries = [None] * shape_len
    entries[d] = entry
    return P(*entries)


def _map_with_plan(fn: Callable, params: Any, plan: Any):
    """tree-map ``fn(param, plan_leaf)`` where plan leaves may be None."""
    p_leaves, treedef = jax.tree.flatten(params, is_leaf=_is_param)
    d_leaves = jax.tree.flatten(plan, is_leaf=lambda x: x is None)[0]
    assert len(p_leaves) == len(d_leaves), (len(p_leaves), len(d_leaves))
    return jax.tree.unflatten(treedef,
                              [fn(p, d) for p, d in zip(p_leaves, d_leaves)])


def fsdp_manual_specs(params: Any, plan: Any,
                      dp_axes: Sequence[str]) -> Any:
    """PartitionSpec tree (Param positions -> P) for the worker shard_map."""
    return _map_with_plan(
        lambda p, d: _spec_for_plan(len(p.value.shape), d, dp_axes),
        params, plan)


def fsdp_tree_shardings(params: Any, mesh, plan: Any,
                        dp_axes: Sequence[str] = ("data",)) -> Any:
    """NamedSharding tree for placing params/opt-state in the FSDP layout."""
    return _map_with_plan(
        lambda p, d: NamedSharding(
            mesh, _spec_for_plan(len(p.value.shape), d, dp_axes)),
        params, plan)


# ---------------------------------------------------------------------------
# Just-in-time gather with the blockwise-CGC reduce-scatter VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4))
def _gather_leaves(leaves: Tuple[jax.Array, ...],
                   dims: Tuple[Optional[int], ...], axes: Tuple[str, ...],
                   f: int, use_cgc: bool) -> Tuple[jax.Array, ...]:
    """Gather every planned leaf of one block (unplanned pass through).

    One custom_vjp over the whole block (the top-level params, or one
    layer of the scan) so the backward clips all of the block's leaves
    with a *joint* CGC scale — the per-worker norm is taken over the
    block's concatenated gradient, the closest locally-computable proxy
    for the replicated trainer's whole-gradient norm.
    """
    return tuple(
        v if d is None else jax.lax.all_gather(v, axes, axis=d, tiled=True)
        for v, d in zip(leaves, dims))


def _gather_leaves_fwd(leaves, dims, axes, f, use_cgc):
    return _gather_leaves(leaves, dims, axes, f, use_cgc), None


def _gather_leaves_bwd(dims, axes, f, use_cgc, _res, cts):
    n = int(jax.lax.psum(1, axes))
    wid = worker_index(axes)
    planned = [ct for ct, d in zip(cts, dims) if d is not None]
    if use_cgc and planned:
        # cts are this worker's full-size block gradients: clip blockwise
        # with one joint scale (CGC filter on the block norms).
        norms = _gather_scalar(tree_norm(planned), axes)
        scale = cgc_scales(norms, f)[wid]
    else:
        scale = None
    out = []
    for ct, d in zip(cts, dims):
        if d is None:                   # unplanned: stays a local gradient
            out.append(ct)
            continue
        if use_cgc:
            total = jax.lax.psum(ct * scale.astype(ct.dtype), axes)
        else:
            total = jax.lax.psum(ct, axes) / n
        blk = total.shape[d] // n
        out.append(jax.lax.dynamic_slice_in_dim(total, wid * blk, blk, d))
    return (tuple(out),)


_gather_leaves.defvjp(_gather_leaves_fwd, _gather_leaves_bwd)


def make_gather_fn(plan: Any, dp_axes: Sequence[str], f: int, use_cgc: bool,
                   strip_layer_dim: bool = False) -> Callable:
    """Build gather(values_subtree) for a plan subtree.

    ``strip_layer_dim`` adjusts planned dims for use inside the layer
    scan, where the leading "layers" axis has been peeled off.
    """
    axes = tuple(dp_axes)
    d_leaves = jax.tree.flatten(plan, is_leaf=lambda x: x is None)[0]

    def gather(values):
        v_leaves, treedef = jax.tree.flatten(values)
        assert len(v_leaves) == len(d_leaves), \
            (len(v_leaves), len(d_leaves))
        dims = tuple(None if d is None else d - int(strip_layer_dim)
                     for d in d_leaves)
        out = _gather_leaves(tuple(v_leaves), dims, axes, f, use_cgc)
        return jax.tree.unflatten(treedef, list(out))

    return gather


def aggregate_rest_cgc(grads: Any, plan: Any, dp_axes: Sequence[str],
                       f: int, use_cgc: bool = True) -> Any:
    """Aggregate the replicated (un-planned) remainder leaves exactly.

    Planned leaves pass through untouched — their aggregation already
    happened in the gather VJP's blockwise reduce-scatter. ``use_cgc``
    must match the gather fns so both leaf classes use the same scale
    convention: CGC filtered sum, or the plain mean.
    """
    axes = tuple(dp_axes)
    g_leaves, treedef = jax.tree.flatten(grads)
    d_leaves = jax.tree.flatten(plan, is_leaf=lambda x: x is None)[0]
    assert len(g_leaves) == len(d_leaves), (len(g_leaves), len(d_leaves))
    rest = [g for g, d in zip(g_leaves, d_leaves) if d is None]
    if rest and use_cgc:
        norms = _gather_scalar(tree_norm(rest), axes)
        scale = cgc_scales(norms, f)[worker_index(axes)]
        rest = iter([jax.lax.psum(g * scale.astype(g.dtype), axes)
                     for g in rest])
    elif rest:
        rest = iter([jax.lax.pmean(g, axes) for g in rest])
    else:
        rest = iter(())
    out = [g if d is not None else next(rest)
           for g, d in zip(g_leaves, d_leaves)]
    return jax.tree.unflatten(treedef, out)


def clip_fsdp_global_norm(grads: Any, plan: Any, dp_axes: Sequence[str],
                          max_norm: float) -> Tuple[Any, jax.Array]:
    """Global-norm clip aware of the FSDP layout.

    Planned leaves are disjoint per-worker shards (their squared norms
    psum to the true global contribution); unplanned leaves are
    replicated (counted once). Every worker derives the same scale, so
    replicated state stays in sync.
    """
    axes = tuple(dp_axes)
    g_leaves, _ = jax.tree.flatten(grads)
    d_leaves = jax.tree.flatten(plan, is_leaf=lambda x: x is None)[0]
    assert len(g_leaves) == len(d_leaves), (len(g_leaves), len(d_leaves))
    f32 = jnp.float32
    shard_sq = sum((jnp.sum(jnp.square(g.astype(f32)))
                    for g, d in zip(g_leaves, d_leaves) if d is not None),
                   jnp.zeros((), f32))
    rest_sq = sum((jnp.sum(jnp.square(g.astype(f32)))
                   for g, d in zip(g_leaves, d_leaves) if d is None),
                  jnp.zeros((), f32))
    norm = jnp.sqrt(jax.lax.psum(shard_sq, axes) + rest_sq)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    return jax.tree.map(lambda g: (g.astype(f32) * scale).astype(g.dtype),
                        grads), norm
