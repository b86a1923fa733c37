"""Byzantine-robust aggregation as shard_map collectives.

``core/aggregators.py`` defines every aggregator on an explicit (n, d)
gradient table. Inside a data-parallel shard_map no such table exists —
each worker holds only its own full-size gradient pytree — so this module
re-derives the same math from collectives over the worker axes:

  * **CGC** (Gupta-Vaidya filter, the paper's aggregation) needs only the
    per-worker gradient *norms*: an n-scalar all-gather, a shared
    ``cgc_scales`` computation, and one psum of the locally-scaled
    gradients. The (n, d) table is never materialised — this is the
    communication pattern that scales CGC to real model sizes.
  * **median / trimmed-mean** are coordinate-wise: leaf-by-leaf
    all-gathers (transient n-times-leaf buffers, never the concatenated
    table) followed by the per-coordinate reduction.
  * **Krum** accumulates the pairwise squared-distance matrix leaf by
    leaf, scores like ``core.aggregators.krum``, then psum-selects the
    winner's gradient.

``AGG_FNS[name](grads, axes, f) -> (aggregate, diags)`` follows the
``core.aggregators.AGGREGATORS`` scale conventions exactly: "cgc" is the
filtered *sum* (paper line 44), everything else is mean-scale — the CPU
test asserts ``AGG_FNS["cgc"]`` matches ``core.aggregators.cgc_sum`` on
the gathered table to ~1e-5 (reduction order differs, so not bitwise).

The norm hot path (``tree_norm``, feeding every CGC/echo/FSDP
aggregation here) dispatches through ``kernels.ops.tree_sq_norm`` to the
fused Pallas streaming pass on TPU (DESIGN.md §5).
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.cgc import cgc_scales, cgc_threshold
from repro.run.registry import COLLECTIVE_AGGREGATORS

F32 = jnp.float32


# ---------------------------------------------------------------------------
# Worker identity
# ---------------------------------------------------------------------------


def axis_sizes(axes: Sequence[str]) -> Tuple[int, ...]:
    """Static sizes of manual mesh axes (psum of a literal constant-folds)."""
    return tuple(jax.lax.psum(1, ax) for ax in axes)


def worker_index(axes: Sequence[str]) -> jax.Array:
    """Linear worker id over ``axes`` (row-major, matching all_gather)."""
    sizes = axis_sizes(axes)
    wid = jnp.zeros((), jnp.int32)
    for ax, sz in zip(axes, sizes):
        wid = wid * sz + jax.lax.axis_index(ax)
    return wid


def num_workers(axes: Sequence[str]) -> int:
    return int(jax.lax.psum(1, tuple(axes)))


def _gather_scalar(x: jax.Array, axes: Sequence[str]) -> jax.Array:
    """All workers' values of a scalar -> (n,) in worker-index order."""
    return jax.lax.all_gather(x.astype(F32), tuple(axes))


def _gather_leaf(x: jax.Array, axes: Sequence[str]) -> jax.Array:
    """All workers' values of one leaf -> (n, *leaf shape)."""
    return jax.lax.all_gather(x, tuple(axes))


# ---------------------------------------------------------------------------
# Byzantine injection (testing / resilience experiments)
# ---------------------------------------------------------------------------

_BYZ_SCALE = {"sign_flip": 1.0, "large_norm": 100.0, "zero": 0.0,
              "little_is_enough": 0.33}


def inject_byzantine(grads, wid: jax.Array, n_byz: int, mode: str,
                     scale: float = None):
    """Overwrite the gradients of workers ``wid < n_byz`` with an attack.

    Mirrors ``core.byzantine``: "sign_flip" sends -scale*g (classic
    descent reversal), "large_norm" sends -scale*g with a huge scale
    (what CGC's norm clipping neutralises), "zero" crashes silently,
    "little_is_enough" reverses with a sub-unit scale (Baruch et al.) —
    deliberately small enough that norm clipping never fires on it.
    """
    if mode not in _BYZ_SCALE:
        raise ValueError(f"unknown byzantine mode {mode!r}; "
                         f"known: {sorted(_BYZ_SCALE)}")
    s = _BYZ_SCALE[mode] if scale is None else scale
    is_byz = wid < n_byz
    factor = jnp.where(is_byz, jnp.float32(-s if mode != "zero" else 0.0),
                       1.0)
    return jax.tree.map(lambda g: g * factor.astype(g.dtype), grads)


# ---------------------------------------------------------------------------
# Norm-only CGC (the scalable path)
# ---------------------------------------------------------------------------


def tree_norm(grads) -> jax.Array:
    """Global L2 norm of a gradient pytree (fp32 accumulation).

    The sum of squares dispatches through ``kernels.ops.tree_sq_norm``
    — on TPU that is the Pallas streaming pass (``cgc_clip.sq_sum``,
    one per leaf, reading each in place), so every CGC/echo/FSDP norm
    in this module rides the kernel
    (backend switch: ``kernels.ops.set_norm_backend`` /
    ``REPRO_NORM_BACKEND``).
    """
    from repro.kernels.ops import tree_sq_norm
    return jnp.sqrt(tree_sq_norm(grads))


@COLLECTIVE_AGGREGATORS.register("cgc")
def aggregate_pytree_cgc_sum(grads, axes: Sequence[str], f: int):
    """CGC filtered *sum* over the worker axes (== cgc_sum on the table).

    One scalar all-gather (the norms) + one psum of the scaled gradients;
    gradients themselves are never gathered.
    """
    axes = tuple(axes)
    norms = _gather_scalar(tree_norm(grads), axes)        # (n,)
    scales = cgc_scales(norms, f)
    mine = scales[worker_index(axes)]
    agg = jax.tree.map(
        lambda g: jax.lax.psum(g * mine.astype(g.dtype), axes), grads)
    diags = {
        "cgc_threshold": cgc_threshold(norms, f),
        "cgc_clipped_frac": jnp.mean((scales < 1.0 - 1e-6).astype(F32)),
        "grad_norm_mean": jnp.mean(norms),
    }
    return agg, diags


@COLLECTIVE_AGGREGATORS.register("cgc_mean")
def aggregate_pytree_cgc(grads, axes: Sequence[str], f: int):
    """CGC filter + *mean* (scale-compatible with the other pytree fns)."""
    axes = tuple(axes)
    n = num_workers(axes)
    agg, diags = aggregate_pytree_cgc_sum(grads, axes, f)
    return jax.tree.map(lambda g: g / n, agg), diags


@COLLECTIVE_AGGREGATORS.register("mean")
def aggregate_pytree_mean(grads, axes: Sequence[str], f: int = 0):
    """Fault-intolerant baseline: plain pmean over the worker axes."""
    axes = tuple(axes)
    return jax.tree.map(lambda g: jax.lax.pmean(g, axes), grads), {}


# ---------------------------------------------------------------------------
# Table-based aggregators (leaf-wise gathers, no concatenated table)
# ---------------------------------------------------------------------------


@COLLECTIVE_AGGREGATORS.register("median")
def aggregate_pytree_median(grads, axes: Sequence[str], f: int = 0):
    """Coordinate-wise median across workers, leaf by leaf."""
    axes = tuple(axes)
    agg = jax.tree.map(
        lambda g: jnp.median(_gather_leaf(g.astype(F32), axes), axis=0
                             ).astype(g.dtype), grads)
    return agg, {}


@COLLECTIVE_AGGREGATORS.register("trimmed_mean")
def aggregate_pytree_trimmed_mean(grads, axes: Sequence[str], f: int):
    """Coordinate-wise f-trimmed mean across workers (needs n > 2f)."""
    axes = tuple(axes)
    n = num_workers(axes)
    if n <= 2 * f:
        raise ValueError(f"trimmed_mean needs n > 2f (n={n}, f={f})")

    def trim(g):
        table = jnp.sort(_gather_leaf(g.astype(F32), axes), axis=0)
        kept = table[f:n - f] if f > 0 else table
        return jnp.mean(kept, axis=0).astype(g.dtype)

    return jax.tree.map(trim, grads), {}


@COLLECTIVE_AGGREGATORS.register("krum")
def aggregate_pytree_krum(grads, axes: Sequence[str], f: int):
    """Krum (Blanchard et al.): leafwise pairwise distances -> winner psum."""
    axes = tuple(axes)
    n = num_workers(axes)
    sq = jnp.zeros((n, n), F32)
    for g in jax.tree.leaves(grads):
        t = _gather_leaf(g.astype(F32), axes).reshape(n, -1)
        # ||ti - tj||^2 via the Gram matrix: no (n, n, d) intermediate.
        gram = t @ t.T
        sn = jnp.diag(gram)
        sq = sq + jnp.maximum(sn[:, None] + sn[None, :] - 2.0 * gram, 0.0)
    sq = sq + jnp.diag(jnp.full((n,), jnp.inf))
    k = max(n - f - 2, 1)
    scores = jnp.sum(jnp.sort(sq, axis=1)[:, :k], axis=1)
    winner = jnp.argmin(scores)
    mine = (worker_index(axes) == winner)
    agg = jax.tree.map(
        lambda g: jax.lax.psum(g * mine.astype(g.dtype), axes), grads)
    return agg, {"krum_score_min": jnp.min(scores)}


# The shared plugin registry (repro.run.registry): a new distributed
# aggregator is one @COLLECTIVE_AGGREGATORS.register("name") function
# with the (grads, axes, f) -> (aggregate, diags) signature above.
AGG_FNS = COLLECTIVE_AGGREGATORS
