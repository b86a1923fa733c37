"""Echo-compressed data-parallel aggregation (the paper's idea at scale).

The radio-network insight of Echo-CGC — a worker whose gradient is close
to the span of previously-heard gradients broadcasts O(n) coefficients
instead of O(d) raw values — maps onto DP training as an *optimistic
fast path*. The trainer keeps ``K`` reference pytrees (the last K round
aggregates, replicated on every worker). Each round every worker:

  1. projects its gradient onto span(basis) using the precomputed K x K
     Gram matrix (one K-vector of tree-dots, one K x K solve — no
     d-sized collective anywhere),
  2. checks the paper's Eq. 7 condition ||g - Bx|| <= r ||g||,
  3. all-gathers only the (K,) coefficient vectors and its gradient norm.

If *all* workers pass the echo test (``all_echo``), CGC runs entirely in
coefficient space: reconstructed gradients are k_j * B x_j with the norm
ratio k_j = ||g_j|| / ||B x_j|| (paper line 39), their norms are the
gathered ||g_j||, and the filtered sum is B @ (sum_j s_j k_j x_j) — each
worker rebuilds it locally from the shared basis. Per-round collective
traffic drops from O(d) to O(n*K + n).

When any worker fails the test the round's metrics flag all_echo=False
and the driver (``repro.launch.engine.Trainer``) re-runs the standard
full-gradient CGC step, then rolls the basis with the returned raw
aggregate (``roll_basis``). Successful echo rounds leave the basis
unchanged by default — the reconstructed aggregate lies in span(basis)
and adds no information, mirroring the paper's reference set R, which
only ever contains overheard RAW gradients (``TrainerConfig.roll_policy``
flips this to roll every round).
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.cgc import cgc_scales
from repro.obs.scopes import ECHO_BASIS_GRAM
from .collectives import _gather_scalar, tree_norm, worker_index

F32 = jnp.float32
_RIDGE = 1e-6


def round_comm_bits(codec, n: int, d: int, k: int, all_echo: bool,
                    attempted: bool = True) -> int:
    """Bits one echo-DP driver round costs under ``codec``.

    An attempted optimistic round has every worker broadcast an echo over
    the k-reference basis (:func:`repro.comm.echo_round_bits`); when the
    round is invalid (or was never attempted — e.g. a metered channel
    refused it) every worker retransmits its raw gradient on top. The
    driver reports this into the shared :class:`repro.comm.CommLedger`.
    """
    from repro.comm import echo_round_bits, raw_round_bits
    bits = echo_round_bits(codec, n, k) if attempted else 0
    if not (attempted and all_echo):
        bits += raw_round_bits(codec, n, d)
    return bits


def init_basis(values: Any, k: int) -> List[Any]:
    """K zero reference pytrees shaped like the gradient (f32)."""
    zero = jax.tree.map(lambda v: jnp.zeros(v.shape, F32), values)
    return [zero for _ in range(k)]


def roll_basis(basis: List[Any], aggregate: Any) -> List[Any]:
    """Drop the oldest reference, append this round's aggregate."""
    newest = jax.tree.map(lambda a: a.astype(F32), aggregate)
    return list(basis[1:]) + [newest]


def tree_vdot(a: Any, b: Any) -> jax.Array:
    """<a, b> over all leaves (fp32)."""
    return sum(jnp.vdot(x.astype(F32), y.astype(F32))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def basis_gram(basis: Sequence[Any]) -> jax.Array:
    """(K, K) Gram matrix of the reference pytrees."""
    k = len(basis)
    with jax.named_scope(ECHO_BASIS_GRAM):
        rows = []
        for i in range(k):
            rows.append(jnp.stack([tree_vdot(basis[i], basis[j])
                                   for j in range(k)]))
        return jnp.stack(rows)


def _ridged(gram: jax.Array) -> jax.Array:
    scale = jnp.maximum(jnp.max(jnp.abs(jnp.diag(gram))), 1.0)
    return gram + _RIDGE * scale * jnp.eye(gram.shape[0], dtype=gram.dtype)


def echo_dp_aggregate(grads: Any, basis: Sequence[Any], gram: jax.Array,
                      axes: Sequence[str], f: int, r: float,
                      codec=None, ef=None
                      ) -> Tuple[Any, jax.Array, Dict[str, jax.Array]]:
    """Coefficient-space CGC over the worker axes.

    Returns (aggregate, all_echo, diags); the aggregate is only valid
    when ``all_echo`` is True (the driver falls back otherwise).

    ``codec`` (a :class:`repro.comm.Codec`, or None for the lossless
    default) is applied to each worker's transmitted coefficient vector:
    the all-gather carries the codec's reconstruction, so a quantized
    wire format degrades the shared aggregate exactly as it would on the
    air. The Eq. 7 test stays sender-local on the exact projection.

    ``ef`` (a replicated ``(n, K)`` array, or None) carries per-worker
    error-feedback residuals (``comm.policy.feedback``): each worker
    adds its row before encoding its coefficients and keeps what the
    codec lost. The updated residuals ride back gathered under
    ``diags["ef_state"]`` — the driver commits them only when this
    round's transmission is actually used (echo valid, no fades), so a
    discarded optimistic attempt never corrupts the carried state.
    """
    axes = tuple(axes)
    K = len(basis)
    # Projection of my gradient onto span(basis): x = (B^T B)^-1 B^T g.
    b = jnp.stack([tree_vdot(basis[i], grads) for i in range(K)])   # (K,)
    x = jnp.linalg.solve(_ridged(gram), b)                          # (K,)
    g_norm = tree_norm(grads)
    proj_sq = x @ gram @ x
    res_sq = jnp.maximum(g_norm ** 2 - 2.0 * (x @ b) + proj_sq, 0.0)
    ok = jnp.sqrt(res_sq) <= r * g_norm                    # Eq. 7

    n_ok = jax.lax.psum(ok.astype(jnp.int32), axes)
    n = int(jax.lax.psum(1, axes))
    all_echo = n_ok == n

    # O(K)-per-worker exchange: coefficients + norms only, wire-coded
    # (with error-feedback compensation when the driver threads it).
    ef_new = None
    if ef is None:
        x_wire = x if codec is None else codec.roundtrip(x)
    else:
        from repro.comm.policy.feedback import ef_compensate
        my_ef = ef[worker_index(axes)]                     # my (K,) row
        x_wire, my_ef_new = ef_compensate(codec, x, my_ef)
        if my_ef_new is None:                              # codec=None
            my_ef_new = my_ef
        ef_new = jax.lax.all_gather(my_ef_new.astype(F32), axes)  # (n, K)
    xs = jax.lax.all_gather(x_wire, axes)                  # (n, K)
    norms = _gather_scalar(g_norm, axes)                   # (n,)
    proj_norms = jnp.sqrt(jnp.maximum(
        jnp.einsum("nk,kl,nl->n", xs, gram, xs), 1e-30))
    k_ratio = jnp.where(proj_norms > 1e-15, norms / proj_norms, 0.0)
    scales = cgc_scales(norms, f)                          # CGC on ||g_j||
    coef = jnp.sum((scales * k_ratio)[:, None] * xs, axis=0)   # (K,)
    agg = jax.tree.map(
        lambda *leaves: sum(c * l.astype(F32)
                            for c, l in zip(coef, leaves)),
        *basis)
    diags = {
        "echo_frac": n_ok.astype(F32) / n,
        "echo_residual_ratio": jax.lax.pmean(
            jnp.sqrt(res_sq) / jnp.maximum(g_norm, 1e-30), axes),
    }
    if ef_new is not None:
        diags["ef_state"] = ef_new
        diags["ef_residual_norm"] = jnp.max(
            jnp.linalg.norm(ef_new, axis=-1))
    return agg, all_echo, diags
