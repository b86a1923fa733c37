"""The echo mechanism — the paper's main novelty (Sec. 3, communication phase).

A worker that overheard raw gradients ``R = {g_{i_1}, ..., g_{i_k}}`` computes
the projection of its local gradient onto span(R):

    A = [g_{i_1} | ... | g_{i_k}]  in R^{d x k}
    x = (A^T A)^{-1} A^T g        (Moore-Penrose least squares)
    echo gradient  g* = A x

and broadcasts the O(n)-bit echo message (||g||/||g*||, x, I) iff

    ||g* - g|| <= r ||g||.                                        (Eq. 7)

We work with a *masked fixed-shape* representation: the reference buffer is
always (n, d) with a boolean ``mask`` marking valid rows, so the whole slot
loop jits. The Gram solve adds a tiny ridge scaled to the Gram diagonal for
numerical stability (exact MP-inverse in exact arithmetic per Appendix D —
columns of A are linearly independent by construction).

The server-side reconstruction is ``g~ = k * A_I x`` (paper line 39), which by
construction satisfies ||g~|| = ||g|| (the norm ratio k restores the original
magnitude while keeping the echo direction).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.obs.scopes import ECHO_PROJECT


class EchoDecision(NamedTuple):
    send_echo: jax.Array     # () bool — Eq. 7 holds and span is non-empty
    k: jax.Array             # () norm ratio ||g|| / ||g*||
    x: jax.Array             # (n,) projection coefficients (masked)
    echo: jax.Array          # (d,) the echo gradient A x
    residual: jax.Array      # () ||Ax - g|| (diagnostic)


def masked_gram(R: jax.Array, mask: jax.Array, ridge: float) -> jax.Array:
    """Gram matrix A^T A of the masked reference rows, ridged for stability.

    Masked-out rows contribute identity rows/cols so the solve stays
    well-posed without affecting valid coefficients.
    """
    n = R.shape[0]
    Rm = R * mask[:, None]
    G = Rm @ Rm.T                                    # (n, n)
    diag_scale = jnp.maximum(jnp.max(jnp.abs(jnp.diag(G))), 1.0)
    # Identity on masked-out rows keeps the system invertible there.
    off = (~mask).astype(G.dtype)
    G = G + jnp.diag(off * diag_scale + ridge * diag_scale)
    return G


def row_buckets(n: int) -> Tuple[int, ...]:
    """Static row counts a bounded projection may read: multiples of 8
    (the fp32 sublane tile) that double, 8, 16, 32, ..., capped at n."""
    out, b = [], 8
    while b < n:
        out.append(b)
        b *= 2
    return tuple(out) + (n,)


def _bucket_index(n_live: jax.Array, buckets: Tuple[int, ...]) -> jax.Array:
    """Index of the smallest bucket holding ``n_live`` rows."""
    return jnp.sum(jnp.asarray(buckets[:-1]) < n_live).astype(jnp.int32)


def rows_read(n_live: jax.Array, n: int) -> jax.Array:
    """Rows a projection bounded by ``n_live`` reads from an (n, d) table."""
    buckets = row_buckets(n)
    return jnp.asarray(buckets, jnp.int32)[_bucket_index(n_live, buckets)]


def _project(R: jax.Array, mask: jax.Array, g: jax.Array, ridge: float
             ) -> Tuple[jax.Array, jax.Array]:
    Rm = R * mask[:, None]
    b = Rm @ g                                       # A^T g, (n,)
    G = masked_gram(R, mask, ridge)
    x = jnp.linalg.solve(G, b)
    x = x * mask
    echo = x @ Rm                                    # A x, (d,)
    return x, echo


def project_onto_span(
    R: jax.Array, mask: jax.Array, g: jax.Array, ridge: float = 1e-8,
    n_live: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Least-squares coefficients x and projection A x of g onto span(R[mask]).

    Equivalent to the paper's x = (A^T A)^{-1} A^T g with A the masked columns
    (we store gradients as rows, so A = R[mask].T). Returns (x, echo) with
    x zero outside the mask.

    ``n_live`` (traced) promises that no row at or past it is in the mask.
    The projection then reads only the smallest bucket of
    :func:`row_buckets` that holds ``n_live`` rows, chosen on the device,
    and pads x back to n: the same mathematics on the rows that can be
    live, so a table that is mostly empty is not read whole.
    """
    with jax.named_scope(ECHO_PROJECT):
        if n_live is None:
            return _project(R, mask, g, ridge)
        n = R.shape[0]
        buckets = row_buckets(n)

        def branch(b):
            def run(R, mask, g):
                x, echo = _project(R[:b], mask[:b], g, ridge)
                return jnp.pad(x, (0, n - b)), echo
            return run

        return jax.lax.switch(_bucket_index(n_live, buckets),
                              [branch(b) for b in buckets], R, mask, g)


def echo_decision_from_projection(
    x: jax.Array,
    echo: jax.Array,
    mask: jax.Array,
    g: jax.Array,
    r: float,
) -> EchoDecision:
    """Eq. 7 decision given a precomputed projection (x, echo) of g.

    Factored out so the slot loop can run the Gram solve once and derive
    both this decision and the independence test from it.
    """
    g_norm = jnp.linalg.norm(g)
    echo_norm = jnp.linalg.norm(echo)
    residual = jnp.linalg.norm(echo - g)
    nonempty = jnp.any(mask)
    ok = (residual <= r * g_norm) & nonempty & (echo_norm > 0)
    k = jnp.where(echo_norm > 0, g_norm / jnp.maximum(echo_norm, 1e-30), 0.0)
    return EchoDecision(send_echo=ok, k=k, x=x, echo=echo, residual=residual)


def echo_decision(
    R: jax.Array,
    mask: jax.Array,
    g: jax.Array,
    r: float,
    ridge: float = 1e-8,
) -> EchoDecision:
    """Full slot-time computation of worker j (paper lines 18-24)."""
    x, echo = project_onto_span(R, mask, g, ridge)
    return echo_decision_from_projection(x, echo, mask, g, r)


def independent_from_projection(
    echo: jax.Array,
    mask: jax.Array,
    g: jax.Array,
    tol: float = 1e-6,
) -> jax.Array:
    """Appendix-D test given a precomputed projection of g onto span(R).

    Relative-residual form: independent iff ||A A^+ g - g|| > tol ||g||;
    an empty R always accepts g.
    """
    res = jnp.linalg.norm(echo - g)
    return (res > tol * jnp.linalg.norm(g)) | (~jnp.any(mask))


def is_linearly_independent(
    R: jax.Array,
    mask: jax.Array,
    g: jax.Array,
    tol: float = 1e-6,
    ridge: float = 1e-8,
) -> jax.Array:
    """Appendix-D test (line 29): g independent of R iff A A^+ g != g."""
    _, proj = project_onto_span(R, mask, g, ridge)
    return independent_from_projection(proj, mask, g, tol)


def wire_norm_ratio(
    R: jax.Array,
    mask: jax.Array,
    x: jax.Array,
    g: jax.Array,
) -> jax.Array:
    """Norm ratio ``k = ||g|| / ||A x||`` for the coefficients *as
    transmitted*.

    When a lossy codec quantizes the echo coefficients, the sender must
    compute the ratio against the quantized reconstruction ``A x̂`` (not
    its exact projection) or the server-side ``g~ = k A x̂`` loses the
    paper's ``||g~|| = ||g||`` invariant. With the ideal fp32 codec
    ``x̂ == x`` and this is bit-for-bit the ratio
    :func:`echo_decision_from_projection` computes.
    """
    Rm = R * mask[:, None]
    echo = (x * mask) @ Rm
    g_norm = jnp.linalg.norm(g)
    echo_norm = jnp.linalg.norm(echo)
    return jnp.where(echo_norm > 0,
                     g_norm / jnp.maximum(echo_norm, 1e-30), 0.0)


def _recon_buckets(n: int) -> Tuple[int, ...]:
    """Row counts a bounded reconstruction may read: none, then the
    projection's :func:`row_buckets`."""
    return (0,) + row_buckets(n)


def _ref_extent(ref_mask: jax.Array, used: jax.Array) -> jax.Array:
    """Rows from the table's top that hold all of I: one past I's highest
    sender ID when the reconstruction is used, 0 otherwise."""
    top = jnp.max(jnp.where(ref_mask, jnp.arange(1, ref_mask.shape[0] + 1), 0))
    return jnp.where(used, top, 0)


def recon_rows_read(ref_mask: jax.Array, used: jax.Array) -> jax.Array:
    """Rows ``reconstruct_echo(..., used=used)`` reads from the (n, d)
    table: the smallest of :func:`_recon_buckets` holding I."""
    buckets = _recon_buckets(ref_mask.shape[0])
    return jnp.asarray(buckets, jnp.int32)[
        _bucket_index(_ref_extent(ref_mask, used), buckets)]


def reconstruct_echo(
    G_server: jax.Array,
    ref_mask: jax.Array,
    k: jax.Array,
    x: jax.Array,
    used: Optional[jax.Array] = None,
) -> jax.Array:
    """Server-side g~ = k * A_I x (paper line 39).

    ``G_server`` is the server's (n, d) gradient table; ``ref_mask`` marks I.
    Coefficients outside I are zeroed defensively (a Byzantine echo may ship
    junk there).

    ``used`` (traced bool) says whether the caller will use the result.
    The reconstruction then reads only the table's first b rows, b the
    smallest of :func:`_recon_buckets` that holds I (chosen on the
    device), and none when the result is not used: zeros come back. The
    same sum over fewer zero terms. The rows are a static slice from the
    top: on a TPU v5e it streams at the rate of a whole-table pass, where
    rows picked by ID cost a whole 8-row tile group each at a dynamic
    offset and a gather copies the table. An honest I is the admitted
    raws, which begin with slot 0, so its highest ID bounds it closely.
    """
    xm = x * ref_mask
    if used is None:
        return k * (xm @ (G_server * ref_mask[:, None]))
    buckets = _recon_buckets(G_server.shape[0])

    def branch(b):
        def run(G, ref_mask, k, xm):
            if b == 0:
                return jnp.zeros(G.shape[1:], G.dtype)
            if b == G.shape[0]:
                return k * (xm @ (G * ref_mask[:, None]))
            # k folded into the b coefficients: no second pass over g~
            return (k * xm[:b]) @ (G[:b] * ref_mask[:b, None])
        return run

    return jax.lax.switch(_bucket_index(_ref_extent(ref_mask, used), buckets),
                          [branch(b) for b in buckets], G_server, ref_mask, k,
                          xm)
