"""The reference set R of the TDMA slot loop, and each worker's view of it.

A raw gradient overheard in a slot is stored once, compactly: row k of R is
the k-th raw stored, in admission order, with its sender ID beside it; rows
from ``n_ref`` on are empty (zeros, ID n). Which stored raws a worker may
project onto is its *view*: an (n,) bool mask in sender-ID order, the order
of the echo coefficients and of the reference IDs on the wire.

With V = 1 view every worker hears every worker, so every R_j is the one
shared in-order independent prefix (the paper's single-hop radio). Under a
partial hearing graph V = n and row j of ``views`` is worker j's.

Only this module knows the storage order. A projection reads the smallest
row bucket (``echo.row_buckets``) that holds the ``n_ref`` stored rows and
hands its coefficients back in sender-ID order.

The Gram matrix K = R R^T of the stored rows is carried beside R, in the
same order, and extended by one row and column when a raw is stored: a
projection masks it to its view and never multiplies the rows with each
other. The new row is R raw, which the sender's own projection has
computed when it projected that raw onto the one view; otherwise
admission reads the bucket once for it.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.obs.scopes import ECHO_PROJECT

from .echo import projection, rows_dot, rows_read, wire_norm_ratio


class RefSet(NamedTuple):
    R: jax.Array        # (n, d) row k = the k-th raw stored
    K: jax.Array        # (n, n) R R^T; zero from row and column n_ref on
    ref_id: jax.Array   # (n,) int32 sender ID of each row (n: empty row)
    n_ref: jax.Array    # () int32 rows stored so far
    views: jax.Array    # (V, n) bool in sender-ID order, V = 1 or n


def empty(n: int, d: int, dtype, n_views: int) -> RefSet:
    return RefSet(R=jnp.zeros((n, d), dtype),
                  K=jnp.zeros((n, n), dtype),
                  ref_id=jnp.full((n,), n, jnp.int32),
                  n_ref=jnp.int32(0),
                  views=jnp.zeros((n_views, n), bool))


def view(ref: RefSet, i: jax.Array) -> jax.Array:
    """Worker i's view: the one shared view, or row i of the n."""
    return ref.views[0] if ref.views.shape[0] == 1 else ref.views[i]


def _row_mask(ref: RefSet, view: jax.Array) -> jax.Array:
    """``view`` as a mask over R's rows; an empty row is in no view."""
    return view.at[ref.ref_id].get(mode="fill", fill_value=False)


def project(ref: RefSet, view: jax.Array, g: jax.Array, ridge: float
            ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Least-squares projection of g onto the span of the rows in ``view``.

    Returns (x, A x, R g, rows read): x in sender-ID order, zero outside
    the view; R g over the rows in the view in storage order, which
    :func:`admit` takes as ``cross`` when g is the raw it stores and
    every stored row is in the view; the rows read are those of the
    bucket holding ``n_ref``.
    """
    n = ref.R.shape[0]
    p = projection(ref.R, _row_mask(ref, view), g, ridge, n_live=ref.n_ref,
                   gram=ref.K)
    with jax.named_scope(ECHO_PROJECT):
        x = jnp.zeros_like(p.x).at[ref.ref_id].set(p.x, mode="drop")
    return x, p.echo, p.atg, rows_read(ref.n_ref, n)


def norm_ratio(ref: RefSet, view: jax.Array, x: jax.Array, g: jax.Array
               ) -> jax.Array:
    """``echo.wire_norm_ratio`` of coefficients x (sender-ID order, as
    transmitted) over the rows in ``view``."""
    x_rows = x.at[ref.ref_id].get(mode="fill", fill_value=0)
    return wire_norm_ratio(ref.R, _row_mask(ref, view), x_rows, g)


def admit(ref: RefSet, i: jax.Array, raw: jax.Array, adds: jax.Array,
          cross: Optional[jax.Array] = None) -> Tuple[RefSet, jax.Array]:
    """Slot i's raw into the views ``adds`` (V,) marks.

    The raw is stored once, at row ``n_ref`` (< n: a slot stores at most
    one raw), when any view admits it: a plain dynamic update, with no
    bounds check that would read the old row. Rows from ``n_ref`` on are
    empty, so a slot that stores nothing rewrites an empty row.

    K gains row and column ``n_ref``: R raw, from ``cross`` (the raw's
    projection's R g, :func:`project`) or else read from the bucket
    holding the stored rows, in a slot that stores only, and ||raw||^2.
    Returns the new set and the rows read for K.
    """
    n = ref.R.shape[0]
    k = ref.n_ref
    stored = jnp.any(adds)
    R = jax.lax.dynamic_update_index_in_dim(
        ref.R, jnp.where(stored, raw, jnp.zeros_like(raw)), k, 0)
    if cross is None:
        cross, gram_rows = rows_dot(ref.R, raw, k, stored)
    else:
        gram_rows = jnp.int32(0)
    row = jnp.where(stored, cross.at[k].set(jnp.vdot(raw, raw)), 0.0)
    K = jax.lax.dynamic_update_index_in_dim(ref.K, row, k, 0)
    K = jax.lax.dynamic_update_index_in_dim(K, row, k, 1)
    ref_id = ref.ref_id.at[k].set(jnp.where(stored, i, n))
    views = ref.views.at[:, i].set(adds | ref.views[:, i])
    return (RefSet(R, K, ref_id, k + stored.astype(jnp.int32), views),
            gram_rows)


def rank(ref: RefSet) -> jax.Array:
    """Raws referenced by at least one view: exactly the rows stored."""
    return ref.n_ref
