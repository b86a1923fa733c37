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
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.obs.scopes import ECHO_PROJECT

from .echo import project_onto_span, rows_read, wire_norm_ratio


class RefSet(NamedTuple):
    R: jax.Array        # (n, d) row k = the k-th raw stored
    ref_id: jax.Array   # (n,) int32 sender ID of each row (n: empty row)
    n_ref: jax.Array    # () int32 rows stored so far
    views: jax.Array    # (V, n) bool in sender-ID order, V = 1 or n


def empty(n: int, d: int, dtype, n_views: int) -> RefSet:
    return RefSet(R=jnp.zeros((n, d), dtype),
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
            ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Least-squares projection of g onto the span of the rows in ``view``.

    Returns (x, A x, rows read): x in sender-ID order, zero outside the
    view; the rows read are those of the bucket holding ``n_ref``.
    """
    n = ref.R.shape[0]
    x_rows, proj = project_onto_span(ref.R, _row_mask(ref, view), g, ridge,
                                     n_live=ref.n_ref)
    with jax.named_scope(ECHO_PROJECT):
        x = jnp.zeros_like(x_rows).at[ref.ref_id].set(x_rows, mode="drop")
    return x, proj, rows_read(ref.n_ref, n)


def norm_ratio(ref: RefSet, view: jax.Array, x: jax.Array, g: jax.Array
               ) -> jax.Array:
    """``echo.wire_norm_ratio`` of coefficients x (sender-ID order, as
    transmitted) over the rows in ``view``."""
    x_rows = x.at[ref.ref_id].get(mode="fill", fill_value=0)
    return wire_norm_ratio(ref.R, _row_mask(ref, view), x_rows, g)


def admit(ref: RefSet, i: jax.Array, raw: jax.Array, adds: jax.Array
          ) -> RefSet:
    """Slot i's raw into the views ``adds`` (V,) marks.

    The raw is stored once, at row ``n_ref`` (< n: a slot stores at most
    one raw), when any view admits it: a plain dynamic update, with no
    bounds check that would read the old row. Rows from ``n_ref`` on are
    empty, so a slot that stores nothing rewrites an empty row.
    """
    n = ref.R.shape[0]
    k = ref.n_ref
    stored = jnp.any(adds)
    R = jax.lax.dynamic_update_index_in_dim(
        ref.R, jnp.where(stored, raw, jnp.zeros_like(raw)), k, 0)
    ref_id = ref.ref_id.at[k].set(jnp.where(stored, i, n))
    views = ref.views.at[:, i].set(adds | ref.views[:, i])
    return RefSet(R, ref_id, k + stored.astype(jnp.int32), views)


def rank(ref: RefSet) -> jax.Array:
    """Raws referenced by at least one view: exactly the rows stored."""
    return ref.n_ref
