"""Echo mechanism: projection, decision rule, server reconstruction."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.echo import (echo_decision, echo_decision_from_projection,
                             is_linearly_independent, project_onto_span,
                             reconstruct_echo, recon_rows_read, rows_read)


def _setup(n=6, d=40, k=3, seed=0):
    key = jax.random.PRNGKey(seed)
    R = jax.random.normal(key, (n, d))
    mask = jnp.arange(n) < k
    g = jax.random.normal(jax.random.fold_in(key, 1), (d,))
    return R, mask, g


def test_projection_is_least_squares():
    R, mask, g = _setup()
    x, echo = project_onto_span(R, mask, g)
    # residual orthogonal to the span
    res = g - echo
    for i in range(3):
        assert float(jnp.abs(R[i] @ res)) < 1e-3
    # coefficients vanish outside the mask
    assert np.all(np.asarray(x[3:]) == 0)


def test_projection_exact_for_in_span_vector():
    R, mask, _ = _setup()
    coeffs = jnp.array([0.5, -1.2, 2.0, 0, 0, 0])
    g = coeffs @ (R * mask[:, None])
    x, echo = project_onto_span(R, mask, g)
    np.testing.assert_allclose(np.asarray(echo), np.asarray(g), rtol=1e-4,
                               atol=1e-5)
    dec = echo_decision(R, mask, g, r=1e-3)
    assert bool(dec.send_echo)
    assert float(dec.residual) < 1e-3 * float(jnp.linalg.norm(g))


def test_echo_decision_rejects_orthogonal():
    d = 30
    R = jnp.zeros((4, d)).at[0, 0].set(1.0).at[1, 1].set(1.0)
    mask = jnp.array([True, True, False, False])
    g = jnp.zeros((d,)).at[5].set(1.0)       # orthogonal to span
    dec = echo_decision(R, mask, g, r=0.5)
    assert not bool(dec.send_echo)


def test_empty_reference_never_echoes():
    R, _, g = _setup()
    mask = jnp.zeros(6, bool)
    dec = echo_decision(R, mask, g, r=1e9)
    assert not bool(dec.send_echo)


def test_reconstruction_preserves_norm():
    # server reconstructs g~ = k A x with ||g~|| = ||g|| (paper Sec. 4.2)
    R, mask, g = _setup(seed=2)
    dec = echo_decision(R, mask, g, r=10.0)   # force echo
    assert bool(dec.send_echo)
    # the whole-table expression and the row bucket the server runs
    for used in (None, jnp.bool_(True)):
        g_rec = reconstruct_echo(R, mask, dec.k, dec.x, used=used)
        assert float(jnp.linalg.norm(g_rec)) == pytest.approx(
            float(jnp.linalg.norm(g)), rel=1e-4)
        # direction == echo direction
        cos = float((g_rec @ dec.echo) /
                    (jnp.linalg.norm(g_rec) * jnp.linalg.norm(dec.echo)))
        assert cos == pytest.approx(1.0, abs=1e-5)


def test_reconstruction_masks_extra_coefficients():
    R, mask, g = _setup(seed=3)
    x = jnp.ones((6,))                        # junk outside mask
    for used in (None, jnp.bool_(True)):
        g1 = reconstruct_echo(R, mask, 1.0, x, used=used)
        g2 = reconstruct_echo(R, mask, 1.0, x * mask, used=used)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-6)


def test_linear_independence_detection():
    R, mask, _ = _setup()
    dep = 2.0 * R[0] - R[1]                   # in span
    assert not bool(is_linearly_independent(R, mask, dep, tol=1e-4))
    key = jax.random.PRNGKey(9)
    indep = jax.random.normal(key, (40,))
    assert bool(is_linearly_independent(R, mask, indep, tol=1e-4))
    # empty reference set accepts anything
    assert bool(is_linearly_independent(R, jnp.zeros(6, bool), dep))


@pytest.mark.parametrize("k,bucket", [(0, 8), (1, 8), (7, 8), (8, 8),
                                      (9, 16), (16, 16), (17, 32), (40, 40)])
def test_bucketed_projection_matches_full_table(k, bucket):
    """``n_live`` bounds the rows read to the bucket holding the live
    rows (8, 16, 32, then n = 40): with the live rows stored compactly,
    out of ID order, and junk past them, the projection equals the
    unbounded one over the ID-ordered table, at every bucket edge and
    at rank n, where the bucket is the whole table."""
    n, d, r = 40, 256, 0.5
    assert int(rows_read(jnp.int32(k), n)) == bucket
    key = jax.random.PRNGKey(k)
    k_tab, k_ids, k_junk, k_c, k_noise = jax.random.split(key, 5)
    R_id = jax.random.normal(k_tab, (n, d))
    ids = jax.random.permutation(k_ids, n)[:k]
    mask_id = jnp.zeros(n, bool).at[ids].set(True)
    R_c = jax.random.normal(k_junk, (n, d)).at[:k].set(R_id[ids])
    live = jnp.arange(n) < k
    g = (jax.random.normal(k_c, (k,)) @ R_id[ids]
         + 0.05 * jax.random.normal(k_noise, (d,)))

    x_ref, e_ref = project_onto_span(R_id, mask_id, g)
    x_c, e_c = jax.jit(
        lambda R, m, g, nl: project_onto_span(R, m, g, n_live=nl))(
        R_c, live, g, jnp.int32(k))
    assert np.all(np.asarray(x_c[k:]) == 0)
    x_got = jnp.zeros(n).at[ids].set(x_c[:k])
    np.testing.assert_allclose(np.asarray(x_got), np.asarray(x_ref),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(e_c), np.asarray(e_ref),
                               rtol=1e-4, atol=1e-5)
    ref = echo_decision_from_projection(x_ref, e_ref, mask_id, g, r)
    got = echo_decision_from_projection(x_got, e_c, live, g, r)
    np.testing.assert_allclose(float(got.residual), float(ref.residual),
                               rtol=1e-4, atol=1e-5)
    assert bool(got.send_echo) == bool(ref.send_echo) == (k > 0)


@pytest.mark.parametrize("m,top,bucket", [
    (0, 0, 0), (1, 1, 8), (1, 8, 8), (8, 8, 8), (9, 9, 16), (5, 14, 16),
    (16, 16, 16), (17, 17, 32), (9, 32, 32), (9, 33, 40), (40, 40, 40),
    (2, 40, 40)])
def test_bucketed_reconstruction_matches_full_table(m, top, bucket):
    """A used echo reads the table's first rows up to the bucket (0, 8,
    16, 32, then n = 40) that holds I's highest ID: m IDs out of order
    below ``top``, junk coefficients outside I and finite junk in the
    rows outside I give the whole-table expression's g~."""
    n, d = 40, 256
    key = jax.random.PRNGKey(100 * m + top)
    k_tab, k_ids, k_x = jax.random.split(key, 3)
    ids = (jax.random.permutation(k_ids, top - 1)[:m - 1] if m else
           jnp.zeros(0, jnp.int32))
    ids = jnp.concatenate([ids, jnp.full(min(m, 1), top - 1)])
    mask = jnp.zeros(n, bool).at[ids].set(True)
    assert int(mask.sum()) == m
    G = 3.0 * jax.random.normal(k_tab, (n, d))      # junk outside I too
    x = jax.random.normal(k_x, (n,))                # junk outside I too
    k = jnp.float32(1.7)
    used = jnp.bool_(True)
    assert int(recon_rows_read(mask, used)) == bucket
    want = reconstruct_echo(G, mask, k, x)
    got = jax.jit(reconstruct_echo)(G, mask, k, x, used)
    scale = float(jnp.max(jnp.abs(want))) if m else 1.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6 * scale)


def test_unused_reconstruction_reads_no_row():
    """An echo the server will not use (a raw, silent or detected slot)
    reads no row and returns zeros, whatever I holds."""
    n, d = 40, 64
    G = jax.random.normal(jax.random.PRNGKey(0), (n, d))
    mask = jnp.arange(n) < 30
    used = jnp.bool_(False)
    assert int(recon_rows_read(mask, used)) == 0
    got = jax.jit(reconstruct_echo)(G, mask, jnp.float32(2.0), jnp.ones(n),
                                    used)
    np.testing.assert_array_equal(np.asarray(got), np.zeros(d, np.float32))
