"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.echo import project_onto_span
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("n", [4, 13, 32])
@pytest.mark.parametrize("d", [128, 1000, 4096])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cgc_clip_sweep(n, d, dtype):
    G = (jax.random.normal(KEY, (n, d)) *
         jnp.arange(1, n + 1)[:, None]).astype(dtype)
    f = max(1, n // 4)
    out = ops.cgc_clip(G, f)
    exp = ref.cgc_clip_ref(G, f)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("n,d", [(4, 256), (8, 1000), (16, 2048)])
def test_cgc_norms_sweep(n, d):
    G = jax.random.normal(jax.random.fold_in(KEY, d), (n, d))
    np.testing.assert_allclose(np.asarray(ops.cgc_norms(G)),
                               np.asarray(ref.cgc_norms_ref(G)), rtol=1e-5)


@pytest.mark.parametrize("n,k,d", [(6, 3, 512), (12, 7, 1000), (16, 16, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_echo_project_sweep(n, k, d, dtype):
    key = jax.random.fold_in(KEY, n * d)
    A = jax.random.normal(key, (n, d)).astype(dtype)
    g = jax.random.normal(jax.random.fold_in(key, 1), (d,)).astype(dtype)
    mask = jnp.arange(n) < k
    x, echo = ops.echo_project(A, mask, g)
    x2, echo2 = project_onto_span(A.astype(jnp.float32), mask,
                                  g.astype(jnp.float32))
    tol = 1e-3 if dtype == jnp.float32 else 6e-2
    np.testing.assert_allclose(np.asarray(x), np.asarray(x2), rtol=tol,
                               atol=tol)
    np.testing.assert_allclose(np.asarray(echo), np.asarray(echo2),
                               rtol=tol, atol=tol)


def test_tree_sq_norm_backend_dispatch():
    """The CGC norm path's backend switch: the fused Pallas pass
    (interpret mode here) matches the plain jnp reduction on a
    mixed-shape/dtype gradient pytree. "e" spans three row tiles with a
    ragged, masked last one; "f" is wider than a row tile takes whole,
    so it is folded and zero-padded."""
    tree = {
        "a": jax.random.normal(KEY, (37, 19)),
        "b": jax.random.normal(jax.random.fold_in(KEY, 1), (301,)),
        "c": jax.random.normal(jax.random.fold_in(KEY, 2), (5,)
                               ).astype(jnp.bfloat16),
        "d": jnp.asarray(2.5),
        "e": jax.random.normal(jax.random.fold_in(KEY, 3), (1100, 1024)),
        "f": jax.random.normal(jax.random.fold_in(KEY, 4), (3, 40000)
                               ).astype(jnp.bfloat16),
    }
    assert ops.norm_backend() in ("jnp", "pallas")
    try:
        ops.set_norm_backend("jnp")
        want = float(ops.tree_sq_norm(tree))
        ops.set_norm_backend("pallas")
        got = float(ops.tree_sq_norm(tree))
    finally:
        ops.set_norm_backend("auto")
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert float(ops.tree_sq_norm({})) == 0.0
    with pytest.raises(ValueError):
        ops.set_norm_backend("nope")
    # dist.collectives.tree_norm rides the same dispatch
    from repro.dist.collectives import tree_norm
    np.testing.assert_allclose(float(tree_norm(tree)), np.sqrt(want),
                               rtol=1e-5)


def test_echo_project_gram_matches_ref():
    A = jax.random.normal(KEY, (8, 1024))
    g = jax.random.normal(jax.random.fold_in(KEY, 1), (1024,))
    from repro.kernels.echo_project import gram_and_proj
    gram, b = gram_and_proj(A, g, 256, interpret=True)
    gram_e, b_e = ref.gram_ref(A, g)
    np.testing.assert_allclose(np.asarray(gram), np.asarray(gram_e),
                               rtol=1e-4)
    np.testing.assert_allclose(np.asarray(b), np.asarray(b_e), rtol=1e-4)


@pytest.mark.parametrize("B,H,K,T", [(2, 8, 4, 256), (1, 16, 2, 300),
                                     (4, 4, 4, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, H, K, T, dtype):
    hd = 64
    key = jax.random.fold_in(KEY, B * T)
    q = jax.random.normal(key, (B, H, hd)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1),
                          (B, T, K, hd)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2),
                          (B, T, K, hd)).astype(dtype)
    mask = jax.random.uniform(jax.random.fold_in(key, 3), (B, T)) < 0.8
    out = ops.decode_attention(q, k, v, mask)
    exp = ref.decode_attention_ref(q, k, v, mask)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), rtol=tol,
                               atol=tol)


@pytest.mark.parametrize("B,H,K,P,ps,NB", [(2, 8, 4, 16, 8, 4),
                                           (3, 4, 4, 32, 16, 3),
                                           (1, 16, 2, 8, 8, 7)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_sweep(B, H, K, P, ps, NB, dtype):
    """Pallas paged kernel (scalar-prefetch block-table gather) vs the
    gather-then-attend oracle, mixed lengths incl. an idle lane."""
    hd = 64
    key = jax.random.fold_in(KEY, B * P * ps)
    q = jax.random.normal(key, (B, H, hd)).astype(dtype)
    kp = jax.random.normal(jax.random.fold_in(key, 1),
                           (P, ps, K, hd)).astype(dtype)
    vp = jax.random.normal(jax.random.fold_in(key, 2),
                           (P, ps, K, hd)).astype(dtype)
    perm = jax.random.permutation(jax.random.fold_in(key, 3), P - 1) + 1
    bt = perm[:B * NB].reshape(B, NB).astype(jnp.int32)
    lengths = (jax.random.randint(jax.random.fold_in(key, 4), (B,), 1,
                                  NB * ps + 1)
               .at[0].set(0).astype(jnp.int32))   # lane 0 idle
    from repro.kernels.decode_attention import paged_decode_attention
    out = paged_decode_attention(q, kp, vp, bt, lengths, interpret=True)
    exp = ref.paged_decode_attention_ref(q, kp, vp, bt, lengths)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), rtol=tol,
                               atol=tol)


def test_scale_rows_backend_dispatch():
    """The server-side CGC filter's row-scaling pass: the Pallas
    ``scale_rows`` streaming kernel (interpret mode here) matches plain
    jnp through the ``REPRO_SCALE_BACKEND`` switch, and the protocol's
    ``cgc_filter`` rides the same dispatch."""
    from repro.core.cgc import cgc_filter
    G = jax.random.normal(KEY, (13, 1000)) * \
        jnp.arange(1, 14)[:, None]
    scale = jax.random.uniform(jax.random.fold_in(KEY, 1), (13,))
    assert ops.scale_backend() in ("jnp", "pallas")
    try:
        ops.set_scale_backend("jnp")
        want = np.asarray(ops.scale_rows(G, scale))
        filt_want = np.asarray(cgc_filter(G, 3))
        ops.set_scale_backend("pallas")
        got = np.asarray(ops.scale_rows(G, scale))
        filt_got = np.asarray(cgc_filter(G, 3))
    finally:
        ops.set_scale_backend("auto")
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(filt_got, filt_want, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(filt_want, np.asarray(ref.cgc_clip_ref(G, 3)),
                               rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError):
        ops.set_scale_backend("nope")


def test_decode_attention_fully_masked_row_safe():
    B, H, K, T, hd = 1, 4, 2, 128, 32
    q = jax.random.normal(KEY, (B, H, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, T, K, hd))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, T, K, hd))
    mask = jnp.zeros((B, T), bool).at[:, 0].set(True)
    out = ops.decode_attention(q, k, v, mask)
    assert np.isfinite(np.asarray(out)).all()
