"""The fused CGC aggregation op vs the unfused cgc_filter chain.

The contract (ISSUE 6 / DESIGN.md §10): ``ops.cgc_fused_aggregate``
returns (aggregate, norms, scales) matching ``sum(cgc_filter(G, f))``
within fp32 tolerance on the Pallas backend and BITWISE on the jnp
backend, across worker counts, byzantine budgets and dimensions that
are not multiples of the d-block.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cgc import cgc_aggregate, cgc_filter
from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(0)


def _stack(n, d, seed=0):
    G = jax.random.normal(jax.random.fold_in(KEY, seed * 131 + n * d),
                          (n, d))
    return G * jnp.arange(1, n + 1)[:, None]


@pytest.mark.parametrize("n,f,d", [
    (4, 0, 128),        # f=0: threshold is the max norm, nothing clips
    (8, 2, 4096),       # block-aligned d
    (13, 3, 1000),      # d not a multiple of the block, odd n
    (5, 4, 300),        # f = n-1 (max byzantine budget)
    (32, 8, 2048),
    (3, 1, 8192),       # d spanning several 2048-blocks
])
def test_fused_matches_filter_sum(n, f, d):
    G = _stack(n, d)
    want = np.asarray(jnp.sum(cgc_filter(G, f), axis=0))
    want_norms = np.asarray(jnp.linalg.norm(G, axis=-1))
    try:
        ops.set_cgc_backend("jnp")
        agg_j, norms_j, scales_j = ops.cgc_fused_aggregate(G, f)
        ops.set_cgc_backend("pallas")
        agg_p, norms_p, scales_p = ops.cgc_fused_aggregate(G, f)
    finally:
        ops.set_cgc_backend("auto")
    # jnp backend: bitwise the cgc_filter + sum chain
    np.testing.assert_array_equal(np.asarray(agg_j), want)
    # pallas backend: fp32 tolerance (different reduction order)
    np.testing.assert_allclose(np.asarray(agg_p), want, rtol=2e-5,
                               atol=2e-5)
    for norms, scales in ((norms_j, scales_j), (norms_p, scales_p)):
        np.testing.assert_allclose(np.asarray(norms), want_norms,
                                   rtol=1e-5)
        s = np.asarray(scales)
        assert s.shape == (n,) and np.all(s <= 1.0 + 1e-6) \
            and np.all(s > 0)
    # the ref oracle agrees too
    agg_r, norms_r, _ = ref.cgc_fused_aggregate_ref(G, f)
    np.testing.assert_allclose(np.asarray(agg_r), want, rtol=2e-5,
                               atol=2e-5)


def test_fused_threshold_ties_match_sort():
    """Duplicate norms: the in-kernel repeated-max extraction must land
    on the same threshold value as the host-side sort."""
    G = jnp.ones((6, 256)).at[0].mul(3.0).at[1].mul(3.0).at[2].mul(3.0)
    for f in range(6):
        want = np.asarray(jnp.sum(cgc_filter(G, f), axis=0))
        try:
            ops.set_cgc_backend("pallas")
            agg, _, _ = ops.cgc_fused_aggregate(G, f)
        finally:
            ops.set_cgc_backend("auto")
        np.testing.assert_allclose(np.asarray(agg), want, rtol=1e-6,
                                   atol=1e-6)


def test_cgc_aggregate_rides_fused_dispatch():
    """core.cgc.cgc_aggregate now dispatches through the fused op; on
    the default (jnp, this CPU host) backend it is bitwise the old
    sum(cgc_filter) — existing protocol trajectories are unchanged."""
    G = _stack(9, 1000, seed=3)
    assert ops.cgc_backend() in ("jnp", "pallas")
    np.testing.assert_array_equal(
        np.asarray(cgc_aggregate(G, 2)),
        np.asarray(jnp.sum(cgc_filter(G, 2), axis=0)))


def test_fused_backend_switch_validation():
    with pytest.raises(ValueError):
        ops.set_cgc_backend("nope")
    with pytest.raises(ValueError):
        ops.cgc_fused_aggregate(_stack(4, 128), 4)     # f >= n
    with pytest.raises(ValueError):
        ops.cgc_fused_aggregate(_stack(4, 128), -1)


def test_fused_bf16_stack():
    """A bf16 stack is aggregated in fp32 (the kernel upcasts each tile),
    so the reference filters and sums the same bf16 values in fp32; a
    bf16 reference would round every clipped row and the sum itself."""
    G = _stack(8, 512).astype(jnp.bfloat16)
    want = np.asarray(jnp.sum(cgc_filter(G.astype(jnp.float32), 2), axis=0))
    try:
        ops.set_cgc_backend("pallas")
        agg, _, _ = ops.cgc_fused_aggregate(G, 2)
    finally:
        ops.set_cgc_backend("auto")
    assert agg.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(agg, np.float32), want,
                               rtol=2e-2, atol=2e-2)


# --- hypothesis property layer (runs under the [test] extra) ----------

hypothesis = pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402


@settings(max_examples=25, deadline=None)
@given(n=st.integers(2, 20), d=st.integers(1, 300),
       f_frac=st.floats(0.0, 0.99), seed=st.integers(0, 99))
def test_fused_property_grid(n, d, f_frac, seed):
    """Both backends match sum(cgc_filter) on arbitrary (n, f, d),
    including d far from any block multiple; jnp bitwise."""
    f = min(n - 1, int(f_frac * n))
    G = _stack(n, d, seed)
    want = np.asarray(jnp.sum(cgc_filter(G, f), axis=0))
    try:
        ops.set_cgc_backend("jnp")
        agg_j, _, _ = ops.cgc_fused_aggregate(G, f)
        ops.set_cgc_backend("pallas")
        agg_p, _, _ = ops.cgc_fused_aggregate(G, f)
    finally:
        ops.set_cgc_backend("auto")
    np.testing.assert_array_equal(np.asarray(agg_j), want)
    np.testing.assert_allclose(np.asarray(agg_p), want, rtol=3e-5,
                               atol=3e-5)
