"""Coverage for launch specs, RoPE/M-RoPE, FSDP planning, roofline model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh

from repro.configs import ARCH_IDS, INPUT_SHAPES, get_config
from repro.launch.roofline import active_params, analyze, fwd_flops_per_token
from repro.launch.specs import (batch_for, check_applicability, decode_specs,
                                long_context_variant)
from repro.models.rope import apply_mrope, apply_rope

MESH = AbstractMesh((16, 16), ("data", "model"))


# ---------------------------------------------------------------------------
# RoPE / M-RoPE
# ---------------------------------------------------------------------------


def test_rope_preserves_norm_and_relativity():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1, 8, 2, 64))
    pos = jnp.arange(8)[None, :]
    y = apply_rope(x, pos)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(x), axis=-1),
                               np.linalg.norm(np.asarray(y), axis=-1),
                               rtol=1e-5)
    # relative property: <rope(q,m), rope(k,n)> depends only on m-n
    q = jax.random.normal(jax.random.fold_in(key, 1), (1, 1, 1, 64))
    k = jax.random.normal(jax.random.fold_in(key, 2), (1, 1, 1, 64))
    def dot(m, n):
        qm = apply_rope(q, jnp.array([[m]]))
        kn = apply_rope(k, jnp.array([[n]]))
        return float(jnp.sum(qm * kn))
    assert dot(3, 1) == pytest.approx(dot(7, 5), rel=1e-4)
    assert dot(3, 1) != pytest.approx(dot(3, 2), rel=1e-3)


def test_mrope_reduces_to_rope_for_text():
    """Identical (t,h,w) positions == standard RoPE (text tokens)."""
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (2, 6, 4, 64))
    pos = jnp.broadcast_to(jnp.arange(6), (2, 6))
    pos3 = jnp.broadcast_to(pos, (3, 2, 6))
    y1 = apply_rope(x, pos)
    y2 = apply_mrope(x, pos3, (8, 12, 12))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-5,
                               atol=1e-6)


def test_mrope_distinct_axes_differ():
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (1, 4, 2, 64))
    t = jnp.arange(4)[None]
    same = jnp.stack([t, t, t])[:, 0][:, None, :]
    spatial = jnp.stack([t, t * 2, t * 3])[:, 0][:, None, :]
    y1 = apply_mrope(x, same.reshape(3, 1, 4), (8, 12, 12))
    y2 = apply_mrope(x, spatial.reshape(3, 1, 4), (8, 12, 12))
    assert float(jnp.max(jnp.abs(y1 - y2))) > 1e-3


# ---------------------------------------------------------------------------
# launch.specs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("shape", list(INPUT_SHAPES))
def test_specs_cover_every_pair(arch, shape):
    cfg = get_config(arch)
    sc = INPUT_SHAPES[shape]
    if check_applicability(cfg, sc):
        assert sc.kind == "decode" and cfg.is_encoder
        return
    cfg = long_context_variant(cfg, sc)
    if sc.kind == "decode":
        io, cache = decode_specs(cfg, sc)
        assert io["token"].value.shape == (sc.global_batch, 1)
        assert len(jax.tree.leaves(cache)) > 0
    else:
        b = batch_for(cfg, sc)
        key = "features" if cfg.frontend == "audio" else "tokens"
        assert b[key].value.shape[0] == sc.global_batch


def test_long_context_variant_windows_dense_only():
    dense = get_config("command-r-plus-104b")
    assert long_context_variant(dense,
                                INPUT_SHAPES["long_500k"]).sliding_window \
        == 8192
    assert long_context_variant(dense,
                                INPUT_SHAPES["decode_32k"]).sliding_window \
        is None
    ssm = get_config("xlstm-125m")
    assert long_context_variant(ssm,
                                INPUT_SHAPES["long_500k"]).sliding_window \
        is None


# ---------------------------------------------------------------------------
# FSDP planning
# ---------------------------------------------------------------------------


def test_fsdp_plan_avoids_model_axis_and_small_leaves():
    from repro.dist.fsdp import plan_fsdp
    from repro.launch.specs import abstract_params
    cfg = get_config("qwen3-0.6b")
    params = abstract_params(cfg)
    plan = plan_fsdp(params, MESH, dp_axes=("data",))
    leaves = jax.tree.leaves(plan, is_leaf=lambda x: x is None)
    planned = [d for d in leaves if d is not None]
    assert planned, "large leaves must be planned"
    # norm scales (tiny) are never planned
    assert plan["final_norm"] is None
    # planned dim must divide by dp=16
    from repro.models.nn import Param
    flat_p = jax.tree.leaves(params,
                             is_leaf=lambda x: isinstance(x, Param))
    flat_d = jax.tree.leaves(plan, is_leaf=lambda x: x is None)
    for p, d in zip(flat_p, flat_d):
        if d is not None:
            assert p.value.shape[d] % 16 == 0


# ---------------------------------------------------------------------------
# roofline analytic model
# ---------------------------------------------------------------------------


def test_active_params_close_to_param_count_dense():
    """For dense archs, active == total params (sanity of the model)."""
    from repro.launch.specs import abstract_params
    from repro.models.nn import Param
    for arch in ["qwen3-0.6b", "codeqwen1.5-7b"]:
        cfg = get_config(arch)
        n_true = sum(int(np.prod(p.value.shape)) for p in jax.tree.leaves(
            abstract_params(cfg), is_leaf=lambda x: isinstance(x, Param)))
        n_model = active_params(cfg)
        assert abs(n_model - n_true) / n_true < 0.02, (arch, n_model, n_true)


def test_moe_active_far_below_total():
    cfg = get_config("deepseek-v2-236b")
    from repro.launch.specs import abstract_params
    from repro.models.nn import Param
    n_true = sum(int(np.prod(p.value.shape)) for p in jax.tree.leaves(
        abstract_params(cfg), is_leaf=lambda x: isinstance(x, Param)))
    n_active = active_params(cfg)
    assert n_active < 0.2 * n_true          # 21B active of 236B


def test_roofline_terms_positive_and_dominant_valid():
    for arch in ["qwen3-0.6b", "zamba2-2.7b", "deepseek-v2-236b"]:
        cfg = get_config(arch)
        for sname, sc in INPUT_SHAPES.items():
            if check_applicability(cfg, sc):
                continue
            rl = analyze(cfg, sc, 256, 16, 16, None)
            assert rl.compute_s > 0 and rl.memory_s > 0
            assert rl.dominant in ("compute", "memory", "collective")
            assert rl.model_flops_global > 0


def test_decode_flops_much_smaller_than_train():
    cfg = get_config("qwen3-0.6b")
    f_train = fwd_flops_per_token(cfg, 2048)
    rl_t = analyze(cfg, INPUT_SHAPES["train_4k"], 256, 16, 16, None)
    rl_d = analyze(cfg, INPUT_SHAPES["decode_32k"], 256, 16, 16, None)
    assert rl_d.compute_s < 1e-2 * rl_t.compute_s
    assert f_train > 2 * active_params(cfg) * 0.5
