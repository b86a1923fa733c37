"""The layers DeepSeek-V2's share forces, against the plain reference
``bench/reference/deepseek_v2.py`` on seeded random weights at a small
size: YaRN, un-normalised top-k routing, the sequence-wise aux loss,
dropless held-expert routing, the expert-parallel share, and one whole
training step.

Tolerances: where the program and the reference both compute in float32
they differ by summation order alone (1e-5 relative, 1e-6 absolute);
the reference runs its matmuls at 'highest' and the program at the
default, so on a TPU both would still be float32 products. Integer
counts compare exactly.
"""
import dataclasses
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import attention, moe
from repro.models import model as M
from repro.models.rope import yarn_freqs, yarn_mscale

REPO = Path(__file__).resolve().parents[1]
RTOL, ATOL = 1e-5, 1e-6           # float32 against float32: order only

# the tiny share the bench tests run: 16 routed experts, 4 held
TINY = json.loads((REPO / "bench" / "configs"
                   / "deepseek-v2-lite-ep8.json").read_text())
TINY.update(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=3, num_attention_heads=4,
            num_key_value_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
            qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=4,
            n_routed_experts_published=16, expert_offset=4,
            num_experts_per_tok=3, n_shared_experts=1, vocab_size=500,
            compute_dtype="float32",
            rope_scaling=dict(TINY["rope_scaling"],
                              original_max_position_embeddings=16))


@pytest.fixture(scope="module")
def ref():
    from bench.reference import deepseek_v2
    return deepseek_v2


def _program_cfg(cfg_json):
    from bench.drivers import trainer_moe
    return trainer_moe.model_config(cfg_json)


def test_yarn_table_at_dim_64(ref):
    """DeepSeek-V2's rope: dim 64, theta 1e4, 4096 original positions,
    beta 32/1, factor 40: the ramp runs from column 10 to 23."""
    inv = yarn_freqs(64, 1e4, 40.0, 4096, 32.0, 1.0)
    extra = 1e4 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:11], extra[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], extra[23:] / 40, rtol=1e-6)
    assert np.all((inv[11:23] < extra[11:23]) & (inv[11:23] > extra[11:23]
                                                 / 40))
    np.testing.assert_allclose(inv, ref.yarn_inv_freq(64, 1e4, 40, 4096, 32,
                                                      1), rtol=1e-6)
    m = yarn_mscale(40, 0.707)
    assert abs(m * m - 1.5896) < 5e-5
    cfg = get_config("deepseek-v2-lite")
    assert math.isclose(attention.mla_scale(cfg), 192 ** -0.5 * m * m)
    published = json.loads((REPO / "bench" / "configs"
                            / "deepseek-v2-lite-ep8.json").read_text())
    assert math.isclose(ref.rope_tables(published, 8)[2],
                        attention.mla_scale(cfg), rel_tol=1e-12)


def test_plain_configs_keep_plain_rope():
    cfg = get_config("deepseek-v2-236b")
    assert attention.mla_scale(cfg) is None
    from repro.models.rope import rope_spec
    assert rope_spec(cfg, 64) == (None, 1.0)


def test_unnormalised_top_k_times_scaling():
    logits = jax.random.normal(jax.random.PRNGKey(0), (32, 16)) * 2
    w, i, p = moe.router_topk(logits, 6, normalise=False, scaling=2.5)
    top, idx = jax.lax.top_k(jax.nn.softmax(logits, -1), 6)
    np.testing.assert_array_equal(i, idx)
    np.testing.assert_allclose(w, 2.5 * top, rtol=RTOL)
    assert float(jnp.max(jnp.sum(w, -1) / 2.5)) < 1.0
    wn, _, _ = moe.router_topk(logits, 6)
    np.testing.assert_allclose(jnp.sum(wn, -1), 1.0, rtol=1e-6)


def test_sequence_wise_aux_matches_reference(ref):
    cfg = _program_cfg(TINY)
    h = jax.random.normal(jax.random.PRNGKey(1), (3, 20, 64))
    router = 0.3 * jax.random.normal(jax.random.PRNGKey(2), (64, 16))
    _, _, aux = moe.route({"router": router}, cfg, h.reshape(60, 64), 20)
    m = {"router": router,
         "w_gate": jnp.zeros((4, 64, 32)), "w_up": jnp.zeros((4, 64, 32)),
         "w_down": jnp.zeros((4, 32, 64)),
         "shared": {"w_gate": jnp.zeros((64, 32)),
                    "w_up": jnp.zeros((64, 32)),
                    "w_down": jnp.zeros((32, 64))}}
    _, ref_aux = ref._moe(TINY, h, m)
    np.testing.assert_allclose(aux, ref_aux, rtol=RTOL)
    # one batch-wide count of a uniform router would read 1 either way;
    # the per-sequence form differs from the batch form on this router
    bw = moe.load_balance_loss(jax.nn.softmax(h.reshape(60, 64) @ router),
                               jax.lax.top_k(h.reshape(60, 64) @ router,
                                             3)[1], 16)
    assert abs(float(bw) - float(aux)) > 1e-4


def _moe_params(key, E_held, D=64, F=32, E=16, skew=0.0):
    ks = jax.random.split(key, 5)
    router = 0.3 * jax.random.normal(ks[0], (D, E))
    router = router.at[:, 5].add(skew)       # expert 5 takes most copies
    return {"router": router,
            "w_gate": 0.2 * jax.random.normal(ks[1], (E_held, D, F)),
            "w_up": 0.2 * jax.random.normal(ks[2], (E_held, D, F)),
            "w_down": 0.2 * jax.random.normal(ks[3], (E_held, F, D)),
            "shared": {n: 0.2 * jax.random.normal(jax.random.fold_in(
                ks[4], i), s) for i, (n, s) in enumerate(
                (("w_gate", (D, F)), ("w_up", (D, F)), ("w_down", (F, D))))}}


@pytest.mark.parametrize("chunk", [7, 2048])
def test_dropless_under_a_skewed_router(ref, monkeypatch, chunk):
    """Most copies go to one held expert: every held copy is computed (in
    chunks of 7 rows too) and the output is the loop over the experts."""
    monkeypatch.setattr(moe, "HELD_CHUNK", chunk)
    cfg = _program_cfg(TINY)
    h = jnp.abs(jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64)))
    p = _moe_params(jax.random.PRNGKey(4), 4, skew=1.0)
    y, stats = moe.moe_forward(p, cfg, h)
    top_i = jax.lax.top_k(h.reshape(48, 64) @ p["router"], 3)[1]
    held = int(jnp.sum((top_i >= 4) & (top_i < 8)))
    assert int(jnp.sum(top_i == 5)) == 48      # every token picks it
    assert float(stats.held_copies) == held
    assert float(stats.dropped_frac) == 0.0
    assert float(stats.load_ratio) > 3.0
    y_ref, _ = ref._moe(TINY, h, p)
    np.testing.assert_allclose(y, y_ref, rtol=RTOL, atol=ATOL)


def test_the_shares_sum_to_the_whole_layer(ref):
    """Eight shares of 2 of 16 routed experts each, plus the shared
    experts counted once, give the uncut layer."""
    whole = dict(TINY, n_routed_experts=16, expert_offset=0)
    cfg_whole = _program_cfg(whole)
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 24, 64))
    p = _moe_params(jax.random.PRNGKey(6), 16)
    shared = moe.mlp_forward(p["shared"], h)
    total = shared
    for s in range(8):
        cfg = dataclasses.replace(cfg_whole, experts_held=2,
                                  expert_offset=2 * s)
        part = dict(p, **{n: p[n][2 * s:2 * s + 2]
                          for n in ("w_gate", "w_up", "w_down")})
        y, _ = moe.moe_forward(part, cfg, h)
        total = total + (y - shared)
    y_whole, _ = moe.moe_forward(p, cfg_whole, h)
    np.testing.assert_allclose(total, y_whole, rtol=RTOL, atol=1e-5)
    y_ref, _ = ref._moe(whole, h, p)
    np.testing.assert_allclose(y_whole, y_ref, rtol=RTOL, atol=ATOL)


def test_mla_with_yarn_matches_reference(ref):
    """Chunked (16-query blocks) and whole, against the reference."""
    from bench.feeds import deepseek_v2 as feed

    cfg = _program_cfg(TINY)
    w = feed.weights(jax.random.PRNGKey(7), TINY)
    a = w["head_layers"][0]["attn"]
    S = 64
    h = jax.random.normal(jax.random.PRNGKey(8), (2, S, 64))
    pos = jnp.broadcast_to(jnp.arange(S), (2, S))
    with jax.default_matmul_precision("highest"):
        want = ref._attention(TINY, h, a, ref.rope_tables(TINY, S))
        for qc in (16, 64):
            got = attention.mla_forward(a, cfg, h, pos, q_chunk=qc)
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_one_training_step_matches_reference(ref):
    """The reduced share's loss and every leaf's gradient norm, program
    (float32 compute) against the reference."""
    from bench.feeds import deepseek_v2 as feed
    from bench.feeds import lm

    cfg = _program_cfg(TINY)
    w = feed.weights(jax.random.PRNGKey(9), TINY)
    batch = lm.batch(jax.random.PRNGKey(10), 0, 500, 2, 32)
    with jax.default_matmul_precision("highest"):
        (loss, metrics), g = jax.value_and_grad(
            lambda v: M.train_loss(v, cfg, batch), has_aux=True)(w)
        ref_loss, ref_g = jax.value_and_grad(ref.loss)(
            w, batch, ref._thaw(ref.cfg_items(TINY)))
    np.testing.assert_allclose(loss, ref_loss, rtol=RTOL)
    np.testing.assert_allclose(ref.leaf_norms(g), ref.leaf_norms(ref_g),
                               rtol=1e-4)
    assert float(metrics["moe_dropped"]) == 0.0
    assert float(metrics["moe_held_copies"]) > 0


def test_registry_entry_is_the_published_config():
    cfg = get_config("deepseek-v2-lite")
    assert cfg.held_experts == cfg.num_experts == 64
    small = reduced(cfg)
    assert small.yarn_factor == 40.0
    assert small.held_experts == small.num_experts == 4
