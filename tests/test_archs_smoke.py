"""Per-architecture smoke tests (deliverable f).

Each assigned architecture instantiates its REDUCED variant (<=2 layers,
d_model<=512, <=4 experts — same family code paths) and runs one forward /
train step on CPU asserting output shapes and the absence of NaNs; decoder
archs additionally run one serve step against a fresh cache.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, reduced
from repro.data import train_inputs
from repro.models import model as M
from repro.models.nn import split_params

B, S = 2, 64


def _build(arch):
    cfg = reduced(get_config(arch))
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    values, _ = split_params(params)
    batch = train_inputs(jax.random.PRNGKey(1), cfg, B, S)
    return cfg, values, batch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_train_step_smoke(arch):
    cfg, values, batch = _build(arch)
    loss, metrics = jax.jit(
        lambda v, b: M.train_loss(v, cfg, b))(values, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), arch
    assert float(loss) > 0
    # one SGD step decreases nothing catastrophically (finite grads)
    grads = jax.grad(lambda v: M.train_loss(v, cfg, batch)[0])(values)
    gn = sum(float(jnp.sum(g.astype(jnp.float32) ** 2))
             for g in jax.tree.leaves(grads))
    assert np.isfinite(gn) and gn > 0, arch


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_shape(arch):
    cfg, values, batch = _build(arch)
    x, stats = jax.jit(lambda v, b: M.forward(v, cfg, b))(values, batch)
    assert x.shape == (B, S, cfg.d_model)
    assert np.isfinite(np.asarray(x, np.float32)).all(), arch


@pytest.mark.parametrize("arch", [a for a in ARCH_IDS
                                  if get_config(a).has_decode])
def test_decode_step_smoke(arch):
    cfg, values, _ = _build(arch)
    cache_p = M.init_cache(cfg, B, 32)
    cache, _ = split_params(cache_p)
    tok = jnp.zeros((B, 1), jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    logits, new_cache = jax.jit(
        lambda v, c, t, p: M.decode_step(v, cfg, c, t, p))(
        values, cache, tok, pos)
    assert logits.shape == (B, cfg.padded_vocab)
    assert np.isfinite(np.asarray(logits)).all(), arch
    assert jax.tree_util.tree_structure(new_cache) == \
        jax.tree_util.tree_structure(cache)


def test_encoder_skips_decode():
    cfg = reduced(get_config("hubert-xlarge"))
    assert not cfg.has_decode
    with pytest.raises(ValueError):
        M.init_cache(cfg, B, 32)


def test_exact_assigned_configs():
    """The FULL configs match the assignment table exactly."""
    t = {a: get_config(a) for a in ARCH_IDS}
    a = t["deepseek-v2-236b"]
    assert (a.num_layers, a.d_model, a.num_heads, a.vocab_size) == \
        (60, 5120, 128, 102400)
    assert (a.num_experts, a.top_k, a.num_shared_experts,
            a.moe_d_ff, a.kv_lora_rank) == (160, 6, 2, 1536, 512)
    z = t["zamba2-2.7b"]
    assert (z.num_layers, z.d_model, z.ssm_state, z.d_ff) == \
        (54, 2560, 64, 10240)
    m = t["minicpm3-4b"]
    assert (m.num_layers, m.d_model, m.num_heads, m.d_ff, m.vocab_size) == \
        (62, 2560, 40, 6400, 73448)
    c = t["codeqwen1.5-7b"]
    assert (c.num_layers, c.d_model, c.d_ff, c.vocab_size) == \
        (32, 4096, 13440, 92416)
    h = t["hubert-xlarge"]
    assert (h.num_layers, h.d_model, h.num_heads, h.d_ff, h.vocab_size) == \
        (48, 1280, 16, 5120, 504)
    assert h.is_encoder
    r = t["command-r-plus-104b"]
    assert (r.num_layers, r.d_model, r.num_heads, r.num_kv_heads, r.d_ff,
            r.vocab_size) == (64, 12288, 96, 8, 33792, 256000)
    x = t["xlstm-125m"]
    assert (x.num_layers, x.d_model, x.num_heads, x.vocab_size, x.d_ff) == \
        (12, 768, 4, 50304, 0)
    q = t["qwen2-vl-72b"]
    assert (q.num_layers, q.d_model, q.num_heads, q.num_kv_heads, q.d_ff,
            q.vocab_size) == (80, 8192, 64, 8, 29568, 152064)
    assert q.mrope
    qm = t["qwen3-moe-30b-a3b"]
    assert (qm.num_layers, qm.d_model, qm.num_heads, qm.num_kv_heads,
            qm.vocab_size) == (48, 2048, 32, 4, 151936)
    assert (qm.num_experts, qm.top_k, qm.moe_d_ff) == (128, 8, 768)
    q6 = t["qwen3-0.6b"]
    assert (q6.num_layers, q6.d_model, q6.num_heads, q6.num_kv_heads,
            q6.d_ff, q6.vocab_size) == (28, 1024, 16, 8, 3072, 151936)
    assert q6.qk_norm


def test_deepseek_v2_lite_published_config():
    """DeepSeek-V2-Lite as its config.json publishes it."""
    d = get_config("deepseek-v2-lite")
    assert (d.num_layers, d.d_model, d.num_heads, d.vocab_size, d.d_ff) == \
        (27, 2048, 16, 102400, 10944)
    assert (d.num_experts, d.top_k, d.num_shared_experts, d.moe_d_ff,
            d.first_dense_layers) == (64, 6, 2, 1408, 1)
    assert (d.kv_lora_rank, d.q_lora_rank, d.qk_nope_head_dim,
            d.qk_rope_head_dim, d.v_head_dim) == (512, 0, 128, 64, 128)
    assert (d.yarn_factor, d.yarn_original_max_pos, d.yarn_beta_fast,
            d.yarn_beta_slow, d.yarn_mscale, d.yarn_mscale_all_dim) == \
        (40.0, 4096, 32.0, 1.0, 0.707, 0.707)
    assert not d.norm_topk_prob and d.routed_scaling_factor == 1.0
    assert d.router_aux == "seq"
    assert d.held_experts == 64 and not d.tie_embeddings
