"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

Interpret mode accepts tilings, scalar stores and VMEM footprints that
the chip's compiler refuses. These tests compile each kernel of the main
path for a described ``v5e:2x2`` topology (no chip attached) at
qwen3-0.6b widths. Nothing runs, so they check what the compiler accepts
and what memory it plans, never results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import cgc_clip, codec_pack, decode_attention, ops
from repro.models import model as M
from repro.models.nn import split_params
from repro.obs.scopes import ECHO_PROJECT, PROTOCOL_SLOT, SLOT_SERVER

QWEN = get_config("qwen3-0.6b")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to check
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# a 2**20-element vector on the codec's (ROWS, cols) tiling
CODEC_COLS = (1 << 20) // codec_pack.ROWS


def test_int8_pack_and_unpack_compile(one_chip):
    v = _spec((codec_pack.ROWS, CODEC_COLS), jnp.float32, one_chip)
    _compile(lambda x: codec_pack.int8_pack(x, interpret=False), v)
    q = _spec((codec_pack.ROWS, CODEC_COLS), jnp.int8, one_chip)
    s = _spec((1, 1), jnp.float32, one_chip)
    _compile(lambda a, b: codec_pack.int8_unpack(a, b, interpret=False),
             q, s)


def test_topk_pack_and_unpack_compile(one_chip):
    k = 32
    v = _spec((codec_pack.ROWS, CODEC_COLS), jnp.float32, one_chip)
    _compile(lambda x: codec_pack.topk_pack_candidates(x, k,
                                                       interpret=False), v)
    vals = _spec((k,), jnp.float32, one_chip)
    idx = _spec((k,), jnp.int32, one_chip)
    _compile(lambda a, b: codec_pack.topk_unpack(a, b, CODEC_COLS,
                                                 interpret=False),
             vals, idx)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cgc_fused_aggregate_compiles(one_chip, dtype):
    # 8 workers (the kernel's sublane pad) over a 2**20-wide gradient
    G = _spec((8, 1 << 20), dtype, one_chip)
    _compile(lambda g: cgc_clip.cgc_fused_aggregate(g, 2, 8,
                                                    interpret=False), G)
    scale = _spec((8,), jnp.float32, one_chip)
    _compile(lambda g, s: cgc_clip.scale_rows(g, s, interpret=False),
             G, scale)


def test_tree_norm_fits_the_qwen3_gradient(one_chip):
    """The leaf-wise norm of the full qwen3-0.6b gradient tree plans no
    copy of the tree: its scratch memory is a sliver of the gradient's
    own bytes (the old concatenated (1, d) row padded to 8 sublanes
    needed 8x the gradient, 19 GB, on a 16 GB chip)."""
    values = jax.eval_shape(
        lambda: split_params(M.init_params(QWEN, jax.random.PRNGKey(0)))[0])
    grads = jax.tree.map(lambda v: _spec(v.shape, jnp.float32, one_chip),
                         values)
    grad_bytes = sum(4 * v.size for v in jax.tree.leaves(grads))
    assert grad_bytes > 2 * 10 ** 9            # published width, not cut

    def norm(tree):
        return sum(jnp.sum(cgc_clip.sq_sum(ops._leaf_rows(g),
                                           interpret=False))
                   for g in jax.tree.leaves(tree))

    mem = _compile(norm, grads).memory_analysis()
    # arguments: the gradient, plus the tile padding of its small leaves
    assert grad_bytes <= mem.argument_size_in_bytes < 1.001 * grad_bytes
    assert mem.temp_size_in_bytes < grad_bytes // 100, mem


def test_paged_decode_attention_compiles(one_chip):
    """One decode step's attention at qwen3-0.6b widths: 16 query heads
    over 8 KV heads of 128, pages of 16 tokens, bf16 page pools."""
    B, NB, P, ps = 4, 16, 128, 16
    H, K, hd = QWEN.num_heads, QWEN.num_kv_heads, QWEN.resolved_head_dim
    q = _spec((B, H, hd), jnp.bfloat16, one_chip)
    pages = _spec((P, ps, K, hd), jnp.bfloat16, one_chip)
    table = _spec((B, NB), jnp.int32, one_chip)
    lengths = _spec((B,), jnp.int32, one_chip)
    _compile(lambda *a: decode_attention.paged_decode_attention(
        *a, interpret=False), q, pages, pages, table, lengths)


def test_shared_slot_loop_compiles_at_paper_width(one_chip):
    """The paper headline's slot loop (n=100 workers, f=10 sign-flippers,
    d = 2**21, an 839 MB table) on the shared reference set: the row
    bucket switches of its projection and of the server's reconstruction
    compile for the chip, and neither the compact R nor the
    reconstruction's row buckets add a copy of the table to the plan
    (the server's G and R are two tables; another would take the scratch
    past 2.25)."""
    from repro.core import byzantine
    from repro.core.protocol import communication_phase
    from repro.core.types import ProtocolConfig

    n, d, f = 100, 1 << 21, 10
    cfg = ProtocolConfig(n=n, f=f, r=0.3, eta=0.1)
    byz = jnp.arange(n) < f

    def round_(grads):
        plan = byzantine.sign_flip(jax.random.PRNGKey(0), grads, byz,
                                   None, None)
        server, stats = communication_phase(cfg, grads, byz, plan)
        return server.G, stats.proj_rows, stats.recon_rows

    with jax.default_matmul_precision("high"):
        compiled = jax.jit(round_).lower(
            _spec((n, d), jnp.float32, one_chip)).compile()
    switches = [line for line in compiled.as_text().splitlines()
                if " conditional(" in line]
    assert any(f"{ECHO_PROJECT}/cond" in line for line in switches)
    assert any(f"{PROTOCOL_SLOT}/{SLOT_SERVER}/cond" in line
               for line in switches)
    table = 4 * n * d
    assert compiled.memory_analysis().temp_size_in_bytes < 2.25 * table
