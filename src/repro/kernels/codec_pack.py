"""Pallas TPU kernels: wire-codec pack/unpack (comm/wire.py hot path).

Quantized-broadcast scenarios run every worker's gradient through a
codec roundtrip each round; at model scale that is pure bandwidth work,
so the int8 and top-k codecs get streaming kernels here, dispatched
behind ``kernels.ops`` (``REPRO_CODEC_BACKEND``). Layout: a length-m
vector is zero-padded and reshaped to ``(ROWS, cols)`` so every tile is
a legal TPU block (int8 wants 32 sublanes; fp32 wants 8 — we use 32 for
both so pack in/out tiles agree), and the kernels stream ``(ROWS,
BLOCK_C)`` column tiles through VMEM:

  int8 pack    (2, c_blocks) grid: phase 0 accumulates per-row absmax
               and, on its last tile, folds it to the global fp32 scale
               (absmax/127) in scratch; phase 1 re-streams, emitting
               clip(round(v/scale)) int8 tiles — one launch instead of
               the jnp max -> div -> round -> clip chain re-reading v.
  int8 unpack  one pass: q * scale.
  topk pack    per-tile candidate extraction: each tile yields its k
               largest-|v| entries (ties -> lowest flat index, matching
               ``lax.top_k`` stability) as (value, flat-index) rows; the
               tiny (c_blocks, k) candidate table is reduced to the
               exact global top-k by the ops.py wrapper.
  topk unpack  one pass over the dense output: each tile selects the
               shipped values whose flat index lands in it.

All kernels are bitwise-faithful to the jnp codec math on the same
input (max/round/clip order preserved; top-k tie-breaks replicated), so
``comm.wire`` can swap backends without perturbing bit accounting or
trajectories.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32

ROWS = 32               # sublane count: int8's minimum tile, fine for f32
DEFAULT_BLOCK_C = 512   # lane tile (multiple of 128)
INV_127 = np.float32(1.0 / 127.0)


def _flat_index(cols: int, i: int, bc: int, shape):
    """Global flat index of each element of column-tile i under the
    row-major (ROWS, cols) layout: r * cols + i * bc + c."""
    r = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    c = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return r * cols + i * bc + c


# ---------------------------------------------------------------------------
# int8 absmax quantization
# ---------------------------------------------------------------------------


def _int8_pack_kernel(v_ref, q_ref, scale_ref, amax_ref, s_ref):
    """Grid (2, c_blocks): phase 0 absmax reduce, phase 1 quantize. The
    scale stays a (1, 1) vector end to end: the TPU compiler refuses
    scalar stores to VMEM."""
    p = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when((p == 0) & (i == 0))
    def _init():
        amax_ref[...] = jnp.zeros_like(amax_ref)

    @pl.when(p == 0)
    def _absmax():
        amax_ref[...] = jnp.maximum(
            amax_ref[...],
            jnp.max(jnp.abs(v_ref[...]), axis=1, keepdims=True))

    @pl.when((p == 0) & (i == pl.num_programs(1) - 1))
    def _scale():
        s = int8_scale(jnp.max(amax_ref[...], axis=0, keepdims=True))
        s_ref[...] = s
        scale_ref[...] = s

    @pl.when(p == 1)
    def _quantize():
        q = jnp.round(v_ref[...] / s_ref[...])
        q_ref[...] = jnp.clip(q, -127, 127).astype(jnp.int8)


def int8_scale(absmax: jax.Array) -> jax.Array:
    """The int8 codec's scale from the absmax: a multiply by the fp32
    reciprocal of 127, not a division. XLA rewrites a division by a
    constant into that multiply under jit but not in eager mode, so a
    division would round differently depending on where it runs."""
    return jnp.maximum(absmax, 1e-30) * INV_127


def int8_pack(V: jax.Array, block_c: int = DEFAULT_BLOCK_C,
              interpret: bool = False):
    """(ROWS, cols) fp32 -> ((ROWS, cols) int8, (1, 1) fp32 scale)."""
    r, cols = V.shape
    assert r == ROWS and cols % block_c == 0, (V.shape, block_c)
    return pl.pallas_call(
        _int8_pack_kernel,
        grid=(2, cols // block_c),
        in_specs=[pl.BlockSpec((ROWS, block_c), lambda p, i: (0, i))],
        # phase 0 parks the int8 block at tile 0, so no unwritten tile
        # is copied back to HBM before phase 1 fills it
        out_specs=[pl.BlockSpec((ROWS, block_c), lambda p, i: (0, i * p)),
                   pl.BlockSpec((1, 1), lambda p, i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((ROWS, cols), jnp.int8),
                   jax.ShapeDtypeStruct((1, 1), F32)],
        scratch_shapes=[pltpu.VMEM((ROWS, 1), F32),
                        pltpu.VMEM((1, 1), F32)],
        interpret=interpret,
    )(V)


def _int8_unpack_kernel(q_ref, scale_ref, out_ref):
    out_ref[...] = q_ref[...].astype(F32) * scale_ref[0, 0]


def int8_unpack(Q: jax.Array, scale: jax.Array,
                block_c: int = DEFAULT_BLOCK_C,
                interpret: bool = False) -> jax.Array:
    """((ROWS, cols) int8, scale) -> (ROWS, cols) fp32 dequantized."""
    r, cols = Q.shape
    assert r == ROWS and cols % block_c == 0, (Q.shape, block_c)
    return pl.pallas_call(
        _int8_unpack_kernel,
        grid=(cols // block_c,),
        in_specs=[pl.BlockSpec((ROWS, block_c), lambda i: (0, i)),
                  pl.BlockSpec((1, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((ROWS, block_c), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((ROWS, cols), F32),
        interpret=interpret,
    )(Q, scale.reshape(1, 1).astype(F32))


# ---------------------------------------------------------------------------
# top-k sparsification
# ---------------------------------------------------------------------------


def _topk_pack_kernel(v_ref, vals_ref, idx_ref, *, k: int, cols: int,
                      bc: int, kp: int):
    """Grid (c_blocks,). Extract this tile's k largest-|v| candidates
    (ties -> lowest flat index) into padded (1, 1, kp) rows; slots past
    k carry idx -1."""
    i = pl.program_id(0)
    blk = v_ref[...]                                  # (ROWS, bc)
    gidx = _flat_index(cols, i, bc, blk.shape)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, kp), 1)
    vals_row = jnp.zeros((1, kp), F32)
    idx_row = jnp.full((1, kp), -1, jnp.int32)
    work = jnp.abs(blk)
    big = cols * ROWS
    for j in range(k):                                # k is static
        hit = work == jnp.max(work)
        first = jnp.min(jnp.where(hit, gidx, big))
        val = jnp.sum(jnp.where(gidx == first, blk, 0.0))
        vals_row = jnp.where(lane == j, val, vals_row)
        idx_row = jnp.where(lane == j, first, idx_row)
        work = jnp.where(gidx == first, -1.0, work)   # below any |v|
    vals_ref[0] = vals_row
    idx_ref[0] = idx_row


def topk_pack_candidates(V: jax.Array, k: int,
                         block_c: int = DEFAULT_BLOCK_C,
                         interpret: bool = False):
    """(ROWS, cols) fp32 -> ((c_blocks, kp) values, (c_blocks, kp) int32
    flat indices): per-tile top-k candidates, kp = k padded to a lane
    multiple (pad slots have idx -1). The exact global top-k is a subset
    of these candidates as long as each tile holds >= k elements."""
    r, cols = V.shape
    assert r == ROWS and cols % block_c == 0, (V.shape, block_c)
    assert ROWS * block_c >= k, (block_c, k)
    nblk = cols // block_c
    kp = -(-k // 128) * 128
    # one (1, kp) row per tile, kept in its own (1, kp) plane: the TPU
    # wants a block's last two dims tiled by (8, 128) or equal to the
    # array's, and a (1, kp) block of an (nblk, kp) array is neither
    vals, idx = pl.pallas_call(
        functools.partial(_topk_pack_kernel, k=k, cols=cols, bc=block_c,
                          kp=kp),
        grid=(nblk,),
        in_specs=[pl.BlockSpec((ROWS, block_c), lambda i: (0, i))],
        out_specs=[pl.BlockSpec((1, 1, kp), lambda i: (i, 0, 0)),
                   pl.BlockSpec((1, 1, kp), lambda i: (i, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((nblk, 1, kp), F32),
                   jax.ShapeDtypeStruct((nblk, 1, kp), jnp.int32)],
        interpret=interpret,
    )(V)
    return vals[:, 0], idx[:, 0]


def _topk_unpack_kernel(vals_ref, idx_ref, out_ref, *, k: int, cols: int,
                        bc: int):
    """Grid (c_blocks,). Scatter the k shipped (value, flat index) pairs
    into the dense tile they land in (idx -1 never matches)."""
    i = pl.program_id(0)
    gidx = _flat_index(cols, i, bc, (ROWS, bc))
    acc = jnp.zeros((ROWS, bc), F32)
    for j in range(k):                                # k is static
        acc = jnp.where(gidx == idx_ref[0, j], vals_ref[0, j], acc)
    out_ref[...] = acc


def topk_unpack(vals: jax.Array, idx: jax.Array, cols: int,
                block_c: int = DEFAULT_BLOCK_C,
                interpret: bool = False) -> jax.Array:
    """((k,) values, (k,) int32 flat indices) -> (ROWS, cols) dense."""
    k = vals.shape[0]
    assert cols % block_c == 0, (cols, block_c)
    return pl.pallas_call(
        functools.partial(_topk_unpack_kernel, k=k, cols=cols, bc=block_c),
        grid=(cols // block_c,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=pl.BlockSpec((ROWS, block_c), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((ROWS, cols), F32),
        interpret=interpret,
    )(vals.reshape(1, k).astype(F32), idx.reshape(1, k).astype(jnp.int32))
