"""Pallas TPU kernels: CGC norm / clip / fused-aggregate over (n, d) stacks.

The server's aggregation phase (paper Eq. 8) streams a matrix whose row
count n is tiny (#workers) but whose row length d is huge (model
dimension) — a textbook memory-bound shape. All kernels tile d through
VMEM in (n, BLOCK_D) tiles:

  ``norms_kernel``  accumulate per-row sum-of-squares in an (n,) fp32
                    VMEM accumulator while streaming the tiles;
  ``scale_kernel``  re-stream the tiles, multiplying each row by a
                    per-row scale;
  ``sq_sum_kernel`` one gradient leaf's sum of squares: full-width row
                    tiles of the leaf in its own layout, so a model's
                    gradient tree is normed leaf by leaf with no
                    concatenated or padded copy of it;
  ``fused_kernel``  the whole round in ONE pallas_call: a (2, d_blocks)
                    grid streams the table twice without ever leaving
                    the device — phase 0 accumulates sq-norms and, on
                    its last tile, derives the clip threshold (the
                    (f+1)-th largest norm) and per-row scales entirely
                    in-kernel; phase 1 re-streams, scaling rows and
                    reducing them into the (1, d) aggregate. This
                    replaces the norms -> host sort -> scale_rows -> sum
                    chain (three HBM round trips and a device->host
                    sync) with one launch.

d-tiles are MXU/VPU aligned (BLOCK_D multiple of 128); n is padded to 8
(sublane) by the wrapper in ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32

DEFAULT_BLOCK_D = 2048
DEFAULT_SQ_BLOCK_ELEMS = 1 << 19   # 2 MiB of fp32 per sq_sum row tile


def _norms_kernel(g_ref, out_ref, acc_ref):
    """Grid (d_blocks,). Accumulate row sum-of-squares into acc (n, 1)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    blk = g_ref[...].astype(F32)                    # (n, BLOCK_D)
    acc_ref[...] += jnp.sum(blk * blk, axis=1, keepdims=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _done():
        out_ref[...] = acc_ref[...]


def row_sq_norms(G: jax.Array, block_d: int = DEFAULT_BLOCK_D,
                 interpret: bool = False) -> jax.Array:
    """(n, d) -> (n,) fp32 sum of squares per row."""
    n, d = G.shape
    bd = min(block_d, d)
    assert d % bd == 0, (d, bd)
    out = pl.pallas_call(
        _norms_kernel,
        grid=(d // bd,),
        in_specs=[pl.BlockSpec((n, bd), lambda i: (0, i))],
        out_specs=pl.BlockSpec((n, 1), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, 1), F32),
        scratch_shapes=[pltpu.VMEM((n, 1), F32)],
        interpret=interpret,
    )(G)
    return out[:, 0]


def _sq_sum_kernel(x_ref, out_ref, acc_ref, *, rows: int, block_r: int):
    """Grid (row_blocks,). Accumulate column sums of x*x into acc (1,
    cols); rows past ``rows`` (the ragged last tile) count as zero."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    blk = x_ref[...].astype(F32)                    # (block_r, cols)
    if rows % block_r:
        r = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0)
        blk = jnp.where(r + i * block_r < rows, blk, 0.0)
    acc_ref[...] += jnp.sum(blk * blk, axis=0, keepdims=True)

    @pl.when(i == pl.num_programs(0) - 1)
    def _done():
        out_ref[...] = acc_ref[...]


def sq_sum(X: jax.Array, interpret: bool = False) -> jax.Array:
    """(rows, cols) -> (1, cols) fp32 column sums of squares.

    Streams row tiles of about ``DEFAULT_SQ_BLOCK_ELEMS`` elements, each
    the full row width; a ragged last tile is masked in-kernel, so the
    operand is read in place with no padded copy."""
    rows, cols = X.shape
    br = max(32, (DEFAULT_SQ_BLOCK_ELEMS // cols) // 32 * 32)
    br = rows if rows <= br else br
    return pl.pallas_call(
        functools.partial(_sq_sum_kernel, rows=rows, block_r=br),
        grid=(pl.cdiv(rows, br),),
        in_specs=[pl.BlockSpec((br, cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1, cols), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((1, cols), F32),
        scratch_shapes=[pltpu.VMEM((1, cols), F32)],
        interpret=interpret,
    )(X)


def _fused_kernel(g_ref, agg_ref, sq_ref, scale_ref, acc_ref, sc_ref, *,
                  f: int, n_valid: int):
    """Grid (2, d_blocks). Phase 0 accumulates row sum-of-squares into
    acc (n_pad, 1) and, at the last d-tile, derives the CGC threshold
    and per-row clip scales in-kernel (f repeated max-extractions over
    n floats — f and n are tiny, so this beats shipping n norms to the
    host for a sort). Phase 1 re-streams the tiles, writing each
    aggregate d-tile as sum_rows(g * scale)."""
    p = pl.program_id(0)
    i = pl.program_id(1)
    n_pad = acc_ref.shape[0]

    @pl.when((p == 0) & (i == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(p == 0)
    def _accumulate():
        blk = g_ref[...].astype(F32)                # (n_pad, BLOCK_D)
        acc_ref[...] += jnp.sum(blk * blk, axis=1, keepdims=True)

    @pl.when((p == 0) & (i == pl.num_programs(1) - 1))
    def _threshold():
        sq = acc_ref[...]                           # (n_pad, 1)
        norms = jnp.sqrt(sq)
        # 1D iota is unsupported on TPU; build row ids as a 2D iota
        row = jax.lax.broadcasted_iota(jnp.int32, (n_pad, 1), 0)
        valid = row < n_valid
        # knock out the f largest norms (ties -> lowest row first, same
        # value the host-side sort would pick); what remains tops out at
        # the (f+1)-th largest = the clip threshold
        work = jnp.where(valid, norms, -jnp.inf)
        for _ in range(f):                          # f is static
            hit = work == jnp.max(work)
            drop = jnp.min(jnp.where(hit, row, n_pad))
            work = jnp.where(row == drop, -jnp.inf, work)
        thr = jnp.max(work)
        scale = jnp.where(
            valid, jnp.minimum(1.0, thr / jnp.maximum(norms, 1e-12)), 0.0)
        sc_ref[...] = scale                         # phase 1 reads this
        sq_ref[...] = sq
        scale_ref[...] = scale

    @pl.when(p == 1)
    def _scale_and_reduce():
        blk = g_ref[...].astype(F32)
        agg_ref[...] = jnp.sum(blk * sc_ref[...], axis=0, keepdims=True)


def cgc_fused_aggregate(G: jax.Array, f: int, n_valid: int,
                        block_d: int = DEFAULT_BLOCK_D,
                        interpret: bool = False):
    """Fused CGC round on an already-padded (n_pad, d_pad) table.

    Returns ``(agg (1, d_pad) f32, sq (n_pad, 1) f32, scale (n_pad, 1)
    f32)``; rows >= ``n_valid`` are padding (scale 0, excluded from the
    threshold). The ops.py wrapper pads/slices and exposes the public
    ``(agg, norms, scales)`` contract.
    """
    n, d = G.shape
    bd = min(block_d, d)
    assert d % bd == 0, (d, bd)
    assert 0 <= f < n_valid <= n, (f, n_valid, n)
    return pl.pallas_call(
        functools.partial(_fused_kernel, f=f, n_valid=n_valid),
        grid=(2, d // bd),
        in_specs=[pl.BlockSpec((n, bd), lambda p, i: (0, i))],
        out_specs=[pl.BlockSpec((1, bd), lambda p, i: (0, i)),
                   pl.BlockSpec((n, 1), lambda p, i: (0, 0)),
                   pl.BlockSpec((n, 1), lambda p, i: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, d), F32),
                   jax.ShapeDtypeStruct((n, 1), F32),
                   jax.ShapeDtypeStruct((n, 1), F32)],
        scratch_shapes=[pltpu.VMEM((n, 1), F32), pltpu.VMEM((n, 1), F32)],
        interpret=interpret,
    )(G)


def _scale_kernel(g_ref, scale_ref, out_ref):
    """Grid (d_blocks,). out = g * scale (row-broadcast)."""
    out_ref[...] = (g_ref[...].astype(F32) * scale_ref[...]).astype(
        out_ref.dtype)


def scale_rows(G: jax.Array, scale: jax.Array,
               block_d: int = DEFAULT_BLOCK_D,
               interpret: bool = False) -> jax.Array:
    n, d = G.shape
    bd = min(block_d, d)
    assert d % bd == 0, (d, bd)
    return pl.pallas_call(
        _scale_kernel,
        grid=(d // bd,),
        in_specs=[pl.BlockSpec((n, bd), lambda i: (0, i)),
                  pl.BlockSpec((n, 1), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((n, bd), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, d), G.dtype),
        interpret=interpret,
    )(G, scale.reshape(n, 1))
