"""Jitted public wrappers around the Pallas kernels.

On a TPU backend the kernels run compiled; everywhere else (this CPU
container, unit tests) they run in interpret mode against the same
BlockSpecs, keeping the contract identical to the ref.py oracles.

These ops pad shapes to kernel-friendly multiples (n -> multiple of 8
sublanes, d -> multiple of the d-block) and strip the padding afterwards,
so callers can use arbitrary worker counts / dimensions.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import cgc_clip as _cgc
from repro.kernels import codec_pack as _pack
from repro.kernels import decode_attention as _dec
from repro.kernels import echo_project as _gram
from repro.run.registry import (CGC_BACKENDS, CODEC_PACK_BACKENDS,
                                NORM_BACKENDS, PAGED_ATTN_BACKENDS,
                                Registry, SCALE_BACKENDS)

F32 = jnp.float32


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Backend switches (DESIGN.md §5): the CGC hot path in
# dist/collectives.py computes gradient-pytree norms through
# ``tree_sq_norm`` below, which dispatches through the NORM_BACKENDS
# registry either to the Pallas streaming pass (cgc_clip.sq_sum over
# each leaf in its own layout) or to plain jnp; scale_rows and
# paged_decode_attention dispatch the same way. Registering a new
# implementation (e.g. a cuda kernel) makes it selectable by name with
# no edits here.
# ---------------------------------------------------------------------------


class _BackendSwitch:
    """One named trace-time backend toggle (REPRO_<NAME>_BACKEND env /
    setter) over a backend registry: "auto" resolves to pallas on TPU
    and jnp elsewhere (interpret-mode pallas is correct anywhere but
    only wins on TPU); any other registered name selects that entry.

    The choice is read at TRACE time: set it before the first jit compile
    of the consuming step — already-compiled executables keep the backend
    they were traced with until ``jax.clear_caches()``.

    Each resolution bumps a ``kernels.<name>.<backend>`` counter on the
    active tracker. Because dispatch happens at trace time, the counters
    measure how often each backend is *traced into* a compilation, not
    per-device-call frequency — exactly the question "which backend did
    my run actually compile?" that the obs layer answers.
    """

    def __init__(self, name: str, env: str, registry: Registry):
        self.name = name
        self.env = env
        self.registry = registry
        self.value = os.environ.get(env, "auto")

    def set(self, name: str) -> None:
        if name != "auto" and name not in self.registry:
            raise ValueError(
                f"unknown {self.env} backend {name!r}; known: "
                f"{['auto'] + self.registry.names()}")
        self.value = name

    def resolve(self) -> str:
        if self.value == "auto":
            return "pallas" if _on_tpu() else "jnp"
        return self.value

    def impl(self):
        resolved = self.resolve()
        obs.counter(f"kernels.{self.name}.{resolved}")
        return self.registry[resolved]


_norm_switch = _BackendSwitch("norm", "REPRO_NORM_BACKEND", NORM_BACKENDS)
_scale_switch = _BackendSwitch("scale", "REPRO_SCALE_BACKEND",
                               SCALE_BACKENDS)
_paged_attn_switch = _BackendSwitch("paged_attn",
                                    "REPRO_PAGED_ATTN_BACKEND",
                                    PAGED_ATTN_BACKENDS)
_cgc_switch = _BackendSwitch("cgc", "REPRO_CGC_BACKEND", CGC_BACKENDS)
_codec_switch = _BackendSwitch("codec_pack", "REPRO_CODEC_BACKEND",
                               CODEC_PACK_BACKENDS)


def set_norm_backend(name: str) -> None:
    """Select the sq-norm backend: "auto" | "jnp" | "pallas"."""
    _norm_switch.set(name)


def norm_backend() -> str:
    return _norm_switch.resolve()


def set_scale_backend(name: str) -> None:
    """Select the row-scaling backend (server-side CGC filter pass 2)."""
    _scale_switch.set(name)


def scale_backend() -> str:
    return _scale_switch.resolve()


def set_paged_attn_backend(name: str) -> None:
    """Select the paged decode-attention backend (repro.serve hot path)."""
    _paged_attn_switch.set(name)


def paged_attn_backend() -> str:
    return _paged_attn_switch.resolve()


def set_cgc_backend(name: str) -> None:
    """Select the fused CGC aggregation backend (server-side round)."""
    _cgc_switch.set(name)


def cgc_backend() -> str:
    return _cgc_switch.resolve()


def set_codec_pack_backend(name: str) -> None:
    """Select the wire-codec pack/unpack backend (comm/wire.py)."""
    _codec_switch.set(name)


def codec_pack_backend() -> str:
    return _codec_switch.resolve()


@NORM_BACKENDS.register("jnp")
def _tree_sq_norm_jnp(leaves) -> jax.Array:
    return sum(jnp.sum(jnp.square(g.astype(F32))) for g in leaves)


_MAX_SQ_COLS = 1 << 15     # widest row a sq_sum tile takes whole
_SQ_FOLD_COLS = 1024       # row width a wider or 1-D leaf is folded to


def _leaf_rows(g: jax.Array) -> jax.Array:
    """A gradient leaf as a 2-D (rows, cols) view for ``sq_sum``.

    Leading dims merge into the rows, which leaves the TPU's tiled
    layout of the last two dims, and so the leaf's bytes, in place.
    Only a leaf whose last dim is too wide for a VMEM row tile is
    flattened and folded, zero-padded to whole rows."""
    if g.ndim >= 2 and g.shape[-1] <= _MAX_SQ_COLS:
        return g.reshape(-1, g.shape[-1])
    flat = g.reshape(-1)
    if flat.shape[0] <= _MAX_SQ_COLS:
        return flat[None, :]
    return _pad_to(flat, _SQ_FOLD_COLS, 0).reshape(-1, _SQ_FOLD_COLS)


@NORM_BACKENDS.register("pallas")
def _tree_sq_norm_pallas(leaves) -> jax.Array:
    interpret = not _on_tpu()
    return sum(jnp.sum(_cgc.sq_sum(_leaf_rows(g), interpret=interpret))
               for g in leaves)


def tree_sq_norm(tree) -> jax.Array:
    """fp32 sum of squares over every leaf of ``tree`` (or leaf list).

    The "pallas" backend streams each leaf through ``cgc_clip.sq_sum``
    in its own row layout, one kernel per leaf: the peak memory of the
    norm is the gradient's own bytes, with no concatenated or padded
    copy of the tree. Safe inside shard_map (interpret mode off-TPU).
    """
    leaves = jax.tree.leaves(tree)
    if not leaves:
        return jnp.zeros((), F32)
    return _norm_switch.impl()(leaves)


def _pad_to(x: jax.Array, mult: int, axis: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _block_for(d: int, block_d: int) -> int:
    """The d-tile for a row of length d: ``block_d`` once rows are long
    enough, else the next power of two (>= 128, so tiles stay
    lane-aligned — ``max(128, d)`` would hand pallas an unaligned tile
    for d like 1000)."""
    if d >= block_d:
        return block_d
    return min(block_d, max(128, 1 << (d - 1).bit_length()))


def pad_rows(G: jax.Array, block_d: int) -> jax.Array:
    """Pad an (n, d) stack to kernel shape: n -> multiple of 8 sublanes,
    d -> multiple of ``block_d``. The one padding path every row-stack
    kernel wrapper shares; a no-op (same array, no copy) when the caller
    already holds a padded table."""
    return _pad_to(_pad_to(G, 8, 0), block_d, 1)


@functools.partial(jax.jit, static_argnames=("f", "block_d", "interpret"))
def cgc_clip(G: jax.Array, f: int, block_d: int = 2048,
             interpret: bool | None = None) -> jax.Array:
    """Fused CGC filter (Eq. 8) on an (n, d) gradient stack."""
    if interpret is None:
        interpret = not _on_tpu()
    n, d = G.shape
    bd = _block_for(d, block_d)
    Gp = pad_rows(G, bd)
    sq = _cgc.row_sq_norms(Gp, bd, interpret)[:n]
    norms = jnp.sqrt(sq)
    thr = jnp.sort(norms)[n - f - 1]
    scale = jnp.minimum(1.0, thr / jnp.maximum(norms, 1e-12))
    scale_p = jnp.pad(scale, (0, Gp.shape[0] - n))
    out = _cgc.scale_rows(Gp, scale_p, bd, interpret)
    return out[:n, :d]


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def cgc_norms(G: jax.Array, block_d: int = 2048,
              interpret: bool | None = None) -> jax.Array:
    if interpret is None:
        interpret = not _on_tpu()
    n, d = G.shape
    bd = _block_for(d, block_d)
    Gp = pad_rows(G, bd)
    return jnp.sqrt(_cgc.row_sq_norms(Gp, bd, interpret)[:n])


@functools.partial(jax.jit,
                   static_argnames=("ridge", "block_d", "interpret"))
def echo_project(A: jax.Array, mask: jax.Array, g: jax.Array,
                 ridge: float = 1e-8, block_d: int = 1024,
                 interpret: bool | None = None):
    """Kernel-accelerated projection of g onto span(A[mask]).

    Same contract as repro.core.echo.project_onto_span: returns (x, echo).
    """
    if interpret is None:
        interpret = not _on_tpu()
    n, d = A.shape
    bd = _block_for(d, block_d)
    Am = A * mask[:, None]
    Ap = pad_rows(Am, bd)
    gp = _pad_to(g[None], bd, 1)[0]
    gram, b = _gram.gram_and_proj(Ap, gp, bd, interpret)
    gram, b = gram[:n, :n], b[:n]
    diag_scale = jnp.maximum(jnp.max(jnp.abs(jnp.diag(gram))), 1.0)
    off = (~mask).astype(F32)
    gram = gram + jnp.diag(off * diag_scale + ridge * diag_scale)
    x = jnp.linalg.solve(gram, b) * mask
    echo = x @ Am
    return x, echo


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     mask: jax.Array, block_t: int = 512,
                     interpret: bool | None = None) -> jax.Array:
    """Flash-decode GQA (see decode_attention.py); ref.decode_attention_ref
    is the oracle."""
    if interpret is None:
        interpret = not _on_tpu()
    B, T, K, hd = k.shape
    bt = min(block_t, T)
    if T % bt:
        k = _pad_to(k, bt, 1)
        v = _pad_to(v, bt, 1)
        mask = _pad_to(mask, bt, 1)
    return _dec.decode_attention(q, k, v, mask, bt, interpret)


@SCALE_BACKENDS.register("jnp")
def _scale_rows_jnp(G: jax.Array, scale: jax.Array,
                    block_d: int) -> jax.Array:
    return (G.astype(F32) * scale.astype(F32)[:, None]).astype(G.dtype)


@SCALE_BACKENDS.register("pallas")
def _scale_rows_pallas(G: jax.Array, scale: jax.Array,
                       block_d: int) -> jax.Array:
    n, d = G.shape
    bd = _block_for(d, block_d)
    Gp = pad_rows(G, bd)
    scale_p = jnp.pad(scale.astype(F32), (0, Gp.shape[0] - n))
    return _cgc.scale_rows(Gp, scale_p, bd, not _on_tpu())[:n, :d]


def scale_rows(G: jax.Array, scale: jax.Array,
               block_d: int = 2048) -> jax.Array:
    """Row-broadcast multiply of an (n, d) stack — pass 2 of the CGC
    filter. Dispatches via the scale backend switch: the Pallas
    ``cgc_clip.scale_rows`` streaming pass on TPU, plain jnp elsewhere
    (``REPRO_SCALE_BACKEND`` / ``set_scale_backend`` override).
    """
    return _scale_switch.impl()(G, scale, block_d)


# ---------------------------------------------------------------------------
# Fused CGC aggregation (the whole server-side round in one dispatch)
# ---------------------------------------------------------------------------


@CGC_BACKENDS.register("jnp")
def _cgc_fused_jnp(G: jax.Array, f: int, block_d: int):
    """Reference backend: bitwise-identical to
    ``sum(core.cgc.cgc_filter(G, f))`` under the jnp scale backend (same
    norm, threshold, scale, cast and reduction order)."""
    from repro.core.cgc import cgc_scales
    norms = jnp.linalg.norm(G, axis=-1)
    scales = cgc_scales(norms, f)
    scaled = (G.astype(F32) * scales.astype(F32)[:, None]).astype(G.dtype)
    scaled = scaled.astype(jnp.result_type(G.dtype, scales.dtype))
    return jnp.sum(scaled, axis=0), norms, scales


@CGC_BACKENDS.register("pallas")
def _cgc_fused_pallas(G: jax.Array, f: int, block_d: int):
    n, d = G.shape
    bd = _block_for(d, block_d)
    Gp = pad_rows(G, bd)
    agg, sq, scale = _cgc.cgc_fused_aggregate(Gp, f, n, bd, not _on_tpu())
    out_dtype = jnp.result_type(G.dtype, F32)
    return (agg[0, :d].astype(out_dtype), jnp.sqrt(sq[:n, 0]),
            scale[:n, 0])


def cgc_fused_aggregate(G: jax.Array, f: int, block_d: int = 2048):
    """One-dispatch CGC round on an (n, d) stack: returns
    ``(aggregate (d,), norms (n,), scales (n,))``.

    Replaces the norms -> host-side sort -> ``scale_rows`` -> sum chain
    of ``core.cgc``: the "pallas" backend streams the table through
    ``cgc_clip.cgc_fused_aggregate`` (threshold derived in-kernel, no
    device->host sync, no (n, d) intermediate); the "jnp" backend is the
    bitwise reference chain (``REPRO_CGC_BACKEND`` / ``set_cgc_backend``
    override). ``f`` must be a static python int.
    """
    n = G.shape[0]
    if not 0 <= f < n:
        raise ValueError(f"need 0 <= f < n, got f={f}, n={n}")
    return _cgc_switch.impl()(G, f, block_d)


# ---------------------------------------------------------------------------
# Wire-codec pack/unpack (comm/wire.py quantized broadcasts)
# ---------------------------------------------------------------------------


def _codec_layout(m: int, block_c: int):
    """Tile layout for a length-m vector: columns of the (ROWS, cols)
    reshape plus the lane tile, cols a multiple of the tile."""
    need = -(-m // _pack.ROWS)
    bc = _block_for(need, block_c)
    return -(-need // bc) * bc, bc


def _as_tiles(v: jax.Array, cols: int) -> jax.Array:
    v = v.astype(F32).reshape(-1)
    return jnp.pad(v, (0, _pack.ROWS * cols - v.shape[0])).reshape(
        _pack.ROWS, cols)


class _JnpCodecPack:
    """Bitwise replica of the inline comm/wire.py codec math."""

    @staticmethod
    def int8_pack(v, block_c):
        v = v.astype(F32)
        scale = _pack.int8_scale(jnp.max(jnp.abs(v)))
        q = jnp.clip(jnp.round(v / scale), -127, 127).astype(jnp.int8)
        return q, scale.astype(F32)

    @staticmethod
    def int8_unpack(q, scale, m, block_c):
        return q.astype(F32) * scale

    @staticmethod
    def topk_pack(v, k, block_c):
        v = v.astype(F32)
        kk = min(k, v.shape[-1])
        _, idx = jax.lax.top_k(jnp.abs(v), kk)
        return v[idx], idx.astype(jnp.int32)

    @staticmethod
    def topk_unpack(vals, idx, m, block_c):
        return jnp.zeros((m,), F32).at[idx].set(vals)


class _PallasCodecPack:
    """Streaming codec_pack.py kernels over the (ROWS, cols) tiling."""

    @staticmethod
    def int8_pack(v, block_c):
        m = v.shape[-1]
        cols, bc = _codec_layout(m, block_c)
        q, scale = _pack.int8_pack(_as_tiles(v, cols), bc, not _on_tpu())
        return q.reshape(-1)[:m], scale[0, 0]

    @staticmethod
    def int8_unpack(q, scale, m, block_c):
        cols, bc = _codec_layout(m, block_c)
        qt = jnp.pad(q.reshape(-1), (0, _pack.ROWS * cols - m)).reshape(
            _pack.ROWS, cols)
        return _pack.int8_unpack(qt, scale, bc, not _on_tpu()
                                 ).reshape(-1)[:m]

    @staticmethod
    def topk_pack(v, k, block_c):
        m = v.shape[-1]
        kk = min(k, m)
        cols, bc = _codec_layout(m, block_c)
        while _pack.ROWS * bc < kk:      # every tile must hold >= kk
            bc *= 2
            cols = -(-cols // bc) * bc
        vals_c, idx_c = _pack.topk_pack_candidates(
            _as_tiles(v, cols), kk, bc, not _on_tpu())
        flat_v, flat_i = vals_c.reshape(-1), idx_c.reshape(-1)
        # exact global top-k over the tiny candidate table, with
        # lax.top_k's tie order (descending |v|, then ascending index);
        # tile pad slots (idx -1) and v's zero padding (idx >= m) lose
        valid = (flat_i >= 0) & (flat_i < m)
        key = jnp.where(valid, jnp.abs(flat_v), -1.0)
        rank = jnp.where(valid, flat_i, jnp.iinfo(jnp.int32).max)
        sel = jnp.lexsort((rank, -key))[:kk]
        return flat_v[sel], flat_i[sel].astype(jnp.int32)

    @staticmethod
    def topk_unpack(vals, idx, m, block_c):
        cols, bc = _codec_layout(m, block_c)
        return _pack.topk_unpack(vals, idx, cols, bc, not _on_tpu()
                                 ).reshape(-1)[:m]


CODEC_PACK_BACKENDS.add("jnp", _JnpCodecPack)
CODEC_PACK_BACKENDS.add("pallas", _PallasCodecPack)


def int8_pack(v: jax.Array, block_c: int = _pack.DEFAULT_BLOCK_C):
    """(m,) float -> ((m,) int8, () fp32 absmax scale). The Int8Codec
    encode path; dispatches via ``REPRO_CODEC_BACKEND``."""
    return _codec_switch.impl().int8_pack(v, block_c)


def int8_unpack(q: jax.Array, scale: jax.Array, m: int,
                block_c: int = _pack.DEFAULT_BLOCK_C) -> jax.Array:
    """((m,) int8, scale) -> (m,) fp32 dequantized."""
    return _codec_switch.impl().int8_unpack(q, scale, m, block_c)


def topk_pack(v: jax.Array, k: int,
              block_c: int = _pack.DEFAULT_BLOCK_C):
    """(m,) float -> ((kk,) values, (kk,) int32 indices), kk=min(k, m),
    ordered exactly as ``lax.top_k`` over |v|."""
    return _codec_switch.impl().topk_pack(v, k, block_c)


def topk_unpack(vals: jax.Array, idx: jax.Array, m: int,
                block_c: int = _pack.DEFAULT_BLOCK_C) -> jax.Array:
    """Sparse (values, indices) -> (m,) dense fp32."""
    return _codec_switch.impl().topk_unpack(vals, idx, m, block_c)


def paged_decode_attention(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_table: jax.Array,
                           lengths: jax.Array,
                           interpret: bool | None = None) -> jax.Array:
    """Paged flash-decode GQA over a block-table-indexed page pool.

    q (B,H,hd); k_pages/v_pages (P,ps,K,hd); block_table (B,NB) int32
    page ids; lengths (B,) valid tokens per sequence (0 -> zeros).
    Dispatches via the paged-attn backend switch: the Pallas kernel
    (scalar-prefetch block-table gather, decode_attention.py) on TPU,
    the gather-then-attend oracle ``ref.paged_decode_attention_ref``
    elsewhere (``REPRO_PAGED_ATTN_BACKEND`` / ``set_paged_attn_backend``
    override) — the jnp path is bitwise the contiguous reference on the
    gathered view.
    """
    return _paged_attn_switch.impl()(q, k_pages, v_pages, block_table,
                                     lengths, interpret)


@PAGED_ATTN_BACKENDS.register("jnp")
def _paged_attn_jnp(q, k_pages, v_pages, block_table, lengths,
                    interpret=None):
    from repro.kernels import ref as _ref
    return _ref.paged_decode_attention_ref(q, k_pages, v_pages,
                                           block_table, lengths)


@PAGED_ATTN_BACKENDS.register("pallas")
def _paged_attn_pallas(q, k_pages, v_pages, block_table, lengths,
                       interpret=None):
    if interpret is None:
        interpret = not _on_tpu()
    return _dec.paged_decode_attention(q, k_pages, v_pages, block_table,
                                       lengths, interpret)
