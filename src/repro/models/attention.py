"""Attention variants: GQA (+qk-norm, bias, sliding window, M-RoPE) and MLA.

Memory discipline (these run at 32k prefill / 104B-scale in the dry-run):
  * train/prefill attention is **query-chunked**: a lax.scan over query blocks
    so the live score buffer is (B, H, qc, T) instead of (B, H, S, T);
  * decode uses explicit KV caches; MLA decodes in the **absorbed** latent
    form (cache = compressed c_kv + rope key, never materialising per-head
    K/V — the whole point of MLA);
  * sliding-window decode keeps a ring-buffer cache of `window` slots.

All einsums accumulate in fp32 (`preferred_element_type`) and cast back.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import nn
from repro.models.rope import apply_mrope, apply_rope, rope_spec, yarn_mscale
from repro.obs.scopes import MLA_ATTENTION

F32 = jnp.float32
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Masking helpers
# ---------------------------------------------------------------------------


def _band_mask(q_pos: jax.Array, k_pos: jax.Array, causal: bool,
               window: Optional[int]) -> jax.Array:
    """(..., Sq, Sk) boolean mask: True = attend."""
    diff = q_pos[..., :, None] - k_pos[..., None, :]
    m = jnp.ones(diff.shape, bool)
    if causal:
        m &= diff >= 0
    if window is not None:
        m &= diff < window
    return m


def _softmax_attend(q: jax.Array, k: jax.Array, v: jax.Array,
                    mask: jax.Array, scale: float) -> jax.Array:
    """q (B,qc,K,G,hd), k (B,T,K,hd), v (B,T,K,hd), mask (B?,qc,T)."""
    scores = jnp.einsum("bqkgh,btkh->bkgqt", q, k,
                        preferred_element_type=F32) * scale
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqt,btkh->bqkgh", probs, v,
                     preferred_element_type=F32)
    return out.astype(v.dtype)


def chunked_gqa(q: jax.Array, k: jax.Array, v: jax.Array,
                q_pos: jax.Array, k_pos: jax.Array, *, causal: bool,
                window: Optional[int], q_chunk: int = 512,
                scale: Optional[float] = None, remat: bool = False
                ) -> jax.Array:
    """Query-chunked GQA core.

    q: (B, S, H, hd); k/v: (B, T, K, hd) with H = K*G; positions (B, S)/(B, T).
    Scans over query chunks so peak score memory is (B, K, G, qc, T).
    ``scale`` multiplies the scores (default ``hd ** -0.5``). With
    ``remat`` each chunk's scores are recomputed in the backward pass
    instead of saved for every chunk, which would hold the whole
    (B, K, G, S, T) again.
    """
    B, S, H, hd = q.shape
    K = k.shape[2]
    G = H // K
    vd = v.shape[-1]                    # may differ from hd (MLA)
    if scale is None:
        scale = hd ** -0.5
    qg = q.reshape(B, S, K, G, hd)

    if S <= q_chunk or S % q_chunk != 0:
        mask = _band_mask(q_pos, k_pos, causal, window)
        out = _softmax_attend(qg, k, v, mask, scale)
        return out.reshape(B, S, H, vd)

    n_chunks = S // q_chunk
    qs = qg.reshape(B, n_chunks, q_chunk, K, G, hd)
    qp = q_pos.reshape(B, n_chunks, q_chunk)

    def body(_, xs):
        qc, qpc = xs                       # (B, qc, K, G, hd), (B, qc)
        mask = _band_mask(qpc, k_pos, causal, window)
        return None, _softmax_attend(qc, k, v, mask, scale)

    _, outs = jax.lax.scan(jax.checkpoint(body) if remat else body, None,
                           (jnp.moveaxis(qs, 1, 0), jnp.moveaxis(qp, 1, 0)))
    out = jnp.moveaxis(outs, 0, 1).reshape(B, S, K, G, vd)
    return out.reshape(B, S, H, vd)


# ---------------------------------------------------------------------------
# GQA layer
# ---------------------------------------------------------------------------


def gqa_params(cfg: ModelConfig, kg: nn.KeyGen, pdtype) -> Dict[str, Any]:
    D, H, K = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    p = {
        "wq": nn.param(kg(), (D, H, hd), ("embed", "heads", None), pdtype),
        "wk": nn.param(kg(), (D, K, hd), ("embed", "kv_heads", None), pdtype),
        "wv": nn.param(kg(), (D, K, hd), ("embed", "kv_heads", None), pdtype),
        "wo": nn.param(kg(), (H, hd, D), ("heads", None, "embed"), pdtype),
    }
    if cfg.use_bias:
        p["bq"] = nn.param(kg(), (H, hd), ("heads", None), pdtype, zero=True)
        p["bk"] = nn.param(kg(), (K, hd), ("kv_heads", None), pdtype,
                           zero=True)
        p["bv"] = nn.param(kg(), (K, hd), ("kv_heads", None), pdtype,
                           zero=True)
    if cfg.qk_norm:
        p["q_norm"] = nn.param(kg(), (hd,), (None,), pdtype, zero=True)
        p["k_norm"] = nn.param(kg(), (hd,), (None,), pdtype, zero=True)
    return p


def _project_qkv(p, cfg: ModelConfig, x: jax.Array):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if cfg.use_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if cfg.qk_norm:
        q = nn.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = nn.rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def gqa_forward(p, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
                mrope_pos: Optional[jax.Array] = None,
                q_chunk: int = 512) -> jax.Array:
    """Full-sequence (train / prefill) GQA attention."""
    q, k, v = _project_qkv(p, cfg, x)
    if cfg.mrope and mrope_pos is not None:
        q = apply_mrope(q, mrope_pos, cfg.mrope_sections, cfg.rope_theta)
        k = apply_mrope(k, mrope_pos, cfg.mrope_sections, cfg.rope_theta)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    out = chunked_gqa(q, k, v, positions, positions, causal=cfg.causal,
                      window=cfg.sliding_window, q_chunk=q_chunk)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


def gqa_init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype
                   ) -> Dict[str, jax.Array]:
    """KV cache. With a sliding window, the cache is a ring buffer of
    ``window`` slots; otherwise ``max_len`` slots."""
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    T = min(cfg.sliding_window, max_len) if cfg.sliding_window else max_len
    return {
        "k": jnp.zeros((batch, T, K, hd), dtype),
        "v": jnp.zeros((batch, T, K, hd), dtype),
        # absolute position stored per slot; -1 = empty
        "slot_pos": jnp.full((batch, T), -1, jnp.int32),
    }


def gqa_decode(p, cfg: ModelConfig, x: jax.Array, pos: jax.Array,
               cache: Dict[str, jax.Array],
               mrope_pos: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode. x: (B, 1, D); pos: (B,) absolute position."""
    B = x.shape[0]
    q, k_new, v_new = _project_qkv(p, cfg, x)
    pos_b1 = pos[:, None]
    if cfg.mrope and mrope_pos is not None:
        q = apply_mrope(q, mrope_pos, cfg.mrope_sections, cfg.rope_theta)
        k_new = apply_mrope(k_new, mrope_pos, cfg.mrope_sections,
                            cfg.rope_theta)
    else:
        q = apply_rope(q, pos_b1, cfg.rope_theta)
        k_new = apply_rope(k_new, pos_b1, cfg.rope_theta)

    T = cache["k"].shape[1]
    slot = jnp.mod(pos, T) if cfg.sliding_window else jnp.minimum(pos, T - 1)
    bidx = jnp.arange(B)
    k = cache["k"].at[bidx, slot].set(k_new[:, 0])
    v = cache["v"].at[bidx, slot].set(v_new[:, 0])
    slot_pos = cache["slot_pos"].at[bidx, slot].set(pos)

    k_pos = slot_pos                       # (B, T); -1 slots masked below
    mask = (k_pos >= 0) & (k_pos <= pos[:, None])
    if cfg.sliding_window:
        mask &= (pos[:, None] - k_pos) < cfg.sliding_window
    K = k.shape[2]
    H = cfg.num_heads
    G = H // K
    hd = cfg.resolved_head_dim
    qg = q.reshape(B, 1, K, G, hd)
    scores = jnp.einsum("bqkgh,btkh->bkgqt", qg, k,
                        preferred_element_type=F32) * hd ** -0.5
    scores = jnp.where(mask[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgqt,btkh->bqkgh", probs, v,
                     preferred_element_type=F32).astype(x.dtype)
    out = out.reshape(B, 1, H, hd)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return y, {"k": k, "v": v, "slot_pos": slot_pos}


# ---------------------------------------------------------------------------
# Paged GQA decode (repro.serve): block-table-indexed page pool
# ---------------------------------------------------------------------------


def gqa_paged_init_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                         dtype) -> Dict[str, jax.Array]:
    """Paged KV cache: a pool of fixed-size pages shared by all sequences.

    Logical position t of a sequence with block table ``bt`` lives at page
    ``bt[t // page_size]``, slot ``t % page_size``. Page 0 is reserved as
    the scratch page (inactive/padded writes land there, never attended);
    ``repro.serve.kv_cache`` owns the free-list allocation of the rest.
    """
    K, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "kp": jnp.zeros((num_pages, page_size, K, hd), dtype),
        "vp": jnp.zeros((num_pages, page_size, K, hd), dtype),
    }


def gqa_paged_decode(p, cfg: ModelConfig, x: jax.Array, pos: jax.Array,
                     cache: Dict[str, jax.Array], block_table: jax.Array,
                     mrope_pos: Optional[jax.Array] = None
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One-token decode against the paged cache.

    x: (B, 1, D); pos: (B,) absolute position, -1 = inactive lane (its
    write is directed to the scratch page and its output is zero);
    block_table: (B, NB) page ids. Attention runs through
    ``kernels.ops.paged_decode_attention`` (Pallas on TPU, gather-ref
    elsewhere).
    """
    from repro.kernels import ops as _kops
    B = x.shape[0]
    q, k_new, v_new = _project_qkv(p, cfg, x)
    pos_b1 = pos[:, None]
    if cfg.mrope and mrope_pos is not None:
        q = apply_mrope(q, mrope_pos, cfg.mrope_sections, cfg.rope_theta)
        k_new = apply_mrope(k_new, mrope_pos, cfg.mrope_sections,
                            cfg.rope_theta)
    else:
        q = apply_rope(q, pos_b1, cfg.rope_theta)
        k_new = apply_rope(k_new, pos_b1, cfg.rope_theta)

    ps = cache["kp"].shape[1]
    active = pos >= 0
    blk = jnp.where(active, pos, 0) // ps
    page = jnp.take_along_axis(block_table, blk[:, None], axis=1)[:, 0]
    page = jnp.where(active, page, 0)           # scratch page for idle lanes
    slot = jnp.where(active, pos % ps, 0)
    kp = cache["kp"].at[page, slot].set(k_new[:, 0])
    vp = cache["vp"].at[page, slot].set(v_new[:, 0])

    lengths = jnp.where(active, pos + 1, 0)
    out = _kops.paged_decode_attention(q[:, 0], kp, vp, block_table,
                                       lengths)          # (B, H, hd)
    y = jnp.einsum("bshk,hkd->bsd", out[:, None].astype(x.dtype),
                   p["wo"].astype(x.dtype))
    return y, {"kp": kp, "vp": vp}


# ---------------------------------------------------------------------------
# MLA (Multi-head Latent Attention, DeepSeek-V2 / MiniCPM3)
# ---------------------------------------------------------------------------


def mla_params(cfg: ModelConfig, kg: nn.KeyGen, pdtype) -> Dict[str, Any]:
    D, H = cfg.d_model, cfg.num_heads
    nd, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    lora, qlora = cfg.kv_lora_rank, cfg.q_lora_rank
    p: Dict[str, Any] = {}
    if qlora:
        p["wq_a"] = nn.param(kg(), (D, qlora), ("embed", None), pdtype)
        p["q_norm"] = nn.param(kg(), (qlora,), (None,), pdtype, zero=True)
        p["wq_b"] = nn.param(kg(), (qlora, H, nd + rd),
                             (None, "heads", None), pdtype)
    else:
        p["wq"] = nn.param(kg(), (D, H, nd + rd), ("embed", "heads", None),
                           pdtype)
    p["wkv_a"] = nn.param(kg(), (D, lora + rd), ("embed", None), pdtype)
    p["kv_norm"] = nn.param(kg(), (lora,), (None,), pdtype, zero=True)
    p["wk_b"] = nn.param(kg(), (lora, H, nd), (None, "heads", None), pdtype)
    p["wv_b"] = nn.param(kg(), (lora, H, vd), (None, "heads", None), pdtype)
    p["wo"] = nn.param(kg(), (H, vd, D), ("heads", None, "embed"), pdtype)
    return p


def _mla_q(p, cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.q_lora_rank:
        cq = nn.dense(x, p["wq_a"].astype(x.dtype))
        cq = nn.rms_norm(cq, p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsq,qhk->bshk", cq, p["wq_b"].astype(x.dtype))
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    return q


def mla_scale(cfg: ModelConfig) -> Optional[float]:
    """MLA's softmax scale under YaRN, (nd + rd)^-0.5 times the squared
    ``mscale_all_dim`` factor; None (chunked_gqa's default) without it."""
    if not (cfg.yarn_factor and cfg.yarn_mscale_all_dim):
        return None
    m = yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def _mla_rope(cfg: ModelConfig, x: jax.Array, positions: jax.Array):
    inv, mscale = rope_spec(cfg, cfg.qk_rope_head_dim)
    return apply_rope(x, positions, cfg.rope_theta, inv, mscale)


def mla_forward(p, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
                q_chunk: int = 512) -> jax.Array:
    """Train/prefill MLA: materialise per-head K/V from the latent."""
    B, S, D = x.shape
    H = cfg.num_heads
    nd, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    lora = cfg.kv_lora_rank

    with jax.named_scope(MLA_ATTENTION):
        q = _mla_q(p, cfg, x)                          # (B,S,H,nd+rd)
        q_nope, q_rope = q[..., :nd], q[..., nd:]
        q_rope = _mla_rope(cfg, q_rope, positions)

        ckv_full = nn.dense(x, p["wkv_a"].astype(x.dtype))  # (B,S,lora+rd)
        ckv, k_pe = ckv_full[..., :lora], ckv_full[..., lora:]
        ckv = nn.rms_norm(ckv, p["kv_norm"], cfg.norm_eps)
        k_pe = _mla_rope(cfg, k_pe[:, :, None, :], positions)  # (B,S,1,rd)

        k_nope = jnp.einsum("bsl,lhn->bshn", ckv, p["wk_b"].astype(x.dtype))
        v = jnp.einsum("bsl,lhv->bshv", ckv, p["wv_b"].astype(x.dtype))

        q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, (B, S, H, rd))], axis=-1)
        out = chunked_gqa(q_full, k_full, v, positions, positions,
                          causal=cfg.causal, window=cfg.sliding_window,
                          q_chunk=q_chunk, scale=mla_scale(cfg), remat=True)
        return jnp.einsum("bshv,hvd->bsd", out, p["wo"].astype(x.dtype))


def mla_init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype
                   ) -> Dict[str, jax.Array]:
    """Latent cache: compressed c_kv + rope key — the MLA memory win."""
    T = min(cfg.sliding_window, max_len) if cfg.sliding_window else max_len
    return {
        "ckv": jnp.zeros((batch, T, cfg.kv_lora_rank), dtype),
        "kpe": jnp.zeros((batch, T, cfg.qk_rope_head_dim), dtype),
        "slot_pos": jnp.full((batch, T), -1, jnp.int32),
    }


def mla_decode(p, cfg: ModelConfig, x: jax.Array, pos: jax.Array,
               cache: Dict[str, jax.Array]
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Absorbed-form single-token MLA decode against the latent cache."""
    B = x.shape[0]
    H = cfg.num_heads
    nd, rd, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    lora = cfg.kv_lora_rank
    scale = mla_scale(cfg) or (nd + rd) ** -0.5

    q = _mla_q(p, cfg, x)                               # (B,1,H,nd+rd)
    q_nope, q_rope = q[..., :nd], q[..., nd:]
    q_rope = _mla_rope(cfg, q_rope, pos[:, None])

    ckv_full = nn.dense(x, p["wkv_a"].astype(x.dtype))
    ckv_new = nn.rms_norm(ckv_full[..., :lora], p["kv_norm"], cfg.norm_eps)
    kpe_new = _mla_rope(cfg, ckv_full[:, :, None, lora:],
                        pos[:, None])[:, :, 0, :]       # (B,1,rd)

    T = cache["ckv"].shape[1]
    slot = jnp.mod(pos, T) if cfg.sliding_window else jnp.minimum(pos, T - 1)
    bidx = jnp.arange(B)
    ckv = cache["ckv"].at[bidx, slot].set(ckv_new[:, 0])
    kpe = cache["kpe"].at[bidx, slot].set(kpe_new[:, 0])
    slot_pos = cache["slot_pos"].at[bidx, slot].set(pos)

    # Absorb W_uk into the query: q_lat (B,1,H,lora).
    q_lat = jnp.einsum("bshn,lhn->bshl", q_nope, p["wk_b"].astype(x.dtype))
    scores = (jnp.einsum("bshl,btl->bhst", q_lat, ckv,
                         preferred_element_type=F32)
              + jnp.einsum("bshr,btr->bhst", q_rope, kpe,
                           preferred_element_type=F32)) * scale
    mask = (slot_pos >= 0) & (slot_pos <= pos[:, None])
    if cfg.sliding_window:
        mask &= (pos[:, None] - slot_pos) < cfg.sliding_window
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    o_lat = jnp.einsum("bhst,btl->bshl", probs, ckv,
                       preferred_element_type=F32).astype(x.dtype)
    out = jnp.einsum("bshl,lhv->bshv", o_lat, p["wv_b"].astype(x.dtype))
    y = jnp.einsum("bshv,hvd->bsd", out, p["wo"].astype(x.dtype))
    return y, {"ckv": ckv, "kpe": kpe, "slot_pos": slot_pos}
