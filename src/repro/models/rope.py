"""Rotary position embeddings: standard RoPE, YaRN and Qwen2-VL M-RoPE."""
from __future__ import annotations

import math
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def rope_freqs(head_dim: int, theta: float = 1e4) -> jax.Array:
    """Inverse frequencies for the rotary half-dim (head_dim must be even)."""
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))


def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature factor, 0.1 m ln(s) + 1 (1 for s <= 1)."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_freqs(dim: int, theta: float, factor: float, original_max_pos: int,
               beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN inverse frequencies (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``).

    Frequencies that turn fewer than ``beta_slow`` times over the original
    context are interpolated (divided by ``factor``); those that turn more
    than ``beta_fast`` times are kept; a linear ramp blends the band in
    between. A numpy constant of shape (dim // 2,).
    """
    half = dim // 2
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / factor

    def corr(rotations):
        return dim * math.log(original_max_pos / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(corr(beta_fast)), 0)
    high = min(math.ceil(corr(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(half) - low) / (high - low), 0.0, 1.0)
    return (inter * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rope_spec(cfg, dim: int) -> Tuple[Optional[np.ndarray], float]:
    """(inverse frequencies, cos/sin scale) of ``cfg``'s rotary embedding
    over ``dim`` columns: (None, 1.0) for plain RoPE."""
    if not cfg.yarn_factor:
        return None, 1.0
    inv = yarn_freqs(dim, cfg.rope_theta, cfg.yarn_factor,
                     cfg.yarn_original_max_pos, cfg.yarn_beta_fast,
                     cfg.yarn_beta_slow)
    return inv, (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
                 / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float = 1e4,
               inv_freq: Optional[np.ndarray] = None, mscale: float = 1.0
               ) -> jax.Array:
    """Standard RoPE. x: (..., S, H, hd); positions: broadcastable to (..., S).

    Uses the "rotate half" convention: pairs (x[..., :half], x[..., half:]).
    ``inv_freq`` replaces the plain frequencies (YaRN) and ``mscale``
    multiplies cos and sin.
    """
    hd = x.shape[-1]
    inv = rope_freqs(hd, theta) if inv_freq is None \
        else jnp.asarray(inv_freq)                    # (half,)
    angles = positions[..., None].astype(jnp.float32) * inv  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]               # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    if mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


def mrope_angles(positions: jax.Array, head_dim: int,
                 sections: Sequence[int], theta: float = 1e4
                 ) -> Tuple[jax.Array, jax.Array]:
    """M-RoPE (Qwen2-VL): 3-axis positions (t, h, w) -> (cos, sin).

    positions: (3, ..., S). ``sections`` splits the rotary half-dim into
    temporal/height/width bands (sums to head_dim // 2). Text tokens carry
    identical (t, h, w) so M-RoPE degenerates to standard RoPE there.
    """
    half = head_dim // 2
    assert sum(sections) == half, (sections, half)
    inv = rope_freqs(head_dim, theta)                 # (half,)
    # angles per axis: (3, ..., S, half)
    ang = positions[..., None].astype(jnp.float32) * inv
    # select which position axis drives each frequency band
    idx = jnp.repeat(jnp.arange(3), jnp.array(sections),
                     total_repeat_length=half)        # (half,)
    sel = jax.nn.one_hot(idx, 3, dtype=jnp.float32)   # (half, 3)
    ang = jnp.einsum("a...h,ha->...h", ang, sel)      # (..., S, half)
    return jnp.cos(ang), jnp.sin(ang)


def apply_mrope(x: jax.Array, positions: jax.Array,
                sections: Sequence[int], theta: float = 1e4) -> jax.Array:
    """Apply M-RoPE to x: (..., S, H, hd), positions: (3, ..., S)."""
    cos, sin = mrope_angles(positions, x.shape[-1], sections, theta)
    cos = cos[..., None, :]                           # (..., S, 1, half)
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)
