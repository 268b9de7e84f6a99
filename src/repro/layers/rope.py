"""Rotary position embeddings (supports position offsets for decode).

``scale`` is linear position scaling (Hugging Face ``rope_scaling``
``{"type": "linear", "factor": scale}``): positions are divided by it
before they are rotated.  At 1 the positions enter as they are.
"""
from __future__ import annotations

import jax.numpy as jnp


def rope_freqs(head_dim: int, *, theta: float = 10000.0):
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)  # (head_dim // 2,)


def apply_rope(x, positions, *, theta: float = 10000.0, scale: float = 1.0):
    """x: (..., T, d) with d even; positions: (T,) or (..., T)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta=theta)
    pos = positions[..., :, None].astype(jnp.float32)
    if scale != 1.0:
        pos = pos / scale
    angles = pos * freqs  # (..., T, d/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    # Expand cos/sin to broadcast over any head dims between batch and T.
    while cos.ndim < x.ndim:
        cos, sin = cos[..., None, :, :], sin[..., None, :, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    out = jnp.stack([y1, y2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)
