"""Rotary position embeddings (RoPE), half-rotation layout (LLaMA/GPT-NeoX)."""

from __future__ import annotations

import jax.numpy as jnp


def rope_frequencies(head_dim: int, max_len: int, theta: float = 10000.0,
                     dtype=jnp.float32, position_offset: int = 0):
    """Precompute (cos, sin) tables of shape [max_len, head_dim//2]."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    pos = jnp.arange(position_offset, position_offset + max_len, dtype=jnp.float32)
    angles = jnp.outer(pos, inv_freq)
    return jnp.cos(angles).astype(dtype), jnp.sin(angles).astype(dtype)


def apply_rotary(x, cos, sin, positions=None):
    """Rotate q or k. x: [..., seq, heads, head_dim]; cos/sin: [max_len, hd//2]
    or already gathered [..., seq, hd//2] when `positions` is None and shapes
    match. `positions`: optional [..., seq] int32 gather indices (decode)."""
    if positions is not None:
        cos = cos[positions]
        sin = sin[positions]
    else:
        cos = cos[: x.shape[-3]]
        sin = sin[: x.shape[-3]]
    return _rotate(x, cos, sin)


def _rotate(x, cos, sin):
    """x [..., seq, heads, hd] by cos/sin [..., seq, hd//2], broadcast over
    heads; the first half of a head's values pairs with the second."""
    cos = jnp.expand_dims(cos, axis=-2)
    sin = jnp.expand_dims(sin, axis=-2)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_rotary_at(x, positions, theta: float = 10000.0):
    """Rotate q or k by angles computed from ``positions`` [..., seq] as
    they come — no table, so a model's context length costs nothing. x:
    [..., seq, heads, head_dim]; the same half-rotation layout."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    return _rotate(x, jnp.cos(angles), jnp.sin(angles))
