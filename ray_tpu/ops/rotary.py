"""Rotary position embeddings (RoPE), half-rotation layout (LLaMA/GPT-NeoX)."""

from __future__ import annotations

import math
from typing import Any, Mapping, Optional, Tuple

import jax.numpy as jnp
import numpy as np


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


def apply_rotary_at(x, positions, theta: float = 10000.0,
                    rule: Optional[Mapping[str, Any]] = None,
                    sections: Optional[Tuple[int, ...]] = None):
    """Rotate q or k by angles computed from ``positions`` [..., seq] as
    they come — no table, so a model's context length costs nothing. x:
    [..., seq, heads, head_dim]; the same half-rotation layout. ``rule``:
    one entry of a source's ``rope_parameters`` (``rule_frequencies``), in
    place of ``theta``. ``sections`` (a source's ``mrope_section``): the
    frequency pairs in runs, each run turned by a position stream of its
    own — ``positions`` [streams, ..., seq], one stream a run (temporal,
    height, width); positions of x's own rank [..., seq] are every stream
    at once, and the rule is plain RoPE. A head of fewer pairs than the
    sections add up to (an index head) takes the runs in the same
    proportion (``stream_of_pairs``)."""
    d = x.shape[-1]
    if rule is not None:
        inv_freq, factor = rule_frequencies(d, rule)
    else:
        inv_freq, factor = 1.0 / (theta ** (
            jnp.arange(0, d, 2, dtype=jnp.float32) / d)), 1.0
    if sections is not None and positions.ndim == x.ndim - 1:
        # pair i's position is its run's stream's: [..., seq, pairs]
        by_pair = jnp.moveaxis(positions, 0, -1)[
            ..., stream_of_pairs(sections, d // 2)]
        angles = by_pair.astype(jnp.float32) * inv_freq
    else:
        angles = positions[..., None].astype(jnp.float32) * inv_freq
    if factor == 1.0:
        return _rotate(x, jnp.cos(angles), jnp.sin(angles))
    return _rotate(x, jnp.cos(angles) * factor, jnp.sin(angles) * factor)


def stream_of_pairs(sections, pairs: int) -> np.ndarray:
    """The position stream [pairs] int that turns each frequency pair under
    ``sections`` (runs of pairs, a stream each, that add up to a head's
    pairs): pair i of a head of ``pairs`` pairs stands where pair ``i *
    sum(sections) / pairs`` of the full head stands."""
    full = np.repeat(np.arange(len(sections)), sections)
    return full[np.arange(pairs) * len(full) // pairs]


ROPE_TYPES = ("default", "yarn")


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's magnitude scale ``0.1 mscale ln(factor) + 1`` (1 for a factor
    of at most 1): what cos and sin, or the scores, are multiplied by."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rule_frequencies(head_dim: int,
                     rule: Mapping[str, Any]) -> Tuple[np.ndarray, float]:
    """(inverse frequencies [head_dim // 2] float32, the factor cos and sin
    are multiplied by) of one RoPE rule, keyed as the public ``transformers``
    configs key it: ``rope_type`` 'default' (``rope_theta`` alone) or 'yarn'
    (Peng et al. 2023, arXiv:2309.00071): the frequencies that turn fewer
    than ``beta_slow`` times over ``original_max_position_embeddings`` are
    divided by ``factor``, those that turn more than ``beta_fast`` times are
    kept, with a linear ramp over the pair indices between (their bounds
    rounded outwards: ``truncate``, true unless the rule says otherwise), and
    cos and sin are scaled by ``attention_factor`` (``0.1 ln(factor) + 1``
    where the rule gives none)."""
    kind = rule.get("rope_type", "default")
    if kind not in ROPE_TYPES:
        raise ValueError(f"unknown rope_type {kind!r}; expected one of "
                         f"{list(ROPE_TYPES)}")
    theta = float(rule["rope_theta"])
    half = head_dim // 2
    base = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / head_dim)
    if kind == "default":
        return base.astype(np.float32), 1.0
    factor = float(rule["factor"])
    original = float(rule["original_max_position_embeddings"])

    def pair_index(turns: float) -> float:
        """The pair whose frequency turns ``turns`` times over the original
        context."""
        return (head_dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = pair_index(float(rule.get("beta_fast", 32)))
    high = pair_index(float(rule.get("beta_slow", 1)))
    if rule.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, head_dim - 1)
    ramp = np.clip((np.arange(half, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = (1.0 - ramp) * base + ramp * base / factor
    attention_factor = rule.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return inv_freq.astype(np.float32), float(attention_factor)
