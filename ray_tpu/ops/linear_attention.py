"""Decayed linear attention (the Lightning-Attention family): a fixed
``[D, D]`` float32 state a head instead of a K/V cache.

    S_t = l_h * S_(t-1) + k_t^T v_t        o_t = q_t S_t / sqrt(D)

with one decay a head, ``l_h = exp(-s_h)``, ``s_h = 2^(-e (h + 1) / H)``
(``slopes``). Two kernels, one recurrence:

  * ``linear_attention_step`` — one token a row: the state is read and
    written once, and a row that is not ``active`` gets its state back
    bitwise (a decode step must not touch a slot that is free or mid-prefill).
  * ``linear_attention_chunk`` — ``S`` tokens in blocks of ``C``: inside a
    block ``(Q K^T * D) V`` with ``D_ij = l^(i-j)`` for ``j <= i``, across
    blocks ``(Q * l^(i+1)) S_in`` and ``S_out = l^C S_in + (K *
    l^(C-1-j))^T V``; the state rides in VMEM from block to block, so a chunk
    reads and writes it once whatever its length. ``real_len`` tokens are
    real and the rest trailing padding, which neither decays the state nor
    adds to it: the state that comes back is the state after ``real_len``
    tokens exactly.

Both are Pallas kernels under those names (what a profiler trace shows as
the op), interpreted off a TPU (``ops/_pallas.py``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops._pallas import should_interpret

_BLOCK = 128       # tokens of one block of the chunked scan
_STEP_HEADS = 8    # heads of one grid cell of the step kernel


def slopes(num_heads: int, exponent: float = 8.0):
    """``s_h = 2^(-exponent (h + 1) / H)``, float32 ``[H]``: the decay of
    head ``h`` is ``exp(-s_h)`` a token."""
    h = jnp.arange(1, num_heads + 1, dtype=jnp.float32)
    return jnp.exp2(-exponent * h / num_heads)


def _step_kernel(active_ref, decay_ref, q_ref, k_ref, v_ref, s_ref,
                 o_ref, so_ref, *, heads, scale):
    b, hg = pl.program_id(0), pl.program_id(1)
    live = active_ref[b] > 0
    for j in range(heads):
        s = s_ref[0, j]                                          # [D, D]
        new = decay_ref[hg * heads + j] * s + k_ref[0, j] * v_ref[0, j]
        o_ref[0, j] = jnp.sum(q_ref[0, j] * new, axis=0,
                              keepdims=True) * scale             # [1, D]
        so_ref[0, j] = jnp.where(live, new, s)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step(q, k, v, state, slope, active, interpret):
    B, H, D = q.shape
    heads = math.gcd(H, _STEP_HEADS)
    f32 = jnp.float32
    column = (1, heads, D, 1)   # q and k stand as columns: [D, 1] * [1, D]
    row = (1, heads, 1, D)
    cell = lambda b, h, *_: (b, h, 0, 0)
    o, new_state = pl.pallas_call(
        functools.partial(_step_kernel, heads=heads,
                          scale=1.0 / math.sqrt(D)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, H // heads),
            in_specs=[pl.BlockSpec(column, cell), pl.BlockSpec(column, cell),
                      pl.BlockSpec(row, cell),
                      pl.BlockSpec((1, heads, D, D), cell)],
            out_specs=[pl.BlockSpec(row, cell),
                       pl.BlockSpec((1, heads, D, D), cell)]),
        out_shape=[jax.ShapeDtypeStruct((B, H, 1, D), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="linear_attention_step", interpret=interpret,
    )(active.astype(jnp.int32), jnp.exp(-slope),
      q.astype(f32)[..., None], k.astype(f32)[..., None],
      v.astype(f32)[:, :, None], state)
    return o[:, :, 0], new_state


def linear_attention_step(q, k, v, state, slope, active):
    """One token a row. q, k, v: ``[B, H, D]``; state: ``[B, H, D, D]``
    float32; slope: ``[H]``; active: ``[B]`` (a row at 0 keeps its state
    bitwise; its output means nothing). Returns ``(o [B, H, D] float32,
    state)``."""
    return _step(q, k, v, state, slope, active, should_interpret())


def _chunk_kernel(len_ref, slope_ref, q_ref, k_ref, v_ref, s_in_ref,
                  o_ref, s_out_ref, s_scr, *, block, scale):
    h, c = pl.program_id(1), pl.program_id(2)
    f32 = jnp.float32

    @pl.when(c == 0)
    def _():
        s_scr[...] = s_in_ref[...]

    slope = slope_ref[h]
    n = jnp.clip(len_ref[0] - c * block, 0, block)  # real tokens in here
    i = lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    j = lax.broadcasted_iota(jnp.int32, (1, block), 1)
    # log of the decay from the block's start to behind token i: a padded
    # token (i >= n) decays nothing
    bi = -slope * jnp.minimum(i + 1, n).astype(f32)
    bj = -slope * jnp.minimum(j + 1, n).astype(f32)
    bn = -slope * n.astype(f32)
    q, k, v = q_ref[...], k_ref[...], v_ref[...]
    a = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=f32)              # [C, C]
    a = jnp.where(jnp.logical_and(j <= i, j < n), a * jnp.exp(bi - bj), 0.0)
    state = s_scr[...]
    o = jnp.dot(a.astype(v.dtype), v, preferred_element_type=f32)
    o += jnp.dot(q.astype(f32) * jnp.exp(bi), state,
                 preferred_element_type=f32)
    o_ref[...] = (o * scale).astype(o_ref.dtype)
    kd = k.astype(f32) * jnp.where(i < n, jnp.exp(bn - bi), 0.0)
    s_scr[...] = jnp.exp(bn) * state + lax.dot_general(
        kd, v.astype(f32), (((0,), (0,)), ((), ())),
        preferred_element_type=f32)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        s_out_ref[...] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _chunk(q, k, v, state, slope, real_len, interpret):
    B, S, H, D = q.shape
    block = min(_BLOCK, -(-S // 8) * 8)
    n_blocks = -(-S // block)
    pad = ((0, 0), (0, n_blocks * block - S), (0, 0), (0, 0))
    qt, kt, vt = (jnp.pad(x, pad).transpose(0, 2, 1, 3) for x in (q, k, v))
    tokens = pl.BlockSpec((None, None, block, D),
                          lambda b, h, c, *_: (b, h, c, 0))
    whole = pl.BlockSpec((None, None, D, D), lambda b, h, c, *_: (b, h, 0, 0))
    o, new_state = pl.pallas_call(
        functools.partial(_chunk_kernel, block=block,
                          scale=1.0 / math.sqrt(D)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, H, n_blocks),
            in_specs=[tokens, tokens, tokens, whole],
            out_specs=[tokens, whole],
            scratch_shapes=[pltpu.VMEM((D, D), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(qt.shape, q.dtype),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="linear_attention_chunk", interpret=interpret,
    )(jnp.reshape(real_len, (1,)).astype(jnp.int32), slope, qt, kt, vt,
      state)
    return o.transpose(0, 2, 1, 3)[:, :S], new_state


def linear_attention_chunk(q, k, v, state, slope, real_len):
    """``S`` tokens a row, the first ``real_len`` (a scalar) real. q, k, v:
    ``[B, S, H, D]``; state: ``[B, H, D, D]`` float32; slope: ``[H]``.
    Returns ``(o [B, S, H, D] in q's type, state after real_len tokens)``;
    the outputs of the padding mean nothing."""
    return _chunk(q, k, v, state, slope, real_len, should_interpret())
