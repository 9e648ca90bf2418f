"""Pallas TPU flash attention, forward + backward kernels.

Layout [B, S, H, D] (seq-major, matches the models); kernels run head-major
[B, H, S, D]. GQA supported by mapping each query head to its kv head in the
BlockSpec index maps — kv heads are never materialized repeated in HBM.
Off-TPU the kernels run in interpreter mode so the same code path is
exercised by the CPU test mesh.

Head-batched blocking: each grid cell processes `block_h` heads at once via
batched `dot_general` (batch dim = head). With head_dim 64 and short
sequences, per-head grids leave the MXU idle on grid/pipeline overhead —
batching heads into one invocation cut the GPT-2s train-step attention time
~3x on v5e. `block_h` must be a multiple of the GQA group (each invocation
covers whole kv heads); kv blocks carry `block_h // group` kv heads.

Forward: online-softmax blockwise (FlashAttention-2 schedule), saving the
per-row logsumexp as residual. Matmul inputs stay in the model dtype
(bf16 on TPU) with f32 MXU accumulation — softmax math is f32.

Backward: two Pallas kernels sharing the recompute-from-(q,k,v,lse) trick:
  - dQ:    grid (B, H/bh, q_blocks, k_blocks), accumulates over k blocks.
  - dK/dV: grid (B, Hkv/bhk, k_blocks, q_blocks), head-batched with the
           GQA group summed in-kernel, so gradients land on the kv head
           without an HBM-repeated intermediate.
D = rowsum(dO * O) is computed in XLA (cheap elementwise) and fed in.

Reference parity surface: the reference delegates to torch SDPA inside
workers; this is the TPU-native equivalent of that compute path.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops._pallas import should_interpret

NEG_INF = -1e30
_LANES = 128

# VMEM budget the auto head-block targets (bytes). v5e has ~16 MiB of VMEM
# per core; the f32 score + prob blocks and double-buffered input windows
# multiply this several-fold, so the knob is deliberately conservative
# (measured: bh=12 @ 256x256 wants 19.9 MiB and is rejected by Mosaic).
_VMEM_TARGET = 3 * 1024 * 1024 + 512 * 1024


def _pick_block(seq: int, target: int) -> int:
    """Largest power-of-two divisor of seq that is <= target (>=1)."""
    b = 1
    while b * 2 <= target and seq % (b * 2) == 0:
        b *= 2
    return b


def _pick_block_h(num_heads: int, group: int, block_q: int, block_k: int,
                  requested: int | None) -> int:
    """Heads per grid cell: a multiple of `group` dividing num_heads, sized
    so the f32 score block (the dominant VMEM tenant) stays in budget."""
    if requested is not None:
        bh = max(group, (requested // group) * group)
    else:
        budget = max(1, _VMEM_TARGET // (block_q * block_k * 6))
        bh = max(group, (budget // group) * group)
    bh = min(bh, num_heads)
    while num_heads % bh or bh % group:
        bh -= group
    return max(bh, group)


def _causal_mask(qi, ki, bh, block_q, block_k):
    qpos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (bh, block_q, block_k), 1)
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (bh, block_q, block_k), 2)
    return qpos >= kpos


def _batched_qk(q, k):
    """[bh, bq, D] x [bh, bk, D] -> [bh, bq, bk] f32 (batch over heads)."""
    return jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)


def _expand_kv(kv, group):
    """[bhk, bk, D] -> [bhk*group, bk, D] (repeat per query head)."""
    if group == 1:
        return kv
    bhk, bk, d = kv.shape
    return jnp.broadcast_to(kv[:, None], (bhk, group, bk, d)).reshape(
        bhk * group, bk, d)


# ---------------------------------------------------------------- forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *,
                sm_scale, causal, block_q, block_k, num_kv, group):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Causal: skip blocks entirely in the future of this q block.
    should_run = (qi * block_q + block_q > ki * block_k) if causal else (ki >= 0)

    @pl.when(should_run)
    def _compute():
        q = q_ref[0]                              # [bh, bq, D]
        k = _expand_kv(k_ref[0], group)           # [bh, bk, D]
        v = _expand_kv(v_ref[0], group)
        bh = q.shape[0]
        s = _batched_qk(q, k) * sm_scale          # [bh, bq, bk] f32
        if causal:
            s = jnp.where(_causal_mask(qi, ki, bh, block_q, block_k),
                          s, NEG_INF)
        m_prev = m_scr[:, :, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scr[:, :, :1] + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == num_kv - 1)
    def _finalize():
        l = l_scr[:, :, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:, :, :1] + jnp.log(l_safe)


def _flash_fwd(q, k, v, sm_scale, causal, block_q, block_k, block_h,
               interpret):
    """Head-major [B,H,S,D] inputs -> (o, lse[B,H,Sq,1])."""
    batch, num_heads, seq_q, head_dim = q.shape
    _, num_kv_heads, seq_k, _ = k.shape
    group = num_heads // num_kv_heads

    block_q = _pick_block(seq_q, block_q)
    block_k = _pick_block(seq_k, block_k)
    bh = _pick_block_h(num_heads, group, block_q, block_k, block_h)
    bhk = bh // group
    grid = (batch, num_heads // bh, seq_q // block_q, seq_k // block_k)

    out, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, num_kv=seq_k // block_k,
            group=group),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bh, block_q, head_dim),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, bhk, block_k, head_dim),
                         lambda b, h, qi, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, bhk, block_k, head_dim),
                         lambda b, h, qi, ki: (b, h, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bh, block_q, head_dim),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            # lane-1 residual: [B, H, Sq, 1], the same layout the bwd
            # kernels consume — not 128-lane-broadcast (128x HBM waste)
            pl.BlockSpec((1, bh, block_q, 1),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((batch, num_heads, seq_q, 1),
                                 jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bh, block_q, _LANES), jnp.float32),
            pltpu.VMEM((bh, block_q, _LANES), jnp.float32),
            pltpu.VMEM((bh, block_q, head_dim), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        name="flash_attention_fwd",
        interpret=interpret,
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------- backward


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, sm_scale, causal, block_q, block_k, num_kv, group):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    should_run = (qi * block_q + block_q > ki * block_k) if causal else (ki >= 0)

    @pl.when(should_run)
    def _compute():
        q = q_ref[0]                              # [bh, bq, D]
        k = _expand_kv(k_ref[0], group)
        v = _expand_kv(v_ref[0], group)
        do = do_ref[0]
        lse = lse_ref[0]                          # [bh, bq, 1] f32
        delta = delta_ref[0]
        bh = q.shape[0]
        s = _batched_qk(q, k) * sm_scale
        if causal:
            s = jnp.where(_causal_mask(qi, ki, bh, block_q, block_k),
                          s, NEG_INF)
        p = jnp.exp(s - lse)         # masked entries underflow to 0
        dp = _batched_qk(do, v)
        ds = p * (dp - delta) * sm_scale
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

    @pl.when(ki == num_kv - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *,
                sm_scale, causal, block_q, block_k, num_q, group):
    ki = pl.program_id(2)
    qi = pl.program_id(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    should_run = (qi * block_q + block_q > ki * block_k) if causal else (qi >= 0)

    @pl.when(should_run)
    def _compute():
        q = q_ref[0]                              # [bh, bq, D]
        k = _expand_kv(k_ref[0], group)
        v = _expand_kv(v_ref[0], group)
        do = do_ref[0]
        lse = lse_ref[0]
        delta = delta_ref[0]
        bh = q.shape[0]
        bhk = bh // group
        s = _batched_qk(q, k) * sm_scale
        if causal:
            s = jnp.where(_causal_mask(qi, ki, bh, block_q, block_k),
                          s, NEG_INF)
        p = jnp.exp(s - lse)                      # [bh, bq, bk] f32
        # dV += P^T dO   (contract q rows, batch heads)
        dv_c = jax.lax.dot_general(
            p.astype(do.dtype), do, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)   # [bh, bk, D]
        dp = _batched_qk(do, v)
        ds = p * (dp - delta) * sm_scale
        dk_c = jax.lax.dot_general(
            ds.astype(q.dtype), q, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)   # [bh, bk, D]
        if group > 1:
            # GQA: sum query-head gradients into their kv head
            bk, d = dv_c.shape[1], dv_c.shape[2]
            dv_c = dv_c.reshape(bhk, group, bk, d).sum(axis=1)
            dk_c = dk_c.reshape(bhk, group, bk, d).sum(axis=1)
        dv_scr[:] = dv_scr[:] + dv_c
        dk_scr[:] = dk_scr[:] + dk_c

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, sm_scale, causal, block_q, block_k,
               block_h, interpret):
    """Head-major grads: q[B,H,Sq,D], k/v[B,Hkv,Sk,D] -> (dq, dk, dv)."""
    batch, num_heads, seq_q, head_dim = q.shape
    _, num_kv_heads, seq_k, _ = k.shape
    group = num_heads // num_kv_heads

    block_q = _pick_block(seq_q, block_q)
    block_k = _pick_block(seq_k, block_k)
    bh = _pick_block_h(num_heads, group, block_q, block_k, block_h)
    bhk = bh // group
    num_q = seq_q // block_q
    num_k = seq_k // block_k

    # D_i = rowsum(dO * O): cheap elementwise — XLA fuses it.
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)       # [B, H, Sq, 1]

    q_spec = pl.BlockSpec((1, bh, block_q, head_dim),
                          lambda b, h, qi, ki: (b, h, qi, 0))
    kv_spec = pl.BlockSpec((1, bhk, block_k, head_dim),
                           lambda b, h, qi, ki: (b, h, ki, 0))
    lse_spec = pl.BlockSpec((1, bh, block_q, 1),
                            lambda b, h, qi, ki: (b, h, qi, 0))
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, num_kv=num_k, group=group),
        grid=(batch, num_heads // bh, num_q, num_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, lse_spec, lse_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bh, block_q, head_dim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        name="flash_attention_bwd_dq",
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dK/dV: inner (arbitrary) loop over q blocks; heads batched, group
    # summed in-kernel
    q_spec_kv = pl.BlockSpec((1, bh, block_q, head_dim),
                             lambda b, h, ki, qi: (b, h, qi, 0))
    kv_spec_kv = pl.BlockSpec((1, bhk, block_k, head_dim),
                              lambda b, h, ki, qi: (b, h, ki, 0))
    lse_spec_kv = pl.BlockSpec((1, bh, block_q, 1),
                               lambda b, h, ki, qi: (b, h, qi, 0))
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, num_q=num_q, group=group),
        grid=(batch, num_kv_heads // bhk, num_k, num_q),
        in_specs=[q_spec_kv, kv_spec_kv, kv_spec_kv, q_spec_kv,
                  lse_spec_kv, lse_spec_kv],
        out_specs=[kv_spec_kv, kv_spec_kv],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bhk, block_k, head_dim), jnp.float32),
                        pltpu.VMEM((bhk, block_k, head_dim), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
        ),
        name="flash_attention_bwd_dkv",
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------- reference


def reference_attention(q, k, v, sm_scale=None, causal=True, bias=None):
    """XLA reference: [B, S, H, D] x [B, S, Hkv, D] GQA attention, f32 softmax."""
    batch, seq_q, num_heads, head_dim = q.shape
    _, seq_k, num_kv_heads, _ = k.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(head_dim)
    group = num_heads // num_kv_heads
    qg = q.reshape(batch, seq_q, num_kv_heads, group, head_dim)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if bias is not None:
        s = s + bias
    if causal:
        qpos = jnp.arange(seq_q)[:, None]
        kpos = jnp.arange(seq_k)[None, :]
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(batch, seq_q, num_heads, head_dim).astype(q.dtype)


# ---------------------------------------------------------------- public op


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, sm_scale=None, causal=True,
                    block_q=256, block_k=512, block_h=None):
    out, _ = _fwd_rule(q, k, v, sm_scale, causal, block_q, block_k, block_h)
    return out


def _fwd_rule(q, k, v, sm_scale, causal, block_q, block_k, block_h=None):
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    interpret = should_interpret()
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    ot, lse = _flash_fwd(qt, kt, vt, sm_scale, causal, block_q, block_k,
                         block_h, interpret)
    return ot.transpose(0, 2, 1, 3), (qt, kt, vt, ot, lse)


def _bwd_rule(sm_scale, causal, block_q, block_k, block_h, res, g):
    qt, kt, vt, ot, lse = res
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(qt.shape[-1])
    interpret = should_interpret()
    dot = g.transpose(0, 2, 1, 3)
    dq, dk, dv = _flash_bwd(qt, kt, vt, ot, lse, dot, sm_scale, causal,
                            block_q, block_k, block_h, interpret)
    return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3))


flash_attention.defvjp(_fwd_rule, _bwd_rule)
