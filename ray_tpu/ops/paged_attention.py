"""Paged attention: flash-style online-softmax THROUGH the page table.

The paged KV arena (models/decode.py, ISSUE 13) stores each layer's cache as
a pool ``[num_pages, page_tokens, Hkv * D]`` (one token's kv heads joined on
the lane axis) plus per-slot page tables. The
original decode/verify programs materialize every slot's full logical
``[pages_per_slot * page_tokens]`` view with a gather before attending — an
O(arena_len)·layers·slots copy per single-token step, so decode cost scales
with pool PROVISIONING rather than the tokens actually attended. This module
computes attention directly against the pool:

  * ``paged_attention(..., impl='pallas')`` — a Pallas TPU kernel, one grid
    cell per (slot, kv-head window). A window is the fewest kv heads whose
    joined lanes fill whole 128-lane tiles (2 heads at D=64, 1 at D=128):
    Mosaic only copies tile-aligned slices out of HBM, which is also why
    the pool keeps ``[page_tokens, Hkv * D]`` as its minor dims. The page
    table and slot lengths ride in as scalar-prefetch operands (SMEM), the
    K/V pools stay in HBM (``memory_space=ANY``), and the kernel
    async-copies ONE page window at a time into VMEM scratch — only
    ``ceil((length+K)/page_tokens)`` pages per slot, a dynamic trip count.
    No contiguous view ever exists. ``pallas_shape_problem`` names the
    shapes the compiled kernel cannot take.
  * ``paged_attention(..., impl='reference')`` — pure JAX with IDENTICAL
    math (same page order, same online-softmax update, same -1e30 mask):
    one fori_loop over pages, trip count = the batch max of allocated
    pages. This is the parity oracle for the kernel and the production
    lane off-TPU.

Mask semantics match ``LayerKVCache.mask_bias``: query row ``i`` of slot
``s`` sits at logical position ``lengths[s] + i`` and may attend logical
position ``j`` iff ``j <= lengths[s] + i``. Page-table entries past a slot's
allocation point at the reserved garbage page 0; every position they cover
is ``> lengths[s] + i``, so the mask zeroes them EXACTLY (exp(-1e30 - m)
underflows to 0.0f) — garbage content can never leak into an attended
value, and masked pages contribute bit-exact zeros to the online
accumulator (the same invariant the gathered-view lane relies on).

Decode is the K=1 case; the fixed-K verify window shares the same kernel —
each query row reduces over pages in ascending order with a full-width
mask, so per-row reduction order matches K sequential decode steps.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops._pallas import should_interpret

NEG_INF = -1e30

PAGED_ATTN_IMPLS = ("pallas", "reference")


def paged_attention(q, k_pool, v_pool, tables, lengths, *,
                    sm_scale: Optional[float] = None, impl: str = "reference"):
    """Attention for q at positions [lengths[s], lengths[s] + K) of each slot.

    q: [S, K, H, D] queries (K = 1 decode, K > 1 verify/prefill window).
    k_pool/v_pool: [N, T, Hkv * D] page pools (page 0 = garbage page).
    tables: [S, P] int32 page tables; lengths: [S] int32 slot cursors.
    Returns [S, K, H, D] in q.dtype.

    The new tokens' k/v must already be WRITTEN into their pages (write-
    before-attend, the arena's standing invariant) — this op only reads.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if impl not in PAGED_ATTN_IMPLS:
        raise ValueError(
            f"unknown paged attention impl {impl!r}; expected one of "
            f"{list(PAGED_ATTN_IMPLS)} (the 'gather' lane is not an op — "
            "models/decode.py dispatches it before reaching here)")
    if q.shape[0] != tables.shape[0] or q.shape[0] != lengths.shape[0]:
        raise ValueError(
            f"slot axis mismatch: q {q.shape}, tables {tables.shape}, "
            f"lengths {lengths.shape}")
    H, D = q.shape[2:]
    if (k_pool.ndim != 3 or k_pool.shape[2] % D != 0
            or H % (k_pool.shape[2] // D) != 0):
        raise ValueError(
            f"head mismatch: q {q.shape} vs pool {k_pool.shape} (pool is "
            "[N, T, Hkv * D]; H must be a multiple of Hkv, D must match)")
    if impl == "pallas":
        return _paged_attention_pallas(q, k_pool, v_pool, tables, lengths,
                                       sm_scale)
    return _paged_attention_reference(q, k_pool, v_pool, tables, lengths,
                                      sm_scale)


# ------------------------------------------------------------- reference


def _paged_attention_reference(q, k_pool, v_pool, tables, lengths, sm_scale):
    """Pure-JAX twin of the kernel: one fori_loop over pages, all slots
    batched per iteration. Trip count is the BATCH MAX of pages any slot
    needs — pages past a slot's own need hit its garbage-page table tail
    and contribute exact zeros, so each slot's result is bit-identical to
    looping only its own pages."""
    S, K, H, D = q.shape
    T = k_pool.shape[1]
    Hkv = k_pool.shape[2] // D
    P = tables.shape[1]
    G = H // Hkv
    # [S, K, Hkv, G, D] f32 — kv-head-major grouping, like the flash kernel
    qf = q.reshape(S, K, Hkv, G, D).astype(jnp.float32)
    qpos = lengths[:, None] + jnp.arange(K, dtype=jnp.int32)[None, :]  # [S,K]
    n_pages = lax.div(jnp.max(lengths) + K + T - 1, jnp.int32(T))
    n_pages = jnp.minimum(n_pages, jnp.int32(P))

    def body(p, carry):
        m, l, acc = carry
        pids = lax.dynamic_index_in_dim(tables, p, axis=1, keepdims=False)
        kpg = k_pool[pids].reshape(S, T, Hkv, D).astype(jnp.float32)
        vpg = v_pool[pids].reshape(S, T, Hkv, D).astype(jnp.float32)
        s_ = jnp.einsum("skhgd,sthd->skhgt", qf, kpg) * sm_scale
        kpos = p * T + jnp.arange(T, dtype=jnp.int32)            # [T]
        allowed = kpos[None, None, :] <= qpos[:, :, None]        # [S, K, T]
        s_ = jnp.where(allowed[:, :, None, None, :], s_, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s_, axis=-1))
        alpha = jnp.exp(m - m_new)
        pr = jnp.exp(s_ - m_new[..., None])
        l_new = l * alpha + jnp.sum(pr, axis=-1)
        acc_new = (acc * alpha[..., None]
                   + jnp.einsum("skhgt,sthd->skhgd", pr, vpg))
        return m_new, l_new, acc_new

    m0 = jnp.full((S, K, Hkv, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((S, K, Hkv, G), jnp.float32)
    a0 = jnp.zeros((S, K, Hkv, G, D), jnp.float32)
    _, l, acc = lax.fori_loop(0, n_pages, body, (m0, l0, a0))
    l = jnp.where(l == 0.0, 1.0, l)  # fully-masked row (can't happen: j=0
    #                                  is always allowed) -> 0, not NaN
    out = acc / l[..., None]
    return out.reshape(S, K, H, D).astype(q.dtype)


# ---------------------------------------------------------------- kernel

_LANES = 128


def _heads_per_window(kv_heads: int, head_dim: int) -> Optional[int]:
    """Fewest kv heads (a divisor of ``kv_heads``) whose joined lanes fill
    whole 128-lane tiles — the unit one page copy moves. None when no
    grouping does (``kv_heads * head_dim`` is not a multiple of 128)."""
    for hp in range(1, kv_heads + 1):
        if kv_heads % hp == 0 and (hp * head_dim) % _LANES == 0:
            return hp
    return None


def pallas_shape_problem(kv_heads: int, head_dim: int) -> Optional[str]:
    """Why Mosaic cannot compile the kernel for this pool shape, or None.

    A page window is DMA'd out of HBM as ``[page_tokens, hp * head_dim]``,
    and the chip's compiler only slices the lane axis of an HBM array on
    128-lane tile boundaries (any page_tokens compiles). The interpreter
    has no such rule, which is why every shape runs off-TPU."""
    if _heads_per_window(kv_heads, head_dim) is None:
        return (f"kv_heads * head_dim = {kv_heads * head_dim} is not a "
                f"multiple of {_LANES} lanes")
    return None


def _paged_kernel(lengths_ref, tables_ref,          # scalar prefetch (SMEM)
                  q_ref,                            # [1, 1, hp, K*G, D] VMEM
                  k_pool_ref, v_pool_ref,           # [N, T, Hkv*D] HBM/ANY
                  o_ref,                            # [1, 1, hp, K*G, D] VMEM
                  k_scr, v_scr, sem_k, sem_v,       # [T, hp*D] VMEM + DMA sems
                  *, page_tokens, qk, group, heads, head_dim, sm_scale):
    s = pl.program_id(0)
    w = pl.program_id(1)
    T, D = page_tokens, head_dim
    W = heads * D
    length = lengths_ref[s]
    n_pages = lax.div(length + qk + T - 1, jnp.int32(T))
    qs = [q_ref[0, 0, i].astype(jnp.float32) for i in range(heads)]
    # row r = i * group + g is query token i: position length + i
    row_pos = length + lax.broadcasted_iota(jnp.int32, (qk * group, 1),
                                            0) // group

    def body(p, carry):
        pid = tables_ref[s, p]
        lanes = pl.ds(pl.multiple_of(w * W, W), W)
        cp_k = pltpu.make_async_copy(k_pool_ref.at[pid, :, lanes], k_scr,
                                     sem_k)
        cp_v = pltpu.make_async_copy(v_pool_ref.at[pid, :, lanes], v_scr,
                                     sem_v)
        cp_k.start()
        cp_v.start()
        cp_k.wait()
        cp_v.wait()
        kpos = p * T + lax.broadcasted_iota(jnp.int32, (1, T), 1)
        out = []
        for i, (m, l, acc) in enumerate(carry):     # heads of this window
            kpg = k_scr[:, i * D:(i + 1) * D].astype(jnp.float32)  # [T, D]
            vpg = v_scr[:, i * D:(i + 1) * D].astype(jnp.float32)
            s_ = jax.lax.dot_general(qs[i], kpg, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            s_ = s_ * sm_scale                      # [K*G, T]
            s_ = jnp.where(kpos <= row_pos, s_, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s_, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            pr = jnp.exp(s_ - m_new)
            l_new = l * alpha + jnp.sum(pr, axis=-1, keepdims=True)
            acc_new = acc * alpha + jax.lax.dot_general(
                pr, vpg, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            out.append((m_new, l_new, acc_new))
        return tuple(out)

    init = tuple((jnp.full((qk * group, 1), NEG_INF, jnp.float32),
                  jnp.zeros((qk * group, 1), jnp.float32),
                  jnp.zeros((qk * group, D), jnp.float32))
                 for _ in range(heads))
    for i, (_, l, acc) in enumerate(lax.fori_loop(0, n_pages, body, init)):
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, i] = (acc / l).astype(o_ref.dtype)


def _paged_attention_pallas(q, k_pool, v_pool, tables, lengths, sm_scale):
    S, K, H, D = q.shape
    T = k_pool.shape[1]
    Hkv = k_pool.shape[2] // D
    G = H // Hkv
    interpret = should_interpret()
    hp = _heads_per_window(Hkv, D)
    if hp is None:
        if not interpret:
            raise ValueError(
                "paged attention kernel cannot compile for this pool: "
                + pallas_shape_problem(Hkv, D))
        hp = Hkv  # interpreted: one window spanning the whole page row
    nw = Hkv // hp
    # window-major rows: [S, nw, hp, K*G, D]; row i*G+g = (token i, group g)
    qr = q.reshape(S, K, nw, hp, G, D).transpose(0, 2, 3, 1, 4, 5)
    qr = qr.reshape(S, nw, hp, K * G, D)
    kernel = functools.partial(_paged_kernel, page_tokens=T, qk=K, group=G,
                               heads=hp, head_dim=D, sm_scale=sm_scale)
    block = (1, 1, hp, K * G, D)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, nw),
        in_specs=[
            pl.BlockSpec(block, lambda s, w, *_: (s, w, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(block, lambda s, w, *_: (s, w, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((T, hp * D), k_pool.dtype),
            pltpu.VMEM((T, hp * D), v_pool.dtype),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qr.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="paged_attention",
        interpret=interpret,
    )(lengths.astype(jnp.int32), tables.astype(jnp.int32),
      qr, k_pool, v_pool)
    out = out.reshape(S, nw, hp, K, G, D).transpose(0, 3, 1, 2, 4, 5)
    return out.reshape(S, K, H, D)
