"""Paged attention: flash-style online-softmax THROUGH the page table.

The paged KV arena (models/decode.py) stores each layer's cache as a pool
``[num_pages, page_tokens, Hkv * D]`` (one token's kv heads joined on the
lane axis) plus per-slot page tables. This module computes attention
directly against the pool, in BLOCKS of whole page rows, so a step's cost
follows the tokens attended and not the pool's provisioning, and it owns the
choice of implementation: ``resolve_impl`` picks one from the platform and
the model's shapes, ``streamed_tokens`` says what each fetches for a call
(the scheduler's counters).

  * A block is ``B`` consecutive entries of a slot's page table, ``B * T``
    tokens; scores and the online-softmax update are per block
    (``[K*G, D] x [D, B*T]``, then ``[K*G, B*T] x [B*T, D]``), with the
    operands in the pool's dtype and f32 accumulation; m, l and the
    accumulator stay f32. ``tile_sizes`` derives ``B`` (and the query tile
    for a long window) from the static shapes alone: up to two megabytes of
    K and V rows and 512 tokens a block — one megabyte covers the HBM's
    latency, but a block's fixed cost (a softmax chain a kv head) is only
    paid off by more, as measured on the v5e (PERF.md 6, PR 25).
  * ``paged_attention(..., impl='pallas')`` — a Pallas TPU kernel, one grid
    cell per (slot, query tile). The page table and slot lengths ride in as
    scalar-prefetch operands (SMEM), the K/V pools stay in HBM
    (``memory_space=ANY``). A page copy moves the WHOLE row
    ``[page_tokens, Hkv * D]`` (contiguous in HBM; every kv head of the
    slot is then served from VMEM by static lane slices); all of a block's
    copies are started before any is waited for, into one of two VMEM
    buffers, and block n+1's copies — or, behind a cell's last block, the
    NEXT grid cell's first block — are started before block n is computed
    on. Only the blocks up to a query tile's last position are fetched, a
    dynamic trip count, and the slots that attend something come first in
    the grid, so a slot without a live sequence is fetched nothing for and
    handed no buffer. No contiguous view ever exists.
    ``pallas_shape_problem`` names the shapes the compiled kernel cannot
    take.
  * ``paged_attention(..., impl='reference')`` — pure JAX with IDENTICAL
    math (same blocks in the same order, same operand dtypes, same
    online-softmax update, same -1e30 mask): one fori_loop over blocks,
    trip count = the batch max of blocks. This is the parity oracle for
    the kernel and what serves off-TPU.

Mask semantics match ``LayerKVCache.mask_bias``: query row ``i`` of slot
``s`` sits at logical position ``lengths[s] + i`` and may attend logical
position ``j`` iff ``j <= lengths[s] + i``. A slot whose whole window lies
before position 0 (``lengths[s] + K <= 0``: how a caller marks a row without
a live sequence) attends nothing, reads no page and returns zeros.
Page-table entries past a slot's
allocation point at the reserved garbage page 0; every position they cover
is ``> lengths[s] + i``, so the mask zeroes them EXACTLY (exp(-1e30 - m)
underflows to 0.0f) — garbage content can never leak into an attended
value, and masked positions contribute bit-exact zeros to the online
accumulator.

The partly filled last block: a masked score gives p = 0, and 0 times
uninitialised VMEM (NaN) would be NaN. So EVERY entry of a block is fetched
through the table, whatever the slot's length: the tail entries are the
table's own garbage-page entries (finite by the arena's standing
invariant), and entries past the table's end, where ``B`` does not divide
its width, read page 0 too. No part of a buffer is ever computed on before
a copy has defined it. What the tail costs is counted by the scheduler
(``attn_tokens_fetched`` against ``attn_tokens_attended``).

A WINDOW (``window=W``, static; a 'sliding_attention' layer's call): row
``i`` attends ``j`` iff ``lengths[s] + i - W < j <= lengths[s] + i``. Both
implementations then START at the block that holds the first position the
query tile's first row may attend and fetch nothing before it, so a call's
cost follows the window and not the context, and the pages wholly behind the
window are never read (their table entries may point at the garbage page:
the scheduler releases them). A block that lies wholly before a LATER row's
window gives that row p = 1 on every masked score for a while; the first
block that holds a position it may attend (its own, if no other) rescales
that by exp(-1e30 - m) = 0 exactly, as the online softmax does for any
stale maximum. Without a window the traced program is what it was.

A STACK of pools (``pool_index=``, a traced scalar; a looped model's serving
forward, whose body is one layer under a loop): the pools are ``[N, pools, T,
Hkv * D]``, a page holding its token span once a pool under the ONE table,
and the call reads pool ``pool_index`` of every page where it lies — the
kernel takes the index by scalar prefetch and its page copy's source is
``pool.at[page, index]``, as contiguous a run of whole rows as a page of a
pool alone; the reference slices the pool out. Without it the traced program
is what it was.

Decode is the K=1 case; the fixed-K verify window and the prefill chunk
share the same kernel — each query row reduces over blocks in ascending
order with a full-width mask, so per-row reduction order matches K
sequential decode steps.
"""

from __future__ import annotations

import functools
import logging
import math
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops._pallas import should_interpret

logger = logging.getLogger(__name__)

NEG_INF = -1e30

PAGED_ATTN_IMPLS = ("pallas", "reference")


def resolve_impl(cfg, impl: Optional[str] = None) -> str:
    """The implementation the serving programs run for the model ``cfg``
    (its ``kv_heads`` and ``head_dim``): the kernel on a TPU, the reference
    elsewhere. On a TPU whose compiler cannot take the model's pool shape
    (``pallas_shape_problem``) it is the reference by that stated rule,
    logged. Resolved ONCE, at scheduler build, so ``stats()`` names what
    really runs.

    An explicit ``impl`` ('reference' | 'pallas') is taken as given: the
    tests run the kernel interpreted on a CPU. Anything else — a falsy
    spelling like "0" or "" too — is refused, and an explicit 'pallas' that
    Mosaic would have to compile for a shape it cannot take raises here
    instead of at the first decode step."""
    if impl is not None and impl not in PAGED_ATTN_IMPLS:
        raise ValueError(
            f"unknown paged attention impl {impl!r}; expected one of "
            f"{list(PAGED_ATTN_IMPLS)}, or None for the platform's")
    chosen = impl
    if chosen is None:
        chosen = "pallas" if jax.default_backend() == "tpu" else "reference"
    if chosen == "pallas" and not should_interpret():
        problem = pallas_shape_problem(cfg.kv_heads, cfg.head_dim)
        if problem and impl is not None:
            raise ValueError(
                f"paged attention impl 'pallas' cannot compile for this "
                f"model on a TPU: {problem}")
        if problem:
            logger.warning("paged attention: the 'reference' implementation "
                           "serves, the Pallas kernel cannot compile here "
                           "(%s)", problem)
            chosen = "reference"
    return chosen


def paged_attention(q, k_pool, v_pool, tables, lengths, *,
                    sm_scale: Optional[float] = None, impl: str = "reference",
                    name: str = "paged_attention",
                    window: Optional[int] = None, pool_index=None):
    """Attention for q at positions [lengths[s], lengths[s] + K) of each slot.

    q: [S, K, H, D] queries (K = 1 decode, K > 1 verify/prefill window).
    k_pool/v_pool: [N, T, Hkv * D] page pools (page 0 = garbage page); with
    ``pool_index`` (an int32 scalar, traced) [N, pools, T, Hkv * D], of
    which pool ``pool_index`` is read in place.
    tables: [S, P] int32 page tables; lengths: [S] int32 slot cursors
    (``-K`` for a row without a live sequence: zeros, no page read).
    Returns [S, K, H, D] in q.dtype. ``name``: what the kernel is called in
    a profiler trace (``ops.sparse_attention`` runs it over tables of chosen
    pages under a name of its own). ``window``: the positions a row
    attends, its own among them (static; None: all up to its own).

    The new tokens' k/v must already be WRITTEN into their pages (write-
    before-attend, the arena's standing invariant) — this op only reads.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if impl not in PAGED_ATTN_IMPLS:
        raise ValueError(
            f"unknown paged attention impl {impl!r}; expected one of "
            f"{list(PAGED_ATTN_IMPLS)}")
    if q.shape[0] != tables.shape[0] or q.shape[0] != lengths.shape[0]:
        raise ValueError(
            f"slot axis mismatch: q {q.shape}, tables {tables.shape}, "
            f"lengths {lengths.shape}")
    H, D = q.shape[2:]
    if (k_pool.ndim != (3 if pool_index is None else 4)
            or k_pool.shape[-1] % D != 0
            or H % (k_pool.shape[-1] // D) != 0):
        raise ValueError(
            f"head mismatch: q {q.shape} vs pool {k_pool.shape} (pool is "
            "[N, T, Hkv * D], with pool_index [N, pools, T, Hkv * D]; H "
            "must be a multiple of Hkv, D must match)")
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if impl == "pallas":
        which = (None if pool_index is None else
                 jnp.reshape(pool_index, (1,)).astype(jnp.int32))
        return _paged_attention_pallas(q, k_pool, v_pool, tables, lengths,
                                       sm_scale, should_interpret(), name,
                                       window, which)
    if pool_index is not None:
        k_pool, v_pool = (lax.dynamic_index_in_dim(
            pool, pool_index, axis=1, keepdims=False)
            for pool in (k_pool, v_pool))
    return _paged_attention_reference(q, k_pool, v_pool, tables, lengths,
                                      sm_scale, window)


# ------------------------------------------------------------ tile sizes

_LANES = 128
_BLOCK_BYTES = 2 << 20   # K and V rows one block's copies keep in flight
_MAX_BLOCK_TOKENS = 512  # lanes of a score tile
_MAX_Q_ROWS = 512        # query rows (tokens x group) of one tile
_SUB_ROWS = 256          # of which one matmul takes so many: the f32 score
#                          tile is [_SUB_ROWS, block tokens], 512 KB at most


def tile_sizes(qk: int, group: int, page_tokens: int, pages_per_slot: int,
               row_bytes: int, window: Optional[int] = None
               ) -> Tuple[int, int]:
    """(pages per block, query tokens per tile) for a K = ``qk`` window over
    a pool whose token rows hold ``row_bytes`` (all kv heads of K or of V).

    Both implementations and ``streamed_tokens`` read the blocking from
    here, and it follows from static shapes alone: a long window is cut into
    tiles of about ``_MAX_Q_ROWS`` query rows (whole multiples of the
    ``_SUB_ROWS`` one matmul takes); a block is the largest power of two of
    pages that keeps its K and V rows within ``_BLOCK_BYTES``, its tokens
    within ``_MAX_BLOCK_TOKENS`` and itself within the page table — and,
    under a ``window``, within half of it: a row's window then spans three
    blocks at most, of which two are full (at 1024 tokens and pages of 16
    the 512-token block as it is)."""
    n_tiles = -(-qk * group // _MAX_Q_ROWS)
    q_tile = -(-qk // n_tiles)
    if q_tile * group > _SUB_ROWS:  # whole matmuls of _SUB_ROWS rows
        step = _SUB_ROWS // math.gcd(group, _SUB_ROWS)
        q_tile = -(-q_tile // step) * step
    pages = min(_BLOCK_BYTES // (2 * page_tokens * row_bytes),
                _MAX_BLOCK_TOKENS // page_tokens, pages_per_slot)
    if window is not None:
        pages = min(pages, window // (2 * page_tokens))
    return 1 << (max(pages, 1).bit_length() - 1), q_tile


def streamed_tokens(impl: str, qk: int, cursors: List[int], idle_rows: int,
                    group: int, page_tokens: int, pages_per_slot: int,
                    row_bytes: int, window: Optional[int] = None,
                    tiles: Optional[Tuple[int, int]] = None
                    ) -> Tuple[int, int]:
    """(attended, fetched) token positions of one ``[S, K = qk]`` call, per
    layer: ``tile_sizes``' twin on the host, for counters. ``cursors``: the
    attention cursor of every row that attends its window; ``idle_rows``:
    the call's other rows, which attend nothing. Both implementations
    stream whole BLOCKS of pages: the kernel each row's own blocks, once per
    query tile, up to the tile's last position (an idle row none); the
    reference every row, idle ones too, over the longest row's blocks.
    Attended (the positions a row, or a query tile of the kernel, may
    attend) over fetched is the block fill share. Under a ``window`` both
    start at the block that holds the first position the window (of the
    row, or of the tile's first row) lets in. ``tiles``: the (pages a
    block, query tokens a tile) of a kernel with a rule of its own
    (``ops.latent_attention.latent_tiles``); default ``tile_sizes``'."""
    pages, q_tile = tiles or tile_sizes(qk, group, page_tokens,
                                        pages_per_slot, row_bytes, window)
    block = pages * page_tokens

    def blocks(upto: int) -> int:
        return min(-(-upto // block), -(-pages_per_slot // pages))

    def behind(start: int) -> int:
        """The positions before a window whose first query is at
        ``start``: never attended, and fetched only where they share the
        window's first block."""
        return 0 if window is None else max(start - window + 1, 0)

    if impl == "reference":
        attended = sum(c + qk - behind(c) for c in cursors)
        fetched = ((len(cursors) + idle_rows) * max(
            blocks(c + qk) - behind(c) // block for c in cursors)
                   if cursors else 0)
    else:  # each query tile streams the blocks up to its own end
        tiles = [(e - q_tile, min(e, qk))
                 for e in range(q_tile, qk + q_tile, q_tile)]
        attended = sum(c + e - behind(c + b) for b, e in tiles
                       for c in cursors)
        fetched = sum(blocks(c + e) - behind(c + b) // block
                      for b, e in tiles for c in cursors)
    return attended, fetched * block


def _vmem_bytes(shape, dtype) -> int:
    """What an array takes in VMEM: its minor axis in whole 128-lane rows,
    the axis before it in whole tiles of eight 32-bit sublanes."""
    *lead, rows, lanes = shape
    item = jnp.dtype(dtype).itemsize
    sub = 8 * 4 // item
    return (math.prod(lead) * -(-rows // sub) * sub
            * -(-lanes // _LANES) * _LANES * item)


def _shapes(q, k_pool, tables, window=None):
    S, K, H, D = q.shape
    T = k_pool.shape[-2]
    Hkv = k_pool.shape[-1] // D
    P = tables.shape[1]
    G = H // Hkv
    B, q_tile = tile_sizes(K, G, T, P,
                           k_pool.shape[-1] * k_pool.dtype.itemsize, window)
    return S, K, D, T, Hkv, P, G, B, q_tile


# ------------------------------------------------------------- reference


def _paged_attention_reference(q, k_pool, v_pool, tables, lengths, sm_scale,
                               window=None):
    """Pure-JAX twin of the kernel: one fori_loop over blocks of pages, all
    slots batched per iteration. Trip count is the BATCH MAX of blocks any
    slot needs — blocks past a slot's own need hit its garbage-page table
    tail and contribute exact zeros, so each slot's result is bit-identical
    to looping only its own blocks. Under a ``window`` iteration ``b`` reads
    every slot's OWN block ``first[s] + b``, its first being the one that
    holds the first position its first row may attend."""
    S, K, D, T, Hkv, P, G, B, _ = _shapes(q, k_pool, tables, window)
    BT = B * T
    n_table_blocks = -(-P // B)
    # entries past the table's end read the garbage page, like its tail
    tables = jnp.pad(tables, ((0, 0), (0, n_table_blocks * B - P)))
    # the kernel's operands: rows [K*G, D] a kv head, row i*G+g = (token i,
    # group g), against that head's [BT, D] block, batched over (slot, head)
    qh = q.reshape(S, K, Hkv, G, D).transpose(0, 2, 1, 3, 4)
    qh = qh.reshape(S, Hkv, K * G, D).astype(k_pool.dtype)
    qpos = lengths[:, None] + jnp.arange(K, dtype=jnp.int32)[None, :]  # [S,K]
    qpos = jnp.repeat(qpos, G, axis=1)[:, None, :, None]      # [S,1,K*G,1]
    n_blocks = lax.div(jnp.max(lengths) + K + BT - 1, jnp.int32(BT))
    n_blocks = jnp.minimum(n_blocks, jnp.int32(n_table_blocks))
    batch = ((0, 1), (0, 1))
    if window is not None:
        first = jnp.maximum(lengths - window + 1, 0) // BT           # [S]
        own = jnp.clip(lax.div(lengths + K + BT - 1, jnp.int32(BT)), 0,
                       n_table_blocks)
        n_blocks = jnp.max(jnp.maximum(own - first, 0))

    def body(b, carry):
        m, l, acc = carry
        if window is None:
            pids = lax.dynamic_slice_in_dim(tables, b * B, B, axis=1)  # [S, B]
        else:  # a block a slot; past the table's end it is masked whole
            at = (first + b)[:, None]
            pids = jnp.take_along_axis(
                tables, jnp.minimum(at, n_table_blocks - 1) * B
                + jnp.arange(B, dtype=jnp.int32)[None], axis=1)
        kb = k_pool[pids].reshape(S, BT, Hkv, D).transpose(0, 2, 1, 3)
        vb = v_pool[pids].reshape(S, BT, Hkv, D).transpose(0, 2, 1, 3)
        s_ = lax.dot_general(qh, kb, (((3,), (3,)), batch),
                             preferred_element_type=jnp.float32)
        if window is None:
            kpos = b * BT + jnp.arange(BT, dtype=jnp.int32)         # [BT]
            seen = kpos <= qpos
        else:
            kpos = (at * BT + jnp.arange(BT, dtype=jnp.int32)[None]
                    )[:, None, None, :]                       # [S,1,1,BT]
            seen = jnp.logical_and(kpos <= qpos, kpos > qpos - window)
        s_ = jnp.where(seen, s_ * sm_scale, NEG_INF)    # [S,Hkv,K*G,BT]
        m_new = jnp.maximum(m, jnp.max(s_, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        pr = jnp.exp(s_ - m_new)
        l_new = l * alpha + jnp.sum(pr, axis=-1, keepdims=True)
        acc_new = acc * alpha + lax.dot_general(
            pr.astype(vb.dtype), vb, (((3,), (2,)), batch),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((S, Hkv, K * G, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((S, Hkv, K * G, 1), jnp.float32)
    a0 = jnp.zeros((S, Hkv, K * G, D), jnp.float32)
    _, l, acc = lax.fori_loop(0, n_blocks, body, (m0, l0, a0))
    l = jnp.where(l == 0.0, 1.0, l)  # no block at all -> 0, not NaN
    out = (acc / l).reshape(S, Hkv, K, G, D).transpose(0, 2, 1, 3, 4)
    # a slot that attends nothing went through the others' blocks, all
    # masked, which leaves the mean of what they held: zeros instead
    out = jnp.where((lengths + K > 0)[:, None, None, None, None], out, 0.0)
    return out.reshape(S, K, Hkv * G, D).astype(q.dtype)


# ---------------------------------------------------------------- kernel


def pallas_shape_problem(kv_heads: int, head_dim: int) -> Optional[str]:
    """Why Mosaic cannot compile the kernel for this pool shape, or None.

    A page row is DMA'd out of HBM whole, ``[page_tokens, kv_heads *
    head_dim]``, and the chip's compiler only moves whole 128-lane tiles
    (any page_tokens the pool's dtype tiles compiles). The interpreter
    has no such rule, which is why every shape runs off-TPU."""
    if (kv_heads * head_dim) % _LANES:
        return (f"kv_heads * head_dim = {kv_heads * head_dim} is not a "
                f"multiple of {_LANES} lanes")
    return None


def _paged_kernel(lengths_ref, tables_ref,      # scalar prefetch (SMEM)
                  order_ref, n_live_ref,        # the same: see below
                  *refs, page_tokens, pages, qk, q_tile, group, kv_heads,
                  head_dim, sm_scale, window=None, stacked=False):
    """One (slot, query tile) cell: R = q_tile * group query rows of every
    kv head over the slot's blocks 0 .. the tile's last position.

    Cell c of the grid's first axis serves slot ``order[c]``: the
    ``n_live`` slots that attend something first, the others after them,
    where they only write their zeros. The grid runs in order, and the two
    buffers are handed from cell to cell: ``first_buf`` names the buffer
    that holds this cell's block 0, whose copies the PREVIOUS cell started
    behind its own last block (the first cell starts its own). So a slot of
    two or three blocks does not pay a cold start (1.4-2.1 us a cell on the
    v5e, PERF.md 6), and nothing is ever started for a slot that attends
    nothing. Under a ``window`` a cell's walk starts at its FIRST block, the
    one that holds the first position its first row may attend
    (``first_block``), and that is the block the cell before it starts for
    it. ``stacked``: a fifth scalar, ``which_ref`` [1], comes first of
    ``refs``, and the pools are ``[N, pools, T, Hkv*D]``: a page's copy
    reads pool ``which_ref[0]`` of it."""
    which_ref, refs = (refs[0], refs[1:]) if stacked else (None, refs)
    (q_ref,                        # [1, 1, Hkv, R, D] VMEM
     k_pool_ref, v_pool_ref,       # [N, T, Hkv*D] HBM/ANY
     o_ref,                        # [1, 1, Hkv, R, D] VMEM
     k_buf, v_buf,                 # [2, B*T, Hkv*D] VMEM
     sems,                         # DMA [2 buffers, k|v]
     first_buf,                    # SMEM [1]: see below
     m_scr, l_scr, acc_scr) = refs  # [Hkv, R, 1|1|D] f32 VMEM
    c, t = pl.program_id(0), pl.program_id(1)
    n_tiles, n_live = pl.num_programs(1), n_live_ref[0]
    s = order_ref[c]
    T, B, D, R = page_tokens, pages, head_dim, q_tile * group
    BT = B * T
    # rows one matmul takes: all of a small tile's (whatever K * group is),
    # else _SUB_ROWS, which tile_sizes makes divide R
    RS = R if R <= _SUB_ROWS else _SUB_ROWS
    P = tables_ref.shape[1]

    def block_copies(s, b, buf, wait=False):
        """Start (or wait for) the 2 * B whole-row copies of block b."""
        for i in range(B):
            j = b * B + i
            if wait:
                pid = 0                  # a wait reads the sizes only
            elif P % B == 0:
                pid = tables_ref[s, j]
            else:                        # past the table's end: page 0
                pid = jnp.where(j < P, tables_ref[s, jnp.minimum(j, P - 1)],
                                0)
            rows = pl.ds(i * T, T)
            for kv, (pool, dst) in enumerate(((k_pool_ref, k_buf),
                                              (v_pool_ref, v_buf))):
                page = (pool.at[pid] if which_ref is None
                        else pool.at[pid, which_ref[0]])
                cp = pltpu.make_async_copy(page, dst.at[buf, rows],
                                           sems.at[buf, kv])
                cp.wait() if wait else cp.start()

    def first_block(s, t):
        """The block that holds the first position the first row of query
        tile ``t`` of slot ``s`` may attend under the window: never past
        the tile's last block, which holds that row's own position."""
        seen = jnp.maximum(lengths_ref[s] + t * q_tile - window + 1, 0)
        return lax.div(seen, jnp.int32(BT))

    b0 = 0 if window is None else first_block(s, t)

    @pl.when(jnp.logical_and(c == 0, t == 0))
    def _():
        first_buf[0] = 0

        @pl.when(n_live > 0)
        def _():
            block_copies(s, b0, 0)

    base = first_buf[0]
    # the blocks up to the tile's last position, within the table
    upto = lengths_ref[s] + jnp.minimum((t + 1) * q_tile, qk)
    nb = jnp.clip(lax.div(upto + BT - 1, jnp.int32(BT)), 1, -(-P // B))
    nb = jnp.where(c < n_live, nb, 0)  # a live cell uses what it was handed
    c_next = jnp.where(t + 1 == n_tiles, c + 1, c)  # whose block 0 is next
    s_next = order_ref[jnp.minimum(c_next, pl.num_programs(0) - 1)]
    if window is None:
        b0_next = 0
    else:
        b0 = jnp.minimum(b0, nb)  # a cell that is not live walks nothing
        b0_next = first_block(s_next, jnp.where(t + 1 == n_tiles, 0, t + 1))

    m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def body(b, _):
        buf = lax.rem(base + b if window is None else base + b - b0, 2)
        more = b + 1 < nb

        @pl.when(jnp.logical_or(more, c_next < n_live))
        def _():
            block_copies(jnp.where(more, s, s_next),
                         jnp.where(more, b + 1, b0_next), 1 - buf)

        block_copies(s, b, buf, wait=True)
        kpos = b * BT + lax.broadcasted_iota(jnp.int32, (1, BT), 1)

        def update(h, r0):
            """Rows [r0, r0 + RS) of kv head h against this block."""
            rows, lanes = pl.ds(r0, RS), slice(h * D, (h + 1) * D)
            s_ = lax.dot_general(q_ref[0, 0, h, rows], k_buf[buf, :, lanes],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            # row r = i * group + g is query token i of the tile
            row_pos = lengths_ref[s] + t * q_tile + (
                r0 + lax.broadcasted_iota(jnp.int32, (RS, 1), 0)) // group
            seen = kpos <= row_pos
            if window is not None:
                seen = jnp.logical_and(seen, kpos > row_pos - window)
            s_ = jnp.where(seen, s_ * sm_scale, NEG_INF)            # [RS,BT]
            m = m_scr[h, rows]
            m_new = jnp.maximum(m, jnp.max(s_, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            pr = jnp.exp(s_ - m_new)
            l_scr[h, rows] = (l_scr[h, rows] * alpha
                              + jnp.sum(pr, axis=-1, keepdims=True))
            acc_scr[h, rows] = acc_scr[h, rows] * alpha + lax.dot_general(
                pr.astype(v_buf.dtype), v_buf[buf, :, lanes],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[h, rows] = m_new

        for h in range(kv_heads):
            if R == RS:
                update(h, 0)
            else:   # a loop, not R / RS unrolled copies: compile time
                lax.fori_loop(0, R // RS, lambda i, _, h=h: update(
                    h, pl.multiple_of(i * RS, RS)), None)

    lax.fori_loop(b0, nb, body, None)
    first_buf[0] = lax.rem(base + nb if window is None else base + nb - b0,
                           2)
    l = l_scr[...]
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


# jitted on its own: a program calls the op once a layer with the same
# shapes, and the kernel's body is then traced and lowered once, not once a
# layer (16 layers cost 14 s of every process's start otherwise)
@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret", "name",
                                             "window"))
def _paged_attention_pallas(q, k_pool, v_pool, tables, lengths, sm_scale,
                            interpret, name="paged_attention", window=None,
                            which=None):
    """``which``: int32 [1], the pool of a stack ``[N, pools, T, Hkv*D]``
    the call reads (one more scalar-prefetch operand); None: a pool
    alone."""
    S, K, D, T, Hkv, P, G, B, q_tile = _shapes(q, k_pool, tables, window)
    problem = None if interpret else pallas_shape_problem(Hkv, D)
    if problem:
        raise ValueError(
            f"paged attention kernel cannot compile for this pool: {problem}")
    n_tiles = -(-K // q_tile)
    R = q_tile * G
    # tile-major rows: [S, tiles, Hkv, R, D]; row i*G+g = (token i, group g)
    qr = jnp.pad(q.astype(k_pool.dtype),
                 ((0, 0), (0, n_tiles * q_tile - K), (0, 0), (0, 0)))
    qr = qr.reshape(S, n_tiles, q_tile, Hkv, G, D).transpose(0, 1, 3, 2, 4, 5)
    qr = qr.reshape(S, n_tiles, Hkv, R, D)
    kernel = functools.partial(_paged_kernel, page_tokens=T, pages=B, qk=K,
                               q_tile=q_tile, group=G, kv_heads=Hkv,
                               head_dim=D, sm_scale=sm_scale, window=window,
                               stacked=which is not None)
    block = (1, 1, Hkv, R, D)
    live = lengths + K > 0
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    bufs = [pltpu.VMEM((2, B * T, Hkv * D), pool.dtype)
            for pool in (k_pool, v_pool)]
    stats = [pltpu.VMEM((Hkv, R, width), jnp.float32) for width in (1, 1, D)]
    # the scratch, the query and output blocks (the pipeline keeps two of
    # each) and four score tiles of one matmul, and half as much again
    vmem = (sum(_vmem_bytes(a.shape, a.dtype) for a in bufs + stats)
            + 2 * (_vmem_bytes(block, k_pool.dtype)
                   + _vmem_bytes(block, q.dtype))
            + 4 * _vmem_bytes((min(R, _SUB_ROWS), B * T), jnp.float32))

    def cell(c, t, lengths_ref, tables_ref, order_ref, *_):
        return order_ref[c], t, 0, 0, 0

    scalars = (lengths.astype(jnp.int32), tables.astype(jnp.int32), order,
               jnp.sum(live, dtype=jnp.int32)[None])
    if which is not None:
        scalars += (which,)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(S, n_tiles),
        in_specs=[
            pl.BlockSpec(block, cell),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(block, cell),
        scratch_shapes=bufs + [pltpu.SemaphoreType.DMA((2, 2)),
                               pltpu.SMEM((1,), jnp.int32)] + stats,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qr.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem + vmem // 2),
        name=name,
        interpret=interpret,
    )(*scalars, qr, k_pool, v_pool)
    out = out.reshape(S, n_tiles, Hkv, q_tile, G, D).transpose(
        0, 1, 3, 2, 4, 5)
    return out.reshape(S, n_tiles * q_tile, Hkv * G, D)[:, :K]
