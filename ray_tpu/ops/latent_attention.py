"""Paged LATENT attention: multi-head latent attention (MLA) with the keys'
and the values' up-projections absorbed, straight against the pages.

A 'latent_attention' layer (models/transformer.py) caches ONE latent of
``rank`` values a token (after its norm) and ONE rotated key of ``rope``
values that every head shares, joined into one row a token (``join``) of a
pool ``[num_pages, page_tokens, width]`` under the page tables every
page-holding kind uses. ``width`` is ``rank + rope`` up to whole 128-lane
tiles (``pool_width``: 640 for 512 + 64): the chip keeps an array's minor
axis in whole tiles whatever its logical size, so a pool of 576 lanes, or
one of 512 beside one of 64, holds and moves the same 640 — and Mosaic moves
no 64-lane slice at all. With the key's up-projection folded into the query
(``q' = q_nope W_uk^T``, [H, rank]) and the value's into the output, head h
of a query row attends the latents themselves:

    score[h, s] = (q'[h] . c[s] + q_rope[h] . kr[s]) * sm_scale
    o'[h]       = sum_s softmax_s(score[h, s]) c[s]            [H, rank]

which is multi-QUERY attention over one key of ``rank + rope`` values a
token whose first ``rank`` values are also its value. So a page is read
ONCE, in one copy, and serves every head as key and, in its first ``rank``
lanes, as value — what ``ops.paged_attention`` cannot do (it reads a K pool
and a V pool).

  * ``latent_attention(..., impl='pallas')`` — one Pallas TPU kernel for the
    decode step (K = 1: a slot's H query rows) and the prefill chunk (K > 1:
    query tokens x H rows), one grid cell a (slot, cell of query tokens).
    The walk is ``ops.paged_attention``'s: page tables and cursors in SMEM,
    the pool in HBM, a block of pages copied page by page into one of two
    VMEM buffers while the block before it is multiplied (the query is
    padded with zeros to the row's width, so the scores are one product),
    only the blocks up to the cell's last position, the slots that attend
    something first in the grid and the next cell's first block started
    behind a cell's last. A slot's context is walked ONCE A CELL, and a
    cell holds as many of the call's query tokens as VMEM does
    (``latent_tiles``: all 512 of a chunk at GLM's sizes, where the paged
    kernel's rule made 8 tiles of 64 and each walked the context again): a
    block is copied in once and meets every row of the cell, ``_SUB_ROWS``
    at a time and ``_IN_FLIGHT`` such groups side by side, so that one
    group's softmax lies under another's products (the matrix unit waits
    for a group's softmax between its two products: alone that chain, not
    the copies or the mask, was the tile's time — PERF.md 6, PR 56). A
    block that ends at or before the cell's first query position is seen by
    every row and takes no mask; only the blocks that reach into the cell's
    own tokens (one or two of a chunk's hundred) build one. It runs under
    the ``name`` the caller gives (a trace tells the step's kernel from the
    chunk's).
  * ``latent_attention(..., impl='reference')`` — plain ``jax.numpy``: the
    slots' pages gathered through the tables, dense masked scores, a
    float32 softmax. What serves off a TPU, and what the kernel is held
    against (interpreted) in tests/test_glm_moe_lite.py.

Mask semantics are ``ops.paged_attention``'s: query row i of slot s sits at
position ``lengths[s] + i`` and attends every position up to its own; a slot
whose window lies wholly before position 0 attends nothing, reads no page
and returns zeros; table entries past a slot's allocation point at the
garbage page 0, whose content is finite by the arena's standing invariant.
The new tokens' latents and keys must already be WRITTEN into their pages.

What a call must do at least (``perfbench/lib/latent_work.py`` counts it): a
(query, key, head) pair costs ``2 (rank + rope) + 2 rank`` operations, a key
read ``(rank + rope) x itemsize`` bytes (the row's padding is the kernel's
cost, not the work's). At GLM-4.7-Flash's sizes (20 heads,
rank 512, rope 64, bf16) a decode row does 37.8 operations a byte against
the v5e's 240: the step is bound by the memory, and its 20 query rows fill
20/128 of an MXU pass, which puts it at the knee.
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
from ray_tpu.ops.paged_attention import (_SUB_ROWS, NEG_INF, PAGED_ATTN_IMPLS,
                                         _vmem_bytes, tile_sizes)


_LANES = 128


def pool_width(rank: int, rope: int) -> int:
    """Lanes of a token's row in the pool: the latent, the rotated key, and
    zeros up to whole 128-lane tiles."""
    return -(-(rank + rope) // _LANES) * _LANES


def join(c, kr):
    """A token's row as the pool holds it: c [..., rank] | kr [..., rope] |
    zeros [..., pool_width - rank - rope]."""
    pad = pool_width(c.shape[-1], kr.shape[-1]) - c.shape[-1] - kr.shape[-1]
    return jnp.concatenate(
        [c, kr, jnp.zeros(c.shape[:-1] + (pad,), c.dtype)], axis=-1)


# What a cell's blocks and scratch may take of VMEM (bytes, as ``latent_tiles``
# counts them). v5e has 128 MiB of VMEM a core, of which a kernel gets 16 MiB
# unless it asks; Mosaic is given the cell's own count and half again.
_CELL_VMEM = 96 << 20
_VMEM_LIMIT = 120 << 20
# groups of ``_SUB_ROWS`` rows a turn of a cell's inner loop takes through a
# block side by side (see ``_latent_kernel``): 2 reads 7.72 ms where 1 reads
# 8.84 and 4 reads 7.63 (a 512 chunk at 55k alone; my chip run, PR 56)
_IN_FLIGHT = 2


def latent_tiles(qk: int, heads: int, page_tokens: int, pages_per_slot: int,
                 width: int, itemsize: int):
    """(pages a block, query tokens a cell) of a K = ``qk`` call. The block
    is the paged kernel's (``ops.paged_attention.tile_sizes`` for one row of
    ``width`` lanes a token). The cell is this kernel's own: ONE row a
    token serves all ``heads`` query rows, so a cell of the paged kernel's
    ``_MAX_Q_ROWS`` would hold a ``heads``-th of its tokens and walk the
    context that much more often. A cell holds all of the call's tokens if
    ``_CELL_VMEM`` does, else the most whole matmuls of ``_SUB_ROWS`` rows
    that fit, the call's tokens spread evenly over its cells. From static
    shapes alone. Counted a query row: the query's and the output's
    pipelined blocks (two of each; the output at the row's whole width, an
    upper bound), the float32 accumulator likewise, m and l a lane tile
    each; beside the two block buffers and the score tiles of the matmuls
    in flight."""
    pages, _ = tile_sizes(qk, heads, page_tokens, pages_per_slot,
                          width * itemsize // 2)
    if qk * heads <= _SUB_ROWS:
        return pages, qk
    block = pages * page_tokens
    row = 4 * width * itemsize + 4 * width + 2 * 4 * _LANES
    fixed = (2 * block * width * itemsize
             + 4 * _IN_FLIGHT * _SUB_ROWS * block * 4)
    step = _SUB_ROWS // math.gcd(heads, _SUB_ROWS)  # tokens of whole matmuls
    wanted = -(-qk // step)
    most = max((_CELL_VMEM - fixed) // (row * step * heads), 1)
    cells = -(-wanted // most)
    return pages, -(-wanted // cells) * step


def latent_attention(q_c, q_r, pool, tables, lengths, *, sm_scale: float,
                     impl: str = "reference",
                     name: str = "latent_attention"):
    """q_c [S, K, H, rank] (the query with the key's up-projection folded
    in) and q_r [S, K, H, rope] (its rotated part) at positions [lengths[s],
    lengths[s] + K) of each slot, over pool [N, T, width] (``join``'s rows)
    through tables [S, P]. Returns o' [S, K, H, rank] in q_c's dtype: every
    head's softmax mix of the latents it attended."""
    if impl not in PAGED_ATTN_IMPLS:
        raise ValueError(
            f"unknown latent attention impl {impl!r}; expected one of "
            f"{list(PAGED_ATTN_IMPLS)}")
    if (q_c.shape[:3] != q_r.shape[:3] or pool.ndim != 3
            or pool.shape[2] != pool_width(q_c.shape[3], q_r.shape[3])):
        raise ValueError(
            f"shape mismatch: q {q_c.shape} / {q_r.shape} against a pool "
            f"{pool.shape}")
    if q_c.shape[0] != tables.shape[0] or q_c.shape[0] != lengths.shape[0]:
        raise ValueError(
            f"slot axis mismatch: q {q_c.shape}, tables {tables.shape}, "
            f"lengths {lengths.shape}")
    if impl == "pallas":
        return _latent_pallas(q_c, q_r, pool, tables, lengths,
                              float(sm_scale), should_interpret(), name)
    return _latent_reference(q_c, q_r, pool, tables, lengths, sm_scale)


def _latent_reference(q_c, q_r, pool, tables, lengths, sm_scale):
    S, K, _, rank = q_c.shape
    T = pool.shape[1]
    N = tables.shape[1] * T
    rows = pool[tables].reshape(S, N, -1)
    c, kr = rows[..., :rank], rows[..., rank:rank + q_r.shape[3]]
    score = lambda q, keys: jnp.einsum(
        "skhr,snr->shkn", q.astype(keys.dtype), keys,
        preferred_element_type=jnp.float32)
    s = (score(q_c, c) + score(q_r, kr)) * sm_scale
    qpos = lengths[:, None] + jnp.arange(K, dtype=jnp.int32)[None]   # [S,K]
    seen = jnp.arange(N, dtype=jnp.int32)[None, None] <= qpos[:, :, None]
    p = jax.nn.softmax(jnp.where(seen[:, None], s, NEG_INF), axis=-1)
    o = jnp.einsum("shkn,snr->skhr", p.astype(c.dtype), c,
                   preferred_element_type=jnp.float32)
    # a slot that attends nothing saw every score masked alike: zeros, not
    # the mean of whatever its table named
    return jnp.where((lengths + K > 0)[:, None, None, None], o, 0.0).astype(
        q_c.dtype)


def _latent_kernel(lengths_ref, tables_ref,     # scalar prefetch (SMEM)
                   order_ref, n_live_ref,       # the same
                   q_ref,                       # [1, 1, R, width] VMEM
                   pool_ref,                    # [N, T, width] HBM/ANY
                   o_ref,                       # [1, 1, R, rank] VMEM
                   buf_ref,                     # [2, B*T, width] VMEM
                   sems,                        # DMA [2 buffers]
                   first_buf,                   # SMEM [1]
                   m_scr, l_scr, acc_scr,       # [R, 1|1|rank] f32 VMEM
                   *, page_tokens, pages, qk, q_tile, heads, rank,
                   sm_scale):
    """One (slot, cell) of the grid: R = q_tile * heads query rows over the
    slot's blocks 0 .. the cell's last position, each block copied in once
    and met by the cell's rows ``_SUB_ROWS`` at a time. The grid's order,
    the two buffers handed from cell to cell and the walk are
    ``ops.paged_attention._paged_kernel``'s, for ONE row a token: the
    block's rows are the scores' second operand and, in their first ``rank``
    lanes, the values. The blocks that end at or before the cell's first
    position come first and take no mask; a row's position is made only in
    the blocks behind them. ``sm_scale`` is 1 where the caller folded it
    into the query."""
    c, t = pl.program_id(0), pl.program_id(1)
    n_tiles, n_live = pl.num_programs(1), n_live_ref[0]
    s = order_ref[c]
    T, B, R = page_tokens, pages, q_tile * heads
    BT = B * T
    RS = R if R <= _SUB_ROWS else _SUB_ROWS
    G = _IN_FLIGHT if R // RS % _IN_FLIGHT == 0 else 1
    P = tables_ref.shape[1]

    def block_copies(s, b, buf, wait=False):
        """Start (or wait for) the B whole-page copies of block b."""
        for i in range(B):
            j = b * B + i
            if wait:
                pid = 0                  # a wait reads the sizes only
            elif P % B == 0:
                pid = tables_ref[s, j]
            else:                        # past the table's end: page 0
                pid = jnp.where(j < P, tables_ref[s, jnp.minimum(j, P - 1)],
                                0)
            cp = pltpu.make_async_copy(
                pool_ref.at[pid], buf_ref.at[buf, pl.ds(i * T, T)],
                sems.at[buf])
            cp.wait() if wait else cp.start()

    def turns(n, rows, fn):
        """``fn(r0)`` at the cell's first ``n`` multiples of ``rows``."""
        if R == RS:   # the step's one group: no loop, as it was
            fn(0)
        else:
            lax.fori_loop(
                0, n, lambda i, _: fn(pl.multiple_of(i * rows, rows)), None)

    @pl.when(jnp.logical_and(c == 0, t == 0))
    def _():
        first_buf[0] = 0

        @pl.when(n_live > 0)
        def _():
            block_copies(s, 0, 0)

    base = first_buf[0]
    first = lengths_ref[s] + t * q_tile          # the cell's first position
    real = jnp.minimum(q_tile, qk - t * q_tile)  # its tokens of the call's
    nb = jnp.clip(lax.div(first + real + BT - 1, jnp.int32(BT)), 1,
                  -(-P // B))
    nb = jnp.where(c < n_live, nb, 0)
    # blocks whose last position, (b + 1) * BT - 1, every row may see
    n_free = jnp.clip(lax.div(first + 1, jnp.int32(BT)), 0, nb)
    # turns of the inner loop, G groups each, that hold one of its rows
    n_turns = lax.div(real * heads + G * RS - 1, jnp.int32(G * RS))
    c_next = jnp.where(t + 1 == n_tiles, c + 1, c)
    s_next = order_ref[jnp.minimum(c_next, pl.num_programs(0) - 1)]

    def clear(r0):
        rows = pl.ds(r0, RS)
        m_scr[rows] = jnp.full((RS, 1), NEG_INF, jnp.float32)
        l_scr[rows] = jnp.zeros((RS, 1), jnp.float32)
        acc_scr[rows] = jnp.zeros((RS, rank), jnp.float32)

    turns(R // RS, RS, clear)

    def body(b, _):
        buf = lax.rem(base + b, 2)
        more = b + 1 < nb

        @pl.when(jnp.logical_or(more, c_next < n_live))
        def _():
            block_copies(jnp.where(more, s, s_next),
                         jnp.where(more, b + 1, 0), 1 - buf)

        block_copies(s, b, buf, wait=True)

        def update(r0, masked):
            """Rows [r0, r0 + G * RS) against this block, G groups of RS
            side by side: a group is a chain (scores, softmax, product with
            the latents) whose matrix unit waits for its softmax, and the
            groups' chains stand in ONE basic block, phase by phase, so one
            group's softmax lies under another's products."""
            rows = [pl.ds(r0 + g * RS, RS) for g in range(G)]
            keys, latents = buf_ref[buf], buf_ref[buf, :, :rank]
            ss = [lax.dot_general(
                q_ref[0, 0, r], keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) for r in rows]
            if sm_scale != 1.0:
                ss = [s_ * sm_scale for s_ in ss]
            if masked:
                kpos = b * BT + lax.broadcasted_iota(jnp.int32, (1, BT), 1)
                # row r = i * heads + h is query token i of the cell
                row = lax.broadcasted_iota(jnp.int32, (RS, 1), 0)
                ss = [jnp.where(
                    kpos <= first + (r0 + g * RS + row) // heads, s_, NEG_INF)
                    for g, s_ in enumerate(ss)]
            ms = [m_scr[r] for r in rows]
            m_news = [jnp.maximum(m, jnp.max(s_, axis=-1, keepdims=True))
                      for m, s_ in zip(ms, ss)]
            alphas = [jnp.exp(m - m_new) for m, m_new in zip(ms, m_news)]
            prs = [jnp.exp(s_ - m_new) for s_, m_new in zip(ss, m_news)]
            ls = [l_scr[r] * alpha + jnp.sum(pr, axis=-1, keepdims=True)
                  for r, alpha, pr in zip(rows, alphas, prs)]
            accs = [acc_scr[r] * alpha + lax.dot_general(
                pr.astype(latents.dtype), latents, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
                for r, alpha, pr in zip(rows, alphas, prs)]
            for r, m_new, l, acc in zip(rows, m_news, ls, accs):
                m_scr[r], l_scr[r], acc_scr[r] = m_new, l, acc

        if R == RS:   # the step: a mask of ``heads`` rows is not worth a path
            update(0, True)
        else:
            @pl.when(b < n_free)
            def _():
                turns(n_turns, G * RS, functools.partial(update, masked=False))

            @pl.when(b >= n_free)
            def _():
                turns(n_turns, G * RS, functools.partial(update, masked=True))

    lax.fori_loop(0, nb, body, None)
    first_buf[0] = lax.rem(base + nb, 2)

    def finish(r0):
        rows = pl.ds(r0, RS)
        l = l_scr[rows]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0, rows] = (acc_scr[rows] / l).astype(o_ref.dtype)

    turns(R // RS, RS, finish)


# jitted on its own: a program calls the op once a layer with the same
# shapes, and the kernel's body is then traced and lowered once
@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret", "name"))
def _latent_pallas(q_c, q_r, pool, tables, lengths, sm_scale, interpret,
                   name="latent_attention"):
    S, K, H, rank = q_c.shape
    T, W, P = pool.shape[1], pool.shape[2], tables.shape[1]
    B, q_tile = latent_tiles(K, H, T, P, W, pool.dtype.itemsize)
    n_tiles = -(-K // q_tile)
    R = q_tile * H
    q = join(q_c, q_r)
    if K > 1:   # once a call, not once a score: the step's rows are as few
        q, sm_scale = q * sm_scale, 1.0
    # cell-major rows [S, cells, R, width]: row i * H + h, a pool row's lanes
    q = jnp.pad(q.astype(pool.dtype),
                ((0, 0), (0, n_tiles * q_tile - K), (0, 0), (0, 0)))
    q = q.reshape(S, n_tiles, R, W)
    kernel = functools.partial(_latent_kernel, page_tokens=T, pages=B, qk=K,
                               q_tile=q_tile, heads=H, rank=rank,
                               sm_scale=sm_scale)
    live = lengths + K > 0
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    bufs = [pltpu.VMEM((2, B * T, W), pool.dtype)]
    stats = [pltpu.VMEM((R, width), jnp.float32) for width in (1, 1, rank)]
    blocks = [(1, 1, R, W), (1, 1, R, rank)]
    vmem = (sum(_vmem_bytes(a.shape, a.dtype) for a in bufs + stats)
            + 2 * (_vmem_bytes(blocks[0], pool.dtype)
                   + _vmem_bytes(blocks[1], q_c.dtype))
            + 4 * _IN_FLIGHT * _vmem_bytes((min(R, _SUB_ROWS), B * T),
                                           jnp.float32))

    def cell(c, t, lengths_ref, tables_ref, order_ref, n_live_ref):
        return order_ref[c], t, 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(S, n_tiles),
        in_specs=[
            pl.BlockSpec(blocks[0], cell),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(blocks[1], cell),
        scratch_shapes=bufs + [pltpu.SemaphoreType.DMA((2,)),
                               pltpu.SMEM((1,), jnp.int32)] + stats,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, n_tiles, R, rank), q_c.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(vmem + vmem // 2, _VMEM_LIMIT)),
        name=name,
        interpret=interpret,
    )(lengths.astype(jnp.int32), tables.astype(jnp.int32), order,
      jnp.sum(live, dtype=jnp.int32)[None], q, pool)
    return out.reshape(S, n_tiles * q_tile, H, rank)[:, :K]
