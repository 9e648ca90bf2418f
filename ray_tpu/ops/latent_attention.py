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
    tiles of query tokens x H rows), one grid cell a (slot, query tile). The
    walk is ``ops.paged_attention``'s: page tables and cursors in SMEM, the
    pool in HBM, a block of pages copied page by page into one of two VMEM
    buffers while the block before it is multiplied (the query is padded
    with zeros to the row's width, so the scores are one product), only the
    blocks up to the tile's last position, the slots that attend something
    first in the grid and the next cell's first block started behind a
    cell's last. Tile sizes are that module's ``tile_sizes`` over the row. It runs under the ``name`` the caller gives (a trace tells the
    step's kernel from the chunk's).
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


def latent_tiles(qk: int, heads: int, page_tokens: int, pages_per_slot: int,
                 width: int, itemsize: int):
    """(pages a block, query tokens a tile) of a K = ``qk`` call: the paged
    kernel's rule (``ops.paged_attention.tile_sizes``) for one row of
    ``width`` lanes a token that ``heads`` query rows share."""
    return tile_sizes(qk, heads, page_tokens, pages_per_slot,
                      width * itemsize // 2)


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
    """One (slot, query tile) cell: R = q_tile * heads query rows over the
    slot's blocks 0 .. the tile's last position. The grid's order, the two
    buffers handed from cell to cell and the walk are
    ``ops.paged_attention._paged_kernel``'s, for ONE row a token: the
    block's rows are the scores' second operand and, in their first ``rank``
    lanes, the values."""
    c, t = pl.program_id(0), pl.program_id(1)
    n_tiles, n_live = pl.num_programs(1), n_live_ref[0]
    s = order_ref[c]
    T, B, R = page_tokens, pages, q_tile * heads
    BT = B * T
    RS = R if R <= _SUB_ROWS else _SUB_ROWS
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

    @pl.when(jnp.logical_and(c == 0, t == 0))
    def _():
        first_buf[0] = 0

        @pl.when(n_live > 0)
        def _():
            block_copies(s, 0, 0)

    base = first_buf[0]
    upto = lengths_ref[s] + jnp.minimum((t + 1) * q_tile, qk)
    nb = jnp.clip(lax.div(upto + BT - 1, jnp.int32(BT)), 1, -(-P // B))
    nb = jnp.where(c < n_live, nb, 0)
    c_next = jnp.where(t + 1 == n_tiles, c + 1, c)
    s_next = order_ref[jnp.minimum(c_next, pl.num_programs(0) - 1)]

    m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
    l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
    acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    def body(b, _):
        buf = lax.rem(base + b, 2)
        more = b + 1 < nb

        @pl.when(jnp.logical_or(more, c_next < n_live))
        def _():
            block_copies(jnp.where(more, s, s_next),
                         jnp.where(more, b + 1, 0), 1 - buf)

        block_copies(s, b, buf, wait=True)
        kpos = b * BT + lax.broadcasted_iota(jnp.int32, (1, BT), 1)

        def update(r0):
            """Rows [r0, r0 + RS) against this block."""
            rows = pl.ds(r0, RS)
            s_ = lax.dot_general(
                q_ref[0, 0, rows], buf_ref[buf], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            latents = buf_ref[buf, :, :rank]
            # row r = i * heads + h is query token i of the tile
            row_pos = lengths_ref[s] + t * q_tile + (
                r0 + lax.broadcasted_iota(jnp.int32, (RS, 1), 0)) // heads
            s_ = jnp.where(kpos <= row_pos, s_ * sm_scale, NEG_INF)
            m = m_scr[rows]
            m_new = jnp.maximum(m, jnp.max(s_, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            pr = jnp.exp(s_ - m_new)
            l_scr[rows] = (l_scr[rows] * alpha
                           + jnp.sum(pr, axis=-1, keepdims=True))
            acc_scr[rows] = acc_scr[rows] * alpha + lax.dot_general(
                pr.astype(latents.dtype), latents, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[rows] = m_new

        if R == RS:
            update(0)
        else:
            lax.fori_loop(0, R // RS, lambda i, _: update(
                pl.multiple_of(i * RS, RS)), None)

    lax.fori_loop(0, nb, body, None)
    first_buf[0] = lax.rem(base + nb, 2)
    l = l_scr[...]
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


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
    # tile-major rows [S, tiles, R, width]: row i * H + h, a pool row's lanes
    q = jnp.pad(join(q_c, q_r).astype(pool.dtype),
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
            + 4 * _vmem_bytes((min(R, _SUB_ROWS), B * T), jnp.float32))

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
            vmem_limit_bytes=vmem + vmem // 2),
        name=name,
        interpret=interpret,
    )(lengths.astype(jnp.int32), tables.astype(jnp.int32), order,
      jnp.sum(live, dtype=jnp.int32)[None], q, pool)
    return out.reshape(S, n_tiles * q_tile, H, rank)[:, :K]
