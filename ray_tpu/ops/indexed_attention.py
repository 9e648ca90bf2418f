"""Token-selected sparse attention over the paged K/V pool (the
DeepSeek-Sparse-Attention indexer of the ``indexed_attention`` mixer; its
scoring and selection serve the latent pool's form too, see below).

A query attends ``topk`` single TOKENS of its context, picked by a learned
scorer that keeps a cache of its own: beside keys and values a layer keeps
one INDEX KEY a token (``index_dim`` values, one head), in pages of the same
table, a row of whole 128-lane tiles a token (``index_row``: the key, then
zeros, which change no product — a row the chip holds without padding and
writes in place, where a 64-lane row made every write a relayout of the
whole pool). No program copies a pool. For the query at position ``t``, with
its ``Hi`` index queries ``qI`` and their weights ``w`` (already scaled):

  1. ``index_scores``: ``I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])``
     over the slot's paged index keys, float32 (the kernel ``index_score``;
     key tiles wholly behind a tile of queries are skipped).
  2. ``select``: every ``s <= t`` where ``t + 1 <= topk``, else the ``topk``
     positions of largest ``I[t, s]``, ties to the lower ``s`` — EXACT, and
     without a sort: the kernel ``indexed_select`` finds a row's ``topk``-th
     largest score by counting passes over the row in fast memory (a binary
     search over the 32 bits of the scores' order-preserving integer form),
     then how many of the scores EQUAL to it belong, by index. A tile of
     rows copies in and walks the lanes up to its own last position only
     (``select_lanes``), and its passes stop once every row's count is
     EXACTLY ``topk``: the choice is decided, and one pass more reads the
     cut off it. What it hands on is two numbers a query, the cut ``tau``
     and the index bound ``M``: ``s`` is chosen iff ``s <= t`` and (``I >
     tau`` or (``I == tau`` and ``s < M``)) — ``chosen`` — so no mask a
     (query, token) is ever written.
  3. attention over the choice. A decode step (one query a row) sorts its
     chosen positions to the front, gathers their ``topk`` rows of K and V
     out of the pages and runs the paged kernel over that compact run
     (``indexed_step_attention``): it reads the chosen tokens and no other.
     A chunk (many queries, each with its own choice) runs a flash-style
     kernel over the slot's context that rebuilds the choice of a (query
     tile, key tile) from the scores and the two numbers
     (``indexed_chunk_attention``): its time follows the context, not the
     choice (PERF.md 7). It takes the K and V pools and the page table: a
     key tile's pages are copied into fast memory by the kernel, the next
     tile's while this one is computed on, once a CELL of query tiles
     (``cell_tokens``), each of which meets the tile while it is there. No
     gathered copy of a slot's K and V exists.

SHARED WITH THE LATENT FORM (``ops.picked_latent_attention``, the
'indexed_latent_attention' mixer: the same indexer inside multi-head latent
attention, whose pages hold a latent row in place of K and V): steps 1 and 2
as they stand — ``index_scores`` (the kernel ``index_score``), ``select``
(``indexed_select``), ``chosen``, ``index_row`` / ``index_width`` and the
index keys' pool — at whatever sizes ``IndexerSizes`` gives (64 heads of 128
there, 16 of 64 here; how much of an index head is rotated is the LAYER's
business, not these sizes'). NOT shared: step 3. ``indexed_chunk_attention``
and ``indexed_step_attention`` read a K pool and a V pool by K/V head; the
latent form compacts each query's choice, gathers its rows of ONE pool and
attends them under all heads at once (``picked_latent_*_attention``).

The kernels carry those names in a profiler trace and are interpreted off a
TPU. What is chosen is returned on request (``return_selected``), bool [B,
S, context]: a comparison with another implementation has to be made on the
same choice, because top-k is discontinuous.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops._pallas import should_interpret
from ray_tpu.ops.paged_attention import _LANES, NEG_INF, paged_attention

_KEY_TILE = 512      # context tokens of one grid cell of a kernel
_SCORE_TOKENS = 128  # query tokens of one tile of the score kernel
SELECT_ROWS = 8      # query rows of one tile of the selection kernel
_SELECT_LANES = 4096  # context tokens of one segment of its passes' walk,
_FOLD_LANES = 1024    # which a pass folds into so many before it sums them
_LOOK_AFTER, _LOOK_EVERY = 20, 4  # passes before a tile asks: all decided?
_ATTN_ROWS = 2048    # query rows (tokens x heads) of one tile of the chunk,
_CELL_TILES = 8      # of which so many meet a key tile while it is in VMEM
_INT_MIN = -2 ** 31


@dataclasses.dataclass(frozen=True)
class IndexerSizes:
    """A source's ``sa_config`` (or, for the latent form, its flat
    ``index_n_heads`` / ``index_head_dim`` / ``index_topk``: the sizes say
    nothing of what the indexer sits beside, a K/V pool or a latent one).
    ``q_chunk_size`` / ``kv_chunk_size`` are tile sizes of the published
    kernel and change no result: kept so the dict maps whole, read by
    nothing."""

    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    indexer_num_kv_heads: int = 1
    topk: int = 2048
    q_chunk_size: int = 512
    kv_chunk_size: int = 512

    def __post_init__(self):
        if self.indexer_num_kv_heads != 1:
            raise ValueError("the indexer keeps ONE index key a token: "
                             f"indexer_num_kv_heads {self.indexer_num_kv_heads}")
        if self.topk < 1 or self.indexer_head_dim % 2:
            raise ValueError("topk >= 1 and an even indexer_head_dim, got "
                             f"{self.topk}, {self.indexer_head_dim}")

    def attended_tokens(self, t):
        """How many tokens the query at position ``t`` (an int or a NumPy
        array of them) attends: a function of the position alone (the
        scheduler's counters mirror it)."""
        return np.minimum(np.asarray(t) + 1, self.topk)


def chunk_tokens(num_heads: int) -> int:
    """Query tokens of one tile of the chunk's kernel, every head's row of
    each among its ``_ATTN_ROWS``."""
    return max(_ATTN_ROWS // num_heads // 8 * 8, 8)


def cell_tokens(num_heads: int) -> int:
    """Query tokens of one cell of the chunk's kernel: the tiles a key tile
    meets for one copy of its pages (the scheduler's counters mirror it)."""
    return _CELL_TILES * chunk_tokens(num_heads)


def index_width(index_dim: int) -> int:
    """Lanes of a token's index key in the pool: whole 128-lane tiles."""
    return -(-index_dim // _LANES) * _LANES


def index_row(x):
    """An index key (or query) x [..., Di] as the pool holds it: zeros up to
    whole lane tiles, which change no product."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [
        (0, index_width(x.shape[-1]) - x.shape[-1])])


def context_tokens(pages: int, page_tokens: int) -> int:
    """The tokens a table of ``pages`` spans in whole key tiles: the lanes
    of a row of index scores (the scheduler's counters mirror it)."""
    return -(-pages * page_tokens // _KEY_TILE) * _KEY_TILE


def _context(tables, page_tokens: int):
    """A row's table padded to whole key tiles (with the garbage page, whose
    tokens lie behind every position of the row) and the tokens it spans."""
    if _KEY_TILE % page_tokens:
        raise ValueError(f"a key tile of {_KEY_TILE} tokens is whole pages: "
                         f"page_tokens {page_tokens}")
    ctx = context_tokens(tables.shape[1], page_tokens)
    tables = jnp.pad(tables.astype(jnp.int32), ((0, 0), (
        0, ctx // page_tokens - tables.shape[1])))
    return tables, ctx


def _tiles(positions, tokens: int, cell: int = 1):
    """positions [B, S] padded to whole cells of ``cell`` tiles of ``tokens``
    rows (the edge repeated) -> (padded [B, Sp], the key tiles each query
    tile reaches [B, n]: at least one, and none for a tile that is padding
    alone)."""
    B, S = positions.shape
    n = -(-S // (tokens * cell)) * cell
    pos = jnp.pad(positions.astype(jnp.int32),
                  ((0, 0), (0, n * tokens - S)), mode="edge")
    nkt = jnp.maximum(pos.reshape(B, n, tokens).max(axis=-1), 0) // _KEY_TILE
    real = jnp.arange(n, dtype=jnp.int32) * tokens < S
    return pos, jnp.where(real, nkt + 1, 0).astype(jnp.int32)


# ------------------------------------------------------------ index scores


def _score_kernel(nkt_ref, q_ref, w_ref, k_ref, o_ref, *, heads, tokens):
    b, qt, kt = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(kt < nkt_ref[b, qt])
    def _():
        k = k_ref[...]
        total = jnp.zeros(o_ref.shape, jnp.float32)
        for j in range(heads):
            s = lax.dot_general(q_ref[j * tokens:(j + 1) * tokens], k,
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            total += jnp.maximum(s, 0.0) * w_ref[:, j:j + 1]
        o_ref[...] = total


def index_scores(qi, w, ik_pool, tables, positions, interpret: bool):
    """qi [B, S, Hi, Di], w [B, S, Hi] float32, the index keys' pool [N, T,
    W] (``index_row``'s rows) through ``tables`` [B, P] -> I [B, S, context]
    float32 (``context``: the table's tokens in whole key tiles). Entries
    behind a query's position are not all computed: every reader masks by
    position. The rows' index keys are gathered over the table's width for
    the kernel, 256 B a token in whole lane tiles: a kernel that copies
    these thin pages (4 KB) in itself, as the chunk's attention does its
    K and V, lost to the gather at the chunk's shape and at the step's
    (PERF.md 6, PR 60)."""
    B, S, Hi, _ = qi.shape
    W = ik_pool.shape[2]
    tables, ctx = _context(tables, ik_pool.shape[1])
    keys = ik_pool[tables].reshape(B, ctx, W)
    tokens = min(_SCORE_TOKENS, -(-S // 8) * 8)
    pos, nkt = _tiles(positions, tokens)
    n_qt, Sp = nkt.shape[1], pos.shape[1]
    # rows of a tile stand head-major: row j * tokens + i = (head j, token i)
    pad = ((0, 0), (0, Sp - S), (0, 0))
    qr = jnp.pad(qi, pad + ((0, W - qi.shape[3]),)).reshape(
        B, n_qt, tokens, Hi, W).transpose(0, 1, 3, 2, 4).reshape(
        B, n_qt, Hi * tokens, W).astype(ik_pool.dtype)
    wr = jnp.pad(w.astype(jnp.float32), pad).reshape(B, n_qt, tokens, Hi)
    reach = lambda b, qt, kt, nkt: jnp.minimum(kt, nkt[b, qt] - 1)
    out = pl.pallas_call(
        functools.partial(_score_kernel, heads=Hi, tokens=tokens),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, n_qt, ctx // _KEY_TILE),
            in_specs=[
                pl.BlockSpec((None, None, Hi * tokens, W),
                             lambda b, qt, kt, nkt: (b, qt, 0, 0)),
                pl.BlockSpec((None, None, tokens, Hi),
                             lambda b, qt, kt, nkt: (b, qt, 0, 0)),
                pl.BlockSpec((None, _KEY_TILE, W),
                             lambda b, qt, kt, nkt: (
                                 b, reach(b, qt, kt, nkt), 0))],
            out_specs=pl.BlockSpec(
                (None, tokens, _KEY_TILE),
                lambda b, qt, kt, nkt: (b, qt, reach(b, qt, kt, nkt)))),
        out_shape=jax.ShapeDtypeStruct((B, Sp, ctx), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 << 20),
        name="index_score", interpret=interpret,
    )(nkt, qr, wr, keys)
    return out[:, :S]


# --------------------------------------------------------------- selection


def _segment(context: int) -> int:
    """Lanes of one segment of the selection's walk over such a table."""
    return min(_SELECT_LANES, context)


def select_lanes(positions, context: int, xp=jnp):
    """positions [rows] of the selection's query rows, in the order it takes
    them -> the lanes each tile of ``SELECT_ROWS`` of them walks, [tiles]:
    whole segments up to the tile's last position, the table's at most. A
    function of the positions alone: ``select`` prefetches it and the
    scheduler's counters mirror it (``xp``: NumPy there)."""
    tiles = -(-positions.shape[0] // SELECT_ROWS)
    pos = xp.pad(positions, (0, tiles * SELECT_ROWS - positions.shape[0]))
    last = xp.maximum(pos.reshape(tiles, SELECT_ROWS).max(axis=-1), 0)
    W = _segment(context)
    return W * xp.minimum(last // W + 1, -(-context // W))


def _select_kernel(lanes_ref, s_ref, pos_ref, o_ref, s_buf, key_scr, sems, *,
                   topk, index_bits):
    """One tile of rows. Its scores stay in HBM; the segments of lanes up
    to the tile's last position (``lanes_ref``) are copied in — the NEXT
    tile's while this one counts — made into keys, and every pass is a loop
    over those segments: what lies behind the reach is never read."""
    r = pl.program_id(0)
    n_seg, rows, W = key_scr.shape
    ctx = s_ref.shape[1]
    tail = ctx - (n_seg - 1) * W  # lanes of the table's last segment
    first = lambda c: pl.multiple_of(c * W, W)

    def copies(tile, buf, wait=False):
        """Start (or wait for) the copies of ``tile``'s live segments."""
        def one(c, width):
            cp = pltpu.make_async_copy(
                s_ref.at[pl.ds(pl.multiple_of(tile * rows, rows), rows),
                         pl.ds(first(c), width)],
                s_buf.at[buf, c, :, pl.ds(0, width)], sems.at[buf])
            cp.wait() if wait else cp.start()

        def segment(c, _):
            if tail == W:
                one(c, W)
            else:
                pl.when(c < n_seg - 1)(lambda: one(c, W))
                pl.when(c == n_seg - 1)(lambda: one(c, tail))
            return 0

        lax.fori_loop(0, lanes_ref[tile] // W, segment, 0)

    buf = lax.rem(r, 2)
    pl.when(r == 0)(lambda: copies(0, 0))
    pl.when(r + 1 < pl.num_programs(0))(lambda: copies(r + 1, 1 - buf))
    copies(r, buf, wait=True)
    reach = lanes_ref[r] // W
    pos = pos_ref[...]
    # a pass folds a segment's lanes into ``A`` of them, a few wide ops a
    # segment: the kernel's body is traced in every process (PERF.md 2)
    A = math.gcd(W, _FOLD_LANES)
    blocks = [slice(j * A, (j + 1) * A) for j in range(W // A)]

    def walk(segment, start):
        """``segment(c, acc)`` over the live segments, from [rows, A] of
        ``start``."""
        return lax.fori_loop(0, reach, segment,
                             jnp.full((rows, A), start, jnp.int32))

    def sweep(op, start, value):
        """One pass over the live lanes: ``value(keys, first lane)`` of every
        [rows, A] block of keys, folded by ``op`` -> [rows, A]."""
        return walk(lambda c, acc: functools.reduce(op, [
            value(key_scr[c, :, b], first(c) + b.start) for b in blocks],
            acc), start)

    count = lambda hit: jnp.sum(sweep(
        jnp.add, 0, lambda key, at: hit(key, at).astype(jnp.int32)),
        axis=1, keepdims=True)

    # the integers order as the floats do; what lies behind the query is
    # below every score. The pass that makes them counts the keys >= 0
    def make_keys(c, acc):
        s = s_buf[buf, c]
        s = jnp.where(s == 0.0, 0.0, s)         # -0.0 is 0.0: one order
        idx = first(c) + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        bits = lax.bitcast_convert_type(s, jnp.int32)
        key = jnp.where(idx <= pos, jnp.where(
            bits >= 0, bits, bits ^ jnp.int32(0x7FFFFFFF)),
            jnp.int32(_INT_MIN))
        key_scr[c] = key
        return functools.reduce(jnp.add, [
            (key[:, b] >= 0).astype(jnp.int32) for b in blocks], acc)

    have = jnp.sum(walk(make_keys, 0), axis=1, keepdims=True)
    # the largest integer that at least ``topk`` keys reach, bit by bit;
    # ``have``: how many reach it, once a count has said (-1 before)
    cut = jnp.where(have >= topk, jnp.int32(0), jnp.int32(_INT_MIN))
    have = jnp.where(have >= topk, have, -1)
    # a row is decided when EXACTLY ``topk`` keys reach its cut (they are
    # its choice, and no lower bit can change it) or it has fewer tokens
    # than ``topk`` (it takes them all); a tile stops when all its rows are
    few = pos + 1 < topk
    open_rows = lambda have: jnp.any(jnp.logical_not(
        jnp.logical_or(have == topk, few))).astype(jnp.int32)

    def raise_cut(i, state, bit):
        cut, have = state
        higher = cut | jnp.left_shift(jnp.int32(1), bit - i)
        reached = count(lambda key, at: key >= higher)
        return (jnp.where(reached >= topk, higher, cut),
                jnp.where(reached >= topk, reached, have))

    def passes_then_look(state):
        """To ask whether every row is decided costs a few hundred cycles
        (a vector's verdict has to reach the scalar side), and between the
        2048th and 2049th largest of 17k-49k scores the first bit that
        differs lies past the 20th: ``_LOOK_AFTER`` passes, a look, then
        one every ``_LOOK_EVERY`` (PERF.md 6, PR 53)."""
        bit, cut, have, _ = state
        n = jnp.minimum(jnp.where(bit == 30, _LOOK_AFTER, _LOOK_EVERY),
                        bit + 1)
        cut, have = lax.fori_loop(
            0, n, functools.partial(raise_cut, bit=bit), (cut, have))
        return bit - n, cut, have, open_rows(have)

    bit, cut, have, _ = lax.while_loop(
        lambda state: jnp.logical_and(state[0] >= 0, state[3] > 0),
        passes_then_look, (jnp.int32(30), cut, have, open_rows(have)))
    # the least key of a decided row's choice IS its ``topk``-th largest:
    # the cut the remaining passes would have come to
    exact = have == topk
    int_max = jnp.int32(2 ** 31 - 1)
    cut = jnp.where(exact, jnp.min(sweep(
        jnp.minimum, int_max,
        lambda key, at: jnp.where(key >= cut, key, int_max)),
        axis=1, keepdims=True), cut)
    # of the keys EQUAL to the cut, those before index ``bound`` fill the
    # choice. Passes only where a row went through every bit and still has
    # more than ``topk`` keys at or above its cut: more lie ON the cut than
    # it has room for (of any other row every one belongs, and the passes
    # come to every bit set)
    full = jnp.full_like(cut, (1 << index_bits) - 1)
    tied = jnp.logical_and(jnp.logical_not(exact), jnp.logical_not(few))

    def ties():
        on_cut = count(lambda key, at: key == cut)
        need = topk - (have - on_cut)

        def raise_bound(i, bound):
            higher = bound | jnp.left_shift(jnp.int32(1), index_bits - 1 - i)
            fits = count(lambda key, at: jnp.logical_and(
                key == cut, at + lax.broadcasted_iota(
                    jnp.int32, key.shape, 1) < higher)) <= need
            return jnp.where(fits, higher, bound)

        return lax.fori_loop(0, index_bits, raise_bound, jnp.zeros_like(cut))

    ran_ties = jnp.any(tied)
    bound = lax.cond(ran_ties, ties, lambda: full)
    # a row of fewer tokens than ``topk`` takes them all: its cut lies
    # below every score, and its bound where passes over the table's dead
    # lanes, all tied down there, came to
    bound = jnp.where(few, topk if ctx > topk else full, bound)
    tau = lax.bitcast_convert_type(
        jnp.where(cut >= 0, cut, cut ^ jnp.int32(0x7FFFFFFF)), jnp.float32)
    tau = jnp.where(cut == jnp.int32(_INT_MIN), -jnp.inf, tau)
    # the keys, the cuts, the least, the ties
    passes = (32 - bit + jnp.where(ran_ties, 1 + index_bits, 0)).astype(
        jnp.float32)
    lane = lax.broadcasted_iota(jnp.int32, o_ref.shape, 1)
    o_ref[...] = jnp.where(lane == 0, tau, jnp.where(
        lane == 1, bound.astype(jnp.float32), jnp.where(
            lane == 2, passes, 0.0)))


def select(scores, positions, topk: int, interpret: bool,
           passes: bool = False):
    """scores [B, S, context] float32, positions [B, S] -> (tau [B, S]
    float32, bound [B, S] int32): the two numbers ``chosen`` reads; with
    ``passes`` also the passes over its live lanes each row's tile ran, [B,
    S] int32 (tests and ``chip_smoke.py`` ask)."""
    B, S, ctx = scores.shape
    rows = B * S
    padded = -(-rows // SELECT_ROWS) * SELECT_ROWS
    flat = jnp.pad(scores.reshape(rows, ctx), ((0, padded - rows), (0, 0)))
    pos = positions.reshape(rows).astype(jnp.int32)
    W = _segment(ctx)
    n_seg = -(-ctx // W)
    tile = lambda r, lanes: (r, 0)
    out = pl.pallas_call(
        functools.partial(_select_kernel, topk=topk,
                          index_bits=max(ctx.bit_length(), 1)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(padded // SELECT_ROWS,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec((SELECT_ROWS, 1), tile)],
            out_specs=pl.BlockSpec((SELECT_ROWS, 128), tile),
            scratch_shapes=[
                pltpu.VMEM((2, n_seg, SELECT_ROWS, W), jnp.float32),
                pltpu.VMEM((n_seg, SELECT_ROWS, W), jnp.int32),
                pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((padded, 128), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=32 << 20),
        name="indexed_select", interpret=interpret,
    )(select_lanes(pos, ctx), flat,
      jnp.pad(pos, (0, padded - rows))[:, None])
    tau, *counts = (out[:rows, i].reshape(B, S) for i in range(3))
    return (tau, *(c.astype(jnp.int32) for c in counts[:1 + passes]))


def chosen(scores, positions, tau, bound, first=0):
    """THE rule of the choice, wherever it is rebuilt: scores [..., rows,
    keys] of the keys ``first`` .. against the rows' positions, cuts and
    bounds [..., rows, 1] -> bool."""
    scores = scores + 0.0
    idx = first + lax.broadcasted_iota(jnp.int32, scores.shape,
                                       scores.ndim - 1)
    return jnp.logical_and(idx <= positions, jnp.logical_or(
        scores > tau, jnp.logical_and(scores == tau, idx < bound)))


# --------------------------------------------------------- chunk attention


def _tile_copies(tables_ref, pools, bufs, sems, b, kt, slot, wait=False):
    """Start (or wait for) the copies of row ``b``'s key tile ``kt``: its
    pages, out of each pool [N, T, W] into ``slot`` of the pool's buffer [2,
    _KEY_TILE, W]."""
    T = pools[0].shape[1]
    per_tile = _KEY_TILE // T

    def page(i, _):
        # a wait reads the sizes only
        pid = 0 if wait else tables_ref[b, kt * per_tile + i]
        for pool, buf in zip(pools, bufs):
            cp = pltpu.make_async_copy(
                pool.at[pid], buf.at[slot, pl.ds(pl.multiple_of(i * T, T), T)],
                sems.at[slot])
            cp.wait() if wait else cp.start()
        return 0

    lax.fori_loop(0, per_tile, page, 0)


def _chunk_kernel(reach_ref, tables_ref, nkt_ref, q_ref, k_pool, v_pool,
                  s_ref, cut_ref, o_ref, k_buf, v_buf, sems, turn,
                  m_scr, l_scr, acc_scr, *, kv_heads, group, tokens,
                  head_dim, sm_scale):
    """One (row b, cell c of query tiles, key tile kt) step. The rows'
    contexts are read out of their pages through the tables [B, P] (scalar
    prefetch): cell (b, c) meets the key tiles 0 .. ``reach_ref[b, c]`` - 1,
    at least one, each copied page by page into one of two buffers while the
    step before it computes — the same cell's next tile, else the first of
    the next cell or row; ``turn`` (SMEM [1]) hands the buffer from step to
    step, so the grid is walked in order. A tile in VMEM meets every query
    tile of the cell that reaches it, every head; a (query tile, key
    tile)'s choice is rebuilt once from the index scores and serves all
    heads."""
    b, c, kt = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_b, n_c = pl.num_programs(0), pl.num_programs(1)
    n_tiles = q_ref.shape[0]
    copies = functools.partial(_tile_copies, tables_ref, (k_pool, v_pool),
                               (k_buf, v_buf), sems)

    def tiles(fn):
        """``fn(j)`` for every query tile of the cell."""
        def one(j, carry):
            fn(j)
            return carry

        if n_tiles == 1:
            fn(0)
        else:
            lax.fori_loop(0, n_tiles, one, None)

    def clear(j):
        m_scr[j] = jnp.full(m_scr.shape[1:], NEG_INF, jnp.float32)
        l_scr[j] = jnp.zeros(l_scr.shape[1:], jnp.float32)
        acc_scr[j] = jnp.zeros(acc_scr.shape[1:], jnp.float32)

    @pl.when(kt == 0)
    def _():
        tiles(clear)

        @pl.when(jnp.logical_and(b == 0, c == 0))
        def _():
            turn[0] = 0
            copies(0, 0, 0)

    def meet(j, slot):
        cut = cut_ref[j]                                   # [tokens, 128]
        pos = cut[:, 0:1].astype(jnp.int32)
        take = chosen(s_ref[j], pos, cut[:, 1:2],
                      cut[:, 2:3].astype(jnp.int32), first=kt * _KEY_TILE)
        bias = jnp.where(take, 0.0, NEG_INF).astype(jnp.float32)
        bias = jnp.concatenate([bias] * group, axis=0)    # [G * tokens, tk]
        R = group * tokens
        for h in range(kv_heads):
            rows = slice(h * R, (h + 1) * R)
            lanes = slice(h * head_dim, (h + 1) * head_dim)
            s = lax.dot_general(q_ref[j, rows], k_buf[slot, :, lanes],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            s = s * sm_scale + bias
            m = m_scr[j, rows]
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            pr = jnp.exp(s - m_new)
            l_scr[j, rows] = l_scr[j, rows] * alpha + jnp.sum(
                pr, axis=1, keepdims=True)
            acc_scr[j, rows] = acc_scr[j, rows] * alpha + lax.dot_general(
                pr.astype(v_buf.dtype), v_buf[slot, :, lanes],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[j, rows] = m_new

    @pl.when(kt < reach_ref[b, c])
    def _():
        slot = turn[0]
        more = kt + 1 < reach_ref[b, c]
        same_row = jnp.logical_or(more, c + 1 < n_c)

        @pl.when(jnp.logical_or(same_row, b + 1 < n_b))
        def _():
            copies(jnp.where(same_row, b, b + 1), jnp.where(more, kt + 1, 0),
                   1 - slot)

        copies(b, kt, slot, wait=True)
        tiles(lambda j: pl.when(kt < nkt_ref[b, c * n_tiles + j])(
            lambda: meet(j, slot)))
        turn[0] = 1 - slot

    def finish(j):
        l = l_scr[j]
        o_ref[j] = (acc_scr[j] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)

    pl.when(kt == pl.num_programs(2) - 1)(lambda: tiles(finish))


def _chunk_attention(q, k_pool, v_pool, tables, positions, scores, tau,
                     bound, interpret: bool):
    """q [B, S, H, D] at ``positions`` [B, S] over each row's own context,
    read out of its pages through ``tables``, every query over its own
    choice."""
    B, S, H, D = q.shape
    Hkv = k_pool.shape[2] // D
    tables, ctx = _context(tables, k_pool.shape[1])
    tokens = min(chunk_tokens(H), -(-S // 8) * 8)
    C = min(_CELL_TILES, -(-S // tokens))                  # tiles a cell
    pos, nkt = _tiles(positions, tokens, C)
    n_qt, Sp = nkt.shape[1], pos.shape[1]
    R = H * tokens
    # rows of a tile stand head-major: row n * tokens + i = (head n, token i)
    qr = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0), (0, 0))).reshape(
        B, n_qt, tokens, H, D).transpose(0, 1, 3, 2, 4).reshape(
        B, n_qt, R, D).astype(k_pool.dtype)
    edge = lambda a: jnp.pad(a, ((0, 0), (0, Sp - S)), mode="edge")
    cut = jnp.stack([pos.astype(jnp.float32), edge(tau),
                     edge(bound).astype(jnp.float32)], axis=-1)
    cut = jnp.pad(cut, ((0, 0), (0, 0), (0, 125)))        # [B, Sp, 128]
    sc = jnp.pad(scores, ((0, 0), (0, Sp - S), (0, 0)))
    reach = nkt.reshape(B, n_qt // C, C).max(axis=-1)
    cell = lambda b, c, kt, *_: (b, c, 0, 0)
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, kv_heads=Hkv, group=H // Hkv,
                          tokens=tokens, head_dim=D,
                          sm_scale=1.0 / math.sqrt(D)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B, n_qt // C, ctx // _KEY_TILE),
            in_specs=[
                pl.BlockSpec((None, C, R, D), cell),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec((None, C, tokens, _KEY_TILE),
                             lambda b, c, kt, reach, *_: (
                                 b, c, 0, jnp.minimum(kt, reach[b, c] - 1))),
                pl.BlockSpec((None, C, tokens, 128), cell)],
            out_specs=pl.BlockSpec((None, C, R, D), cell),
            scratch_shapes=[
                pltpu.VMEM((2, _KEY_TILE, Hkv * D), k_pool.dtype),
                pltpu.VMEM((2, _KEY_TILE, Hkv * D), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((C, R, 1), jnp.float32),
                pltpu.VMEM((C, R, 1), jnp.float32),
                pltpu.VMEM((C, R, D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, n_qt, R, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=96 << 20),
        name="indexed_chunk_attention", interpret=interpret,
    )(reach, tables, nkt, qr, k_pool, v_pool,
      sc.reshape(B, n_qt, tokens, ctx), cut.reshape(B, n_qt, tokens, 128))
    out = out.reshape(B, n_qt, H, tokens, D).transpose(0, 1, 3, 2, 4)
    return out.reshape(B, Sp, H, D)[:, :S]


# ---------------------------------------------------------- step attention


def _step_attention(q, k_pool, v_pool, tables, live, take, topk: int,
                    impl: str):
    """One query a row: the chosen positions sorted to the front, their rows
    of K and V gathered out of the pages into a run of ``topk`` tokens a
    row, and the paged kernel over that run, whose causal mask cuts behind
    the last chosen token."""
    B = q.shape[0]
    T, HD = k_pool.shape[1:]
    ctx = take.shape[-1]
    width = -(-min(topk, ctx) // T) * T
    idx = jnp.arange(ctx, dtype=jnp.int32)
    order = jnp.sort(jnp.where(take, idx, ctx + idx), axis=-1)[:, :width]
    held = order < ctx
    order = jnp.where(held, order, 0)
    pages = jnp.take_along_axis(tables, jnp.minimum(
        order // T, tables.shape[1] - 1), axis=1)
    rows = jnp.where(held, pages * T + order % T, 0)       # [B, width]
    run = lambda pool: pool.reshape(-1, HD)[rows].reshape(
        B * width // T, T, HD)
    own = jnp.arange(B * width // T, dtype=jnp.int32).reshape(B, width // T)
    lengths = jnp.where(live, held.sum(axis=-1, dtype=jnp.int32) - 1, -1)
    return paged_attention(q, run(k_pool), run(v_pool), own, lengths,
                           impl=impl, name="indexed_step_attention")


# ------------------------------------------------------------------ the op


def indexed_attention(q, qi, w, k_pool, v_pool, ik_pool, tables, positions,
                      lengths, sizes: IndexerSizes, *, impl: str,
                      return_selected: bool = False):
    """Attention of q [B, S, H, D] at ``positions`` [B, S] over the tokens
    each query's indexer picks, through its row's page table. qi [B, S, Hi,
    Di] and w [B, S, Hi] float32: the index queries and their weights;
    k_pool/v_pool: [N, T, Hkv * D]; ik_pool: [N, T, W], the index keys in
    ``index_row``'s rows (all three already written for the rows' own
    tokens); tables: [B, P];
    lengths: [B], as ``paged_attention`` takes them (a row whose window lies
    before position 0 attends nothing). ``impl``: what the paged kernel runs
    as for a step ('reference' | 'pallas'). Returns [B, S, H, D], and with
    ``return_selected`` the choice, bool [B, S, context]."""
    return _attention(q, qi, w, k_pool, v_pool, ik_pool, tables, positions,
                      lengths, sizes, impl, return_selected,
                      should_interpret())


# jitted as the kernels' own wrappers are: a model's layers and a program's
# groups of one shape are traced and lowered ONCE
@functools.partial(jax.jit, static_argnames=(
    "sizes", "impl", "return_selected", "interpret"))
def _attention(q, qi, w, k_pool, v_pool, ik_pool, tables, positions, lengths,
               sizes, impl, return_selected, interpret):
    S = q.shape[1]
    scores = index_scores(qi, w, ik_pool, tables, positions, interpret)
    # a row that attends nothing asks nothing of the selection: it stands
    # at position 0 there, so no tile walks a table for its sake
    tau, bound = select(scores, jnp.where((lengths + S > 0)[:, None],
                                          positions, 0), sizes.topk, interpret)
    take = None
    if S == 1 or return_selected:
        take = chosen(scores, positions[..., None], tau[..., None],
                      bound[..., None])
    if S == 1:
        o = _step_attention(q, k_pool, v_pool, tables, lengths + 1 > 0,
                            take[:, 0], sizes.topk, impl)
    else:
        o = _chunk_attention(q, k_pool, v_pool, tables, positions, scores,
                             tau, bound, interpret)
    return (o, take) if return_selected else o
