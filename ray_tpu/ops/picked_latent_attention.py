"""Latent attention over the tokens a learned indexer PICKS, straight against
the pages (DeepSeek-Sparse-Attention inside multi-head latent attention: the
'indexed_latent_attention' mixer of models/transformer.py).

A layer of the kind keeps TWO rows a token under one page table: the latent
row of ``ops.latent_attention`` (``join``: the normed latent, the one rotated
key every head shares, zeros up to whole 128-lane tiles) and the index key's
row of ``ops.indexed_attention`` (``index_row``). A query at position ``t``
scores its whole context with its index heads, picks the ``topk`` best tokens
(all of them while ``t + 1 <= topk``) and attends THOSE latents, absorbed:

  1. ``index_scores``, ``select``, ``chosen``: ``ops.indexed_attention``'s,
     AS THEY ARE (the kernels ``index_score`` and ``indexed_select``, the
     rule of the choice and its ties). Shared with the K/V form.
  2. ``picked_rows``: a query's chosen positions in ascending order, without
     a sort — the context in blocks of 128 lanes, a slot's block found by
     counting the blocks that end before it, its lane in the block by the
     block's prefix sums (a triangular product a block, then a one-hot
     product a slot: no gather). NOT shared: the K/V form's chunk never
     compacts (its kernel walks the context and rebuilds the choice a tile),
     and its step sorts 8 rows.
  3. the rows brought together. A CHUNK's queries all belong to one slot,
     whose whole context is small beside what they pick out of it (512 x
     2048 rows of a 40k context): the chunk kernel copies the slot's pages
     up to the chunk's last position into a VMEM scratch ONCE, in logical
     order through the slot's row of ``tables`` — so a chosen POSITION is
     its row there, and no page is looked up a row — and then moves a
     query's chosen rows into its run by VECTOR LOADS at a dynamic sublane
     (``_move_rows``: 3.8 ns a row on the v5e): no copy descriptor a row,
     which is what XLA's row gather and a DMA a row both pay (10-25 ns a
     row whatever the row's bytes; PERF.md 6, PR 61 and PR 62). A context
     longer than the scratch is walked in segments (``_segments``: from
     the VMEM budget and the row's bytes), a call a segment, the softmax's
     statistics merged behind them. A STEP's rows belong to a slot each: copying a
     slot's context (70 MB at 55k) to move 2048 rows is 0.09 ms of
     bandwidth against 0.03 ms of gather, so the step keeps ONE XLA gather
     of ``topk`` pool rows a query (position -> page and offset through
     the table), as the 'reference' form of either does. The rule is in
     the shapes — rows a slot against context bytes a slot — not in a
     model's name: ``S == 1`` gathers, ``S > 1`` copies.
  4. the attention (``picked_latent_step_attention`` for one query a slot,
     over its gathered run; ``picked_latent_chunk_attention`` for a
     chunk's, behind the move, in the same grid cell): one grid cell a
     query token, whose H heads are the rows of ONE product ``[H, width] x
     [width, topk]``, a float32 softmax with the padding of a short choice
     masked, and ONE product ``[H, topk] x [topk, rank]``. The absorbed
     form is multi-QUERY attention, so the one run serves every head: at
     128 heads, rank 512 and rope 64 that is 242 operations a byte of the
     run, the v5e's knee. The dense walk of ``ops.latent_attention`` is no
     fallback: its time follows the context, this one's the choice.

A query whose context is no longer than ``topk`` attends all of it, and the
result equals ``ops.latent_attention``'s there (tests/test_deepseek_v32.py).
Mask semantics and the pools' invariants are those two modules'. What a call
must do at least (``perfbench/lib/picked_work.py`` counts it): a chosen
(query, key, head) costs ``2 (rank + rope) + 2 rank`` operations, a chosen
row ``(rank + rope) x itemsize`` bytes once a query.
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
from ray_tpu.ops.indexed_attention import (IndexerSizes, chosen, index_scores,
                                           select)
from ray_tpu.ops.latent_attention import _VMEM_LIMIT, join
from ray_tpu.ops.paged_attention import _LANES, NEG_INF, PAGED_ATTN_IMPLS

# query tokens whose chosen rows are GATHERED at once (the step's, and the
# 'reference' form's): 64 x 2048 x 1,280 B = 168 MB at the published sizes
_QUERY_BLOCK = 64
# chosen rows the chunk kernel joins into ONE tile of its run before the
# tile is stored: a bf16 tile's sixteen sublane pairs
_GROUP = 16
# page copies of a slot's context in flight at once
_COPIES = 32


def picked_rows(take, width: int):
    """take [R, context] bool (a multiple of 128 lanes; at most ``width`` set
    a row) -> (positions [R, width] int32, each row's chosen positions in
    ascending order and 0 behind them, how many [R] int32)."""
    R, ctx = take.shape
    nb = ctx // _LANES
    blocks = take.reshape(R, nb, _LANES)
    n = blocks.sum(axis=-1, dtype=jnp.int32)                     # [R, nb]
    ends = jnp.cumsum(n, axis=-1)
    total = ends[:, -1]
    slot = jnp.arange(width, dtype=jnp.int32)
    # the blocks that end at or before a slot lie wholly before its token
    before = ends[:, None, :] <= slot[None, :, None]         # [R, width, nb]
    block = jnp.minimum(before.sum(axis=-1, dtype=jnp.int32), nb - 1)
    rank = slot[None] - jnp.where(before, n[:, None, :], 0).sum(
        axis=-1, dtype=jnp.int32)                  # the slot's place in it
    # inclusive prefix sums of every block's bits (counts to 128, exact in
    # bf16), and a slot's block's row of them by a ONE-HOT product: XLA's
    # gather of those rows cost what the gather of the latent rows costs
    # (PERF.md 6, PR 61)
    upper = (jnp.arange(_LANES)[:, None] <= jnp.arange(_LANES)[None]).astype(
        jnp.bfloat16)
    sums = jnp.einsum("rbl,lm->rbm", blocks.astype(jnp.bfloat16), upper,
                      preferred_element_type=jnp.float32).astype(jnp.bfloat16)
    own = (block[..., None] == jnp.arange(nb, dtype=jnp.int32)).astype(
        jnp.bfloat16)
    prefix = jnp.einsum("rsb,rbm->rsm", own, sums,
                        preferred_element_type=jnp.float32)
    lane = (prefix <= rank[..., None].astype(jnp.float32)).sum(
        axis=-1, dtype=jnp.int32)
    held = slot[None] < total[:, None]
    return jnp.where(held, block * _LANES + jnp.minimum(lane, _LANES - 1),
                     0), total


def _scores_and_values(q, run, first, n, rank):
    """q [H, W] (scaled) over a run [width, W] of chosen rows, of which
    rows ``first`` (None: 0) .. ``first + n - 1`` are real -> (the
    probabilities' product with the values [H, rank] float32, unnormalised; the scores'
    maxima and the probabilities' sums [H, 1])."""
    s = lax.dot_general(q, run, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    j = lax.broadcasted_iota(jnp.int32, (1, s.shape[1]), 1)
    real = j < n if first is None else jnp.logical_and(
        j >= first, j < first + n)
    s = jnp.where(real, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    o = lax.dot_general(p.astype(run.dtype), run[:, :rank],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
    return o, m, jnp.sum(p, axis=-1, keepdims=True)


def _picked_kernel(count_ref, q_ref, run_ref, o_ref, *, rank):
    """One query token: q [H, W] (scaled) over its run [width, W] of chosen
    rows, of which the first ``count_ref[i]`` are real."""
    n = count_ref[pl.program_id(0)]
    o, _, l = _scores_and_values(q_ref[...], run_ref[...], None, n, rank)
    o_ref[...] = jnp.where(n > 0, o / l, 0.0).astype(o_ref.dtype)


def _attend_pallas(q, run, count, rank, interpret, name):
    """q [Q, H, W], run [Q, width, W], count [Q] -> o' [Q, H, rank]."""
    Q, H, W = q.shape
    width = run.shape[1]
    cell = lambda i, count: (i, 0, 0)
    return pl.pallas_call(
        functools.partial(_picked_kernel, rank=rank),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(Q,),
            in_specs=[pl.BlockSpec((None, H, W), cell),
                      pl.BlockSpec((None, width, W), cell)],
            out_specs=pl.BlockSpec((None, H, rank), cell)),
        out_shape=jax.ShapeDtypeStruct((Q, H, rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=64 << 20),
        name=name, interpret=interpret,
    )(count, q, run)


def _move_rows(ctx_ref, at_ref, odd_ref, run_ref, g):
    """Rows ``g * _GROUP`` .. of the run: the context's rows that ``at_ref``
    [1, width] (SMEM) names. A row is one vector load at a dynamic sublane
    and a select into its sublane of the group's tile; the tile is stored
    whole. Mosaic takes such a load of a 32-bit array and not of a packed
    one ("cannot statically prove that index in dimension 0 is a multiple
    of 8"), so a bf16 context is read as uint32 [rows / 2, W] — a PAIR of
    rows a sublane, the even one's bits below: ``at_ref`` then names the
    pair (position >> 1), and ``odd_ref`` [1, width / _GROUP] holds the
    group's parities, a bit a row, by which the tile takes each pair's
    upper or lower half ONCE, on the vector unit; the half on top is the
    row's float32 value. The loop is bound by the scalar unit and the
    sublane broadcasts about alike (a row: its position's ``sld``, its
    address ``(i >> 2) * 20 + (i & 3)``, five ``vperm.slane``): 55 bundles
    for 16 rows where the shift made a row from its own parity took 92
    (PERF.md 6, PR 62)."""
    packed = odd_ref is not None
    rows = ctx_ref.bitcast(jnp.uint32) if packed else ctx_ref
    W = run_ref.shape[1]
    sublane = lax.broadcasted_iota(jnp.int32, (_GROUP, W), 0)
    tile = jnp.zeros((_GROUP, W), rows.dtype)
    for k in range(_GROUP):
        row = rows[pl.ds(at_ref[0, g * _GROUP + k], 1), :]
        tile = jnp.where(sublane == k, jnp.broadcast_to(row, tile.shape),
                         tile)
    if packed:
        upper = ((odd_ref[0, g] >> sublane) & 1) == 1
        tile = pltpu.bitcast(
            jnp.where(upper, tile & jnp.uint32(0xFFFF0000), tile << 16),
            jnp.float32)
    run_ref[pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP), :] = (
        tile.astype(run_ref.dtype))


def _chunk_kernel(last_ref, first_ref, count_ref, tables_ref,  # prefetch
                  at_ref,                   # [1, width] int32 SMEM
                  *refs, rank, lo, packed, stats):
    """One (slot b, query i of its S) of the grid, over the context's
    segment that starts at position ``lo``: at the slot's first query its
    pages of the segment, up to the chunk's last position ``last_ref[b]``,
    are copied into ``ctx`` in logical order; every query then moves the
    rows ``at_ref`` names (positions less ``lo``; of a ``packed`` context
    the pairs, their parities the next input: ``_move_rows``) into ``run``
    and attends rows ``first`` .. ``first + count - 1`` of it, its choice's
    share in this segment. ALL of the run's rows are moved each query (a
    slot behind the count names row 0, as the gather did): nothing of an
    earlier query, whose rows may be anything if it was padding, is left
    for a zero probability to meet. With ``stats`` the cell also hands out
    its maxima and sums, for the merge over segments."""
    odd_ref = refs[0] if packed else None  # [1, width / _GROUP] SMEM
    q_ref, pool_ref = refs[packed:packed + 2]  # [H, W] VMEM, [N, T, W] ANY
    out, (ctx, run, sem) = refs[packed + 2:-3], refs[-3:]
    b, i = pl.program_id(0), pl.program_id(1)
    T = pool_ref.shape[1]
    pages, width = ctx.shape[0] // T, run.shape[0]
    row = b * pl.num_programs(1) + i
    first, n = first_ref[row], count_ref[row]

    @pl.when(i == 0)
    def _():
        reach = jnp.clip(lax.div(last_ref[b], jnp.int32(T)) + 1 - lo // T,
                         0, pages)

        def copy(k):
            return pltpu.make_async_copy(
                pool_ref.at[tables_ref[b, lo // T + k]],
                ctx.at[pl.ds(pl.multiple_of(k * T, T), T)], sem)

        def start(k, _):
            copy(k).start()

            @pl.when(k >= _COPIES)
            def _():
                copy(0).wait()        # a wait reads the sizes only

        lax.fori_loop(0, reach, start, None)
        lax.fori_loop(0, jnp.minimum(reach, _COPIES),
                      lambda k, _: copy(0).wait(), None)

    @pl.when(n > 0)
    def _():
        lax.fori_loop(0, width // _GROUP,
                      lambda g, _: _move_rows(ctx, at_ref, odd_ref, run, g),
                      None)

    o, m, l = _scores_and_values(q_ref[...], run[...], first, n, rank)
    out[0][...] = jnp.where(n > 0, o / l, 0.0).astype(out[0].dtype)
    if stats:
        out[1][...] = jnp.where(n > 0, m, NEG_INF)
        out[2][...] = jnp.where(n > 0, l, 0.0)


def _segments(context: int, page_tokens: int, width: int, heads: int,
              row_bytes: int, budget: int):
    """(segments, rows a segment, the kernel's VMEM bytes) for a context of
    ``context`` rows of ``row_bytes``: as few equal segments, of whole
    pages and whole tiles, as leave ``budget`` room for the run, the query's
    and the output's pipelined blocks and the scores' float32 tiles (four:
    scores, probabilities, their bf16 copy and the compiler's own)."""
    fixed = (width * row_bytes + 4 * heads * row_bytes
             + 4 * heads * width * 4)
    unit = math.lcm(page_tokens, _GROUP)
    n = max(-(-context * row_bytes // max(budget - fixed, row_bytes)), 1)
    rows = -(-context // (n * unit)) * unit
    return -(-context // rows), rows, fixed + rows * row_bytes


def _attend_chunk(q, at, count, pool, tables, last, context, rank, interpret,
                  name, budget=_VMEM_LIMIT - (8 << 20)):
    """q [B, S, H, W] (scaled), at [B, S, width] (``picked_rows``'s: a
    query's chosen positions ascending, 0 behind ``count`` [B, S]), straight
    against the pool through ``tables`` [B, P], whose pages up to position
    ``last`` [B] (below 0: none) a slot's queries may name, of a context of
    ``context`` positions (the table's and the garbage page behind it, as
    ``index_scores`` has it) -> o' [B, S, H, rank]. ``budget``: the VMEM
    bytes a call may take."""
    B, S, H, W = q.shape
    T, width = pool.shape[1], at.shape[-1]
    n_seg, rows, vmem = _segments(context, T, width, H,
                                  W * pool.dtype.itemsize, budget)
    tables = jnp.pad(tables.astype(jnp.int32),
                     ((0, 0), (0, n_seg * rows // T - tables.shape[1])))
    stats, packed = n_seg > 1, pool.dtype == jnp.bfloat16
    cell = lambda b, i, *_: (b * S + i, 0, 0)
    scalars = lambda a: pl.BlockSpec((None, 1, a), cell,
                                     memory_space=pltpu.SMEM)
    shapes = [jax.ShapeDtypeStruct(
        (B * S, H, rank), jnp.float32 if stats else q.dtype)]
    specs = [pl.BlockSpec((None, H, rank), cell)]
    if stats:
        shapes += [jax.ShapeDtypeStruct((B * S, H, 1), jnp.float32)] * 2
        specs += [pl.BlockSpec((None, H, 1), cell)] * 2
    parts = []
    for g in range(n_seg):
        lo = g * rows
        if stats:   # the choice's share in this segment: a contiguous run
            held = jnp.arange(width, dtype=jnp.int32) < count[..., None]
            inside = held & (at >= lo) & (at < lo + rows)
            local = jnp.where(inside, at - lo, 0)
            first = (held & (at < lo)).sum(axis=-1, dtype=jnp.int32)
            n = inside.sum(axis=-1, dtype=jnp.int32)
        else:
            local, first, n = at, jnp.zeros_like(count), count
        named = [local.reshape(B * S, 1, width)]
        if packed:   # the pairs, and their parities a word a group of rows
            named = [named[0] >> 1, (
                (named[0] & 1).reshape(B * S, 1, width // _GROUP, _GROUP)
                << jnp.arange(_GROUP, dtype=jnp.int32)).sum(axis=-1)]
        parts.append(pl.pallas_call(
            functools.partial(_chunk_kernel, rank=rank, lo=lo, packed=packed,
                              stats=stats),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=4, grid=(B, S),
                in_specs=[scalars(a.shape[-1]) for a in named] + [
                    pl.BlockSpec((None, H, W), cell),
                    pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=specs,
                scratch_shapes=[pltpu.VMEM((rows, W), pool.dtype),
                                pltpu.VMEM((width, W), pool.dtype),
                                pltpu.SemaphoreType.DMA(())]),
            out_shape=shapes,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=min(vmem + (8 << 20), _VMEM_LIMIT)),
            name=name, interpret=interpret,
        )(last.astype(jnp.int32), first.reshape(-1), n.reshape(-1), tables,
          *named, q.reshape(B * S, H, W), pool))
    if not stats:
        return parts[0][0].reshape(B, S, H, rank)
    o, m, l = (jnp.stack(a) for a in zip(*parts))
    weight = l * jnp.exp(m - m.max(axis=0))
    total = weight.sum(axis=0)
    o = (o * weight).sum(axis=0) / jnp.where(total > 0, total, 1.0)
    return o.reshape(B, S, H, rank).astype(q.dtype)


def _attend_reference(q, run, count, rank):
    s = jnp.einsum("qhw,qnw->qhn", q, run,
                   preferred_element_type=jnp.float32)
    real = jnp.arange(run.shape[1])[None, None] < count[:, None, None]
    p = jax.nn.softmax(jnp.where(real, s, NEG_INF), axis=-1)
    o = jnp.einsum("qhn,qnr->qhr", p.astype(run.dtype), run[..., :rank],
                   preferred_element_type=jnp.float32)
    return jnp.where((count > 0)[:, None, None], o, 0.0).astype(q.dtype)


def picked_latent_attention(q_c, q_r, qi, w, pool, ik_pool, tables,
                            positions, lengths, sizes: IndexerSizes, *,
                            sm_scale: float, impl: str = "reference",
                            return_selected: bool = False):
    """q_c [B, S, H, rank] (the key's up-projection folded in) and q_r [B,
    S, H, rope] at ``positions`` [B, S] over the latents each query's
    indexer picks, through its row's page table. qi [B, S, Hi, Di] and w
    [B, S, Hi] float32: the index queries and their weights; pool: [N, T,
    width] (``ops.latent_attention.join``'s rows); ik_pool: [N, T, W]
    (``ops.indexed_attention.index_row``'s), both already written for the
    rows' own tokens; tables: [B, P]; lengths: [B] as ``paged_attention``
    takes them (a row whose window lies before position 0 attends nothing
    and returns zeros). ``impl``: what the last step runs as ('reference' |
    'pallas'; the indexer's two kernels run either way, interpreted off a
    TPU). Returns o' [B, S, H, rank], and with ``return_selected`` the
    choice, bool [B, S, context]."""
    if impl not in PAGED_ATTN_IMPLS:
        raise ValueError(
            f"unknown picked latent attention impl {impl!r}; expected one "
            f"of {list(PAGED_ATTN_IMPLS)}")
    return _attention(q_c, q_r, qi, w, pool, ik_pool, tables, positions,
                      lengths, sizes, float(sm_scale), impl, return_selected,
                      should_interpret())


# jitted as the kernels' own wrappers are: a model's layers and a program's
# groups of one shape are traced and lowered ONCE
@functools.partial(jax.jit, static_argnames=(
    "sizes", "sm_scale", "impl", "return_selected", "interpret"))
def _attention(q_c, q_r, qi, w, pool, ik_pool, tables, positions, lengths,
               sizes, sm_scale, impl, return_selected, interpret):
    B, S, H, rank = q_c.shape
    T, W = pool.shape[1:]
    live = lengths + S > 0
    scores = index_scores(qi, w, ik_pool, tables, positions, interpret)
    ctx = scores.shape[-1]
    # a row that attends nothing asks nothing of the selection (as the K/V
    # form has it), and nothing is taken for it
    tau, bound = select(scores, jnp.where(live[:, None], positions, 0),
                        sizes.topk, interpret)
    take = chosen(scores, positions[..., None], tau[..., None],
                  bound[..., None]) & live[:, None, None]
    width = -(-min(sizes.topk, ctx) // _LANES) * _LANES
    q = (join(q_c, q_r).astype(jnp.float32) * sm_scale).astype(pool.dtype)
    name = ("picked_latent_step_attention" if S == 1
            else "picked_latent_chunk_attention")
    rows = B * S
    block = min(_QUERY_BLOCK, rows)
    n = -(-rows // block)
    pad = lambda a: jnp.pad(a.reshape(rows, *a.shape[2:]), (
        (0, n * block - rows),) + ((0, 0),) * (a.ndim - 2)).reshape(
            n, block, *a.shape[2:])
    def blocked(fn, *a):
        """``fn`` over [B, S, ...] arrays a block of queries at a time: the
        compaction's one-hot products and the gather's run are temporaries
        of ``block`` queries whatever the chunk."""
        a = tuple(map(pad, a))
        out = (jax.tree.map(lambda o: o[None], fn(*(x[0] for x in a)))
               if n == 1 else lax.map(lambda args: fn(*args), a))
        return jax.tree.map(
            lambda o: o.reshape(n * block, *o.shape[2:])[:rows].reshape(
                B, S, *o.shape[2:]), out)

    # rows a slot against context bytes a slot (the docstring's step 3); the
    # kernel moves rows of 32 bits and of bf16
    if S > 1 and impl == "pallas" and (pool.dtype == jnp.bfloat16
                                       or pool.dtype.itemsize == 4):
        at, count = blocked(lambda take: picked_rows(take, width), take)
        o = _attend_chunk(q, at, count, pool, tables,
                          jnp.where(live, positions[:, -1], -1), ctx, rank,
                          interpret, name)
    else:
        flat = pool.reshape(-1, W)

        def attend(q, take, slot):
            at, count = picked_rows(take, width)
            pages = jnp.take_along_axis(tables[slot], jnp.minimum(
                at // T, tables.shape[1] - 1), axis=1)
            run = flat[jnp.where(jnp.arange(width)[None] < count[:, None],
                                 pages * T + at % T, 0)]   # [block, width, W]
            if impl == "pallas":
                return _attend_pallas(q, run, count, rank, interpret, name)
            return _attend_reference(q, run, count, rank)

        o = blocked(attend, q, take, jnp.broadcast_to(
            jnp.arange(B, dtype=jnp.int32)[:, None], (B, S)))
    o = o.astype(q_c.dtype)
    return (o, take) if return_selected else o
