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
  3. the rows brought together: position -> page and offset through the
     slot's table -> ONE gather of ``topk`` pool rows a query (XLA's; a block
     of ``_QUERY_BLOCK`` queries at a time, so the temporary stays at
     ``_QUERY_BLOCK x topk x width`` values whatever the chunk).
  4. the kernel (``picked_latent_step_attention`` for one query a slot,
     ``picked_latent_chunk_attention`` for a chunk's): one grid cell a query
     token, whose H heads are the rows of ONE product ``[H, width] x [width,
     topk]``, a float32 softmax with the padding of a short choice masked,
     and ONE product ``[H, topk] x [topk, rank]``. The absorbed form is
     multi-QUERY attention, so the one gathered run serves every head: at
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

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops._pallas import should_interpret
from ray_tpu.ops.indexed_attention import (IndexerSizes, chosen, index_scores,
                                           select)
from ray_tpu.ops.latent_attention import join
from ray_tpu.ops.paged_attention import _LANES, NEG_INF, PAGED_ATTN_IMPLS

# query tokens whose chosen rows are gathered at once: 64 x 2048 x 1,280 B =
# 168 MB at the published sizes
_QUERY_BLOCK = 64


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


def _picked_kernel(count_ref, q_ref, run_ref, o_ref, *, rank):
    """One query token: q [H, W] (scaled) over its run [width, W] of chosen
    rows, of which the first ``count_ref[i]`` are real."""
    n = count_ref[pl.program_id(0)]
    run = run_ref[...]
    s = lax.dot_general(q_ref[...], run, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    s = jnp.where(lax.broadcasted_iota(jnp.int32, (1, s.shape[1]), 1) < n,
                  s, NEG_INF)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True))
    o = lax.dot_general(p.astype(run.dtype), run[:, :rank],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
    o = o / jnp.sum(p, axis=-1, keepdims=True)
    o_ref[...] = jnp.where(n > 0, o, 0.0).astype(o_ref.dtype)


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
    slot_of = pad(jnp.broadcast_to(
        jnp.arange(B, dtype=jnp.int32)[:, None], (B, S)))
    flat = pool.reshape(-1, W)

    def attend(args):
        q, take, slot = args
        at, count = picked_rows(take, width)
        pages = jnp.take_along_axis(
            tables[slot], jnp.minimum(at // T, tables.shape[1] - 1), axis=1)
        run = flat[jnp.where(jnp.arange(width)[None] < count[:, None],
                             pages * T + at % T, 0)]       # [block, width, W]
        if impl == "pallas":
            return _attend_pallas(q, run, count, rank, interpret, name)
        return _attend_reference(q, run, count, rank)

    blocks = (pad(q), pad(take), slot_of)
    o = (attend(tuple(a[0] for a in blocks))[None] if n == 1
         else lax.map(attend, blocks))
    o = o.reshape(n * block, H, rank)[:rows].reshape(B, S, H, rank).astype(
        q_c.dtype)
    return (o, take) if return_selected else o
