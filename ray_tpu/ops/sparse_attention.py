"""Block-selected sparse attention over the paged K/V pool (the InfLLM-v2
attention of the ``minicpm4`` mixer).

A query attends a few BLOCKS of its context instead of all of it. Beside
keys and values a layer keeps one POOLED row a page, the mean of the page's
keys (``page_tokens`` is the pooling stride; a pooled key is the mean of
``kernel_size`` = two strides of keys, so pooled key ``j`` is the mean of the
rows of pages ``j`` and ``j + 1``). For the query at position ``t``:

  1. ``sparse_select``: ``softmax_j(q_h . kc_j / sqrt(D))`` over the pooled
     keys whose tokens all lie at or before ``t``, summed over the query
     heads of a K/V group (the kernel); a block's score is the largest of the
     pooled keys that overlap it; chosen are the first ``init_blocks``
     blocks, the blocks of the last ``window_size`` tokens and the ``topk``
     best of the rest (``choose_blocks``). A context of at most ``dense_len``
     tokens chooses every block.
  2. ``sparse_paged_attention``: causal softmax attention over the tokens of
     the chosen blocks. A decode step (one query a row) compacts each row's
     chosen pages into a table of its own and runs the paged kernel over
     that: it reads the chosen pages and no other. A chunk (many queries,
     each with its own choice) runs a flash-style kernel over the slot's
     context with the choice as a mask a (query, block): its time follows
     the context, not the choice (PERF.md 7).

The sixth layer kind, 'indexed_attention', selects single TOKENS by a
learned scorer with a cache of its own and shares none of this file's
choice: ``ops/indexed_attention.py`` (its chunk runs a masked flash kernel
over the slot's context as this one's does, a mask a (query, token) there).

The kernels carry those two names in a profiler trace and are interpreted
off a TPU. What is chosen is returned on request (``return_selected``): a
comparison with another implementation has to be made on the same choice,
because top-k is discontinuous.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops._pallas import should_interpret
from ray_tpu.ops.paged_attention import NEG_INF, paged_attention

_KEY_TILE = 512    # context tokens of one grid cell of the chunk kernel
_ROWS = 512        # query rows (tokens x group) of one tile of it
_SELECT_TOKENS = 128  # query tokens of one tile of the selection kernel


@dataclasses.dataclass(frozen=True)
class SparseSizes:
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    topk: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    dense_len: int = 8192

    def __post_init__(self):
        if self.kernel_size != 2 * self.kernel_stride:
            raise ValueError("a pooled key spans two strides: kernel_size "
                             f"{self.kernel_size} != 2 x {self.kernel_stride}")
        if self.block_size % self.kernel_stride or self.init_blocks < 1:
            raise ValueError("block_size must be whole strides and "
                             "init_blocks >= 1")

    @property
    def pages_per_block(self) -> int:
        return self.block_size // self.kernel_stride

    def max_chosen_blocks(self) -> int:
        """The most blocks one query attends: its choice, or a dense
        context's all."""
        return max(self.init_blocks + self.window_size // self.block_size
                   + 1 + self.topk, -(-self.dense_len // self.block_size) + 1)

    def chosen_blocks(self, t):
        """How many blocks the query at position ``t`` (an int or a NumPy
        array of them) attends: a function of the position alone (the
        scheduler's counters mirror it)."""
        import numpy as np

        t = np.asarray(t)
        cur = t // self.block_size
        first_window = np.maximum(
            (t - self.window_size + 1) // self.block_size, 0)
        forced = (cur - first_window + 1) + np.minimum(self.init_blocks,
                                                       first_window)
        sparse = forced + np.minimum(self.topk, cur + 1 - forced)
        return np.where(t + 1 <= self.dense_len, cur + 1, sparse)

    def attended_tokens(self, t):
        import numpy as np

        return ((self.chosen_blocks(t) - 1) * self.block_size
                + np.asarray(t) % self.block_size + 1)


def check_pool(sizes: SparseSizes, page_tokens: int, pages_per_slot: int):
    if page_tokens != sizes.kernel_stride:
        raise ValueError(
            f"block-selected attention keeps one pooled key row a page: "
            f"page_tokens ({page_tokens}) must be its kernel_stride "
            f"({sizes.kernel_stride})")
    if pages_per_slot % sizes.pages_per_block:
        raise ValueError(
            f"a slot's pages ({pages_per_slot}) must be whole blocks of "
            f"{sizes.pages_per_block} pages")


# --------------------------------------------------------- pooled key rows


def update_page_means(means, k_pool, *windows):
    """Recompute the pooled row of every page that ``windows`` — each a pair
    (write_tables [B, P], positions [B, S], consecutive a row), the groups
    of rows one write put into ``k_pool`` — were just written to, in ONE
    scatter: means [N, Hkv * D] float32. A page that is not full yet gets
    the mean of what it holds; no query sees it before the write that fills
    it recomputes it."""
    T = k_pool.shape[1]
    phys = []
    for write_tables, positions in windows:
        P = write_tables.shape[1]
        n = (positions.shape[1] + T - 2) // T + 1
        logical = positions[:, :1] // T + jnp.arange(n, dtype=jnp.int32)[None]
        pages = jnp.take_along_axis(
            write_tables, jnp.clip(logical, 0, P - 1), axis=1)
        phys.append(jnp.where(logical < P, pages, 0))
    phys = phys[0] if len(phys) == 1 else jnp.concatenate(
        [pages.reshape(-1) for pages in phys])
    return means.at[phys].set(k_pool[phys].astype(jnp.float32).mean(axis=-2))


# --------------------------------------------------------------- selection


def _select_kernel(q_ref, kc_ref, pos_ref, p_ref, *, group, tokens, pooled,
                   stride, kernel, sm_scale):
    kc = kc_ref[...]
    j = lax.broadcasted_iota(jnp.int32, (1, kc.shape[0]), 1)
    # a pooled key is seen once all its tokens lie at or before the query
    seen = jnp.logical_and(j * stride + kernel - 1 <= pos_ref[...],
                           j < pooled)
    total = jnp.zeros(p_ref.shape, jnp.float32)
    for g in range(group):
        s = lax.dot_general(q_ref[g * tokens:(g + 1) * tokens], kc,
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(seen, s, NEG_INF)
        e = jnp.where(seen, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)),
                      0.0)
        l = jnp.sum(e, axis=-1, keepdims=True)
        total += e / jnp.where(l == 0.0, 1.0, l)
    p_ref[...] = total


def _pooled_scores(q, kc, positions, sizes: SparseSizes, pooled: int,
                   interpret: bool):
    """q [B, S, H, D], kc [B, Hkv, NP', D], positions [B, S] -> the pooled
    keys' probabilities summed over a group's heads, [B, Hkv, S, NP']."""
    B, S, H, D = q.shape
    Hkv, NPp = kc.shape[1:3]
    G = H // Hkv
    tokens = min(_SELECT_TOKENS, -(-S // 8) * 8)
    n_tiles = -(-S // tokens)
    Sp = n_tiles * tokens
    # rows of a tile stand head-major: row g * tokens + i = (head g, token i)
    qr = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0), (0, 0))).reshape(
        B, n_tiles, tokens, Hkv, G, D).transpose(0, 3, 1, 4, 2, 5).reshape(
        B, Hkv, n_tiles, G * tokens, D).astype(kc.dtype)
    pos = jnp.pad(positions, ((0, 0), (0, Sp - S))).reshape(
        B, n_tiles, tokens, 1).astype(jnp.int32)
    p = pl.pallas_call(
        functools.partial(_select_kernel, group=G, tokens=tokens,
                          pooled=pooled, stride=sizes.kernel_stride,
                          kernel=sizes.kernel_size,
                          sm_scale=1.0 / math.sqrt(D)),
        grid=(B, Hkv, n_tiles),
        in_specs=[
            pl.BlockSpec((None, None, None, G * tokens, D),
                         lambda b, h, t: (b, h, t, 0, 0)),
            pl.BlockSpec((None, None, NPp, D), lambda b, h, t: (b, h, 0, 0)),
            pl.BlockSpec((None, None, tokens, 1),
                         lambda b, h, t: (b, t, 0, 0))],
        out_specs=pl.BlockSpec((None, None, tokens, NPp),
                               lambda b, h, t: (b, h, t, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, Sp, NPp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=64 << 20),
        name="sparse_select", interpret=interpret,
    )(qr, kc, pos)
    return p[:, :, :S]


def block_scores(p, sizes: SparseSizes, n_blocks: int):
    """[..., NP'] pooled probabilities -> [..., NB]: a block's score is the
    largest among the pooled keys that overlap it (pooled key j spans pages
    j and j + 1; block b pages [R b, R b + R))."""
    R = sizes.pages_per_block
    need = R * n_blocks + 1
    # entry 0 stands for pooled key -1, which does not exist
    shifted = jnp.pad(p, [(0, 0)] * (p.ndim - 1) + [(1, 0)])
    shifted = jnp.pad(shifted, [(0, 0)] * (p.ndim - 1)
                      + [(0, max(need - shifted.shape[-1], 0))])[..., :need]
    inner = shifted[..., :R * n_blocks].reshape(
        *p.shape[:-1], n_blocks, R).max(axis=-1)
    return jnp.maximum(inner, shifted[..., R::R][..., :n_blocks])


def choose_blocks(scores, positions, sizes: SparseSizes):
    """scores [B, Hkv, S, NB], positions [B, S] -> bool [B, S, Hkv, NB]: the
    blocks each query attends."""
    NB = scores.shape[-1]
    blk = jnp.arange(NB, dtype=jnp.int32)
    t = positions[:, :, None, None]                     # [B, S, 1, 1]
    seen = blk <= t // sizes.block_size
    window = blk >= (t - sizes.window_size + 1) // sizes.block_size
    forced = jnp.logical_and(
        jnp.logical_or(blk < sizes.init_blocks, window), seen)
    cand = jnp.logical_and(seen, jnp.logical_not(forced))
    ranked = jnp.where(cand, scores.transpose(0, 2, 1, 3), -1.0)
    best = lax.top_k(ranked, min(sizes.topk, NB))[1]    # [B, S, Hkv, k]
    chosen = (best[..., None] == blk).any(axis=-2)
    sparse = jnp.logical_or(forced, jnp.logical_and(chosen, cand))
    return jnp.where(t + 1 <= sizes.dense_len, seen, sparse)


# --------------------------------------------------------- chunk attention


def _chunk_kernel(nkt_ref, q_ref, k_ref, v_ref, sel_ref, pos_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, key_tile, block, sm_scale):
    """One (row, kv head, query tile, key tile) cell, scores TRANSPOSED
    (keys on sublanes, query rows on lanes): a block's choice is then one
    row of ``sel_ref``, read at a dynamic sublane and spread over the block's
    keys."""
    b, qt, kt = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(kt == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(kt < nkt_ref[b, qt])
    def _():
        q = q_ref[...]
        row_pos = pos_ref[...]                                   # [1, R]
        for j in range(key_tile // block):
            keys = slice(j * block, (j + 1) * block)
            s = lax.dot_general(k_ref[keys], q, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            sel = sel_ref[pl.ds(kt * (key_tile // block) + j, 1)]
            kpos = kt * key_tile + j * block + lax.broadcasted_iota(
                jnp.int32, (block, 1), 0)
            s = jnp.where(jnp.logical_and(sel > 0.0, kpos <= row_pos),
                          s * sm_scale, NEG_INF)                 # [block, R]
            m = m_scr[...]
            m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            alpha = jnp.exp(m - m_new)
            pr = jnp.exp(s - m_new)
            l_scr[...] = l_scr[...] * alpha + jnp.sum(pr, axis=0,
                                                      keepdims=True)
            acc_scr[...] = acc_scr[...] * alpha + lax.dot_general(
                v_ref[keys], pr.astype(v_ref.dtype),
                (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)              # [D, R]
            m_scr[...] = m_new

    @pl.when(kt == pl.num_programs(3) - 1)
    def _():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


def _chunk_attention(q, k_pool, v_pool, tables, positions, selected,
                     sizes: SparseSizes, interpret: bool):
    """q [B, S, H, D] at ``positions`` [B, S] over each row's own context
    (its pages, gathered into one run), ``selected`` [B, S, Hkv, NB]."""
    B, S, H, D = q.shape
    T = k_pool.shape[1]
    Hkv = k_pool.shape[2] // D
    G = H // Hkv
    BS = sizes.block_size
    key_tile = max(_KEY_TILE // BS, 1) * BS
    pages = -(-tables.shape[1] * T // key_tile) * key_tile // T
    tables = jnp.pad(tables, ((0, 0), (0, pages - tables.shape[1])))
    ctx = pages * T
    n_kt, NBp = ctx // key_tile, ctx // BS
    view = lambda pool: pool[tables].reshape(B, ctx, Hkv, D).transpose(
        0, 2, 1, 3)
    tokens = min(max(_ROWS // G, 8), -(-S // 8) * 8)
    n_qt = -(-S // tokens)
    Sp, R = n_qt * tokens, tokens * G
    # rows of a tile stand token-major: row i * G + g = (token i, head g)
    qr = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0), (0, 0))).reshape(
        B, n_qt, tokens, Hkv, G, D).transpose(0, 3, 1, 2, 4, 5).reshape(
        B, Hkv, n_qt, R, D).astype(k_pool.dtype)
    pos = jnp.pad(positions.astype(jnp.int32), ((0, 0), (0, Sp - S)),
                  mode="edge")
    sel = jnp.pad(selected, ((0, 0), (0, Sp - S), (0, 0),
                             (0, NBp - selected.shape[-1])))
    sel = sel.at[..., 0].set(True)  # block 0 is every query's: m stays finite
    sel = jnp.repeat(sel.reshape(B, n_qt, tokens, Hkv, NBp).transpose(
        0, 3, 1, 4, 2).astype(jnp.float32), G, axis=-1)  # [B,Hkv,n_qt,NBp,R]
    row_pos = jnp.repeat(pos.reshape(B, n_qt, 1, tokens), G, axis=-1)
    nkt = pos.reshape(B, n_qt, tokens).max(axis=-1) // key_tile + 1
    cell = lambda b, h, qt, kt, nkt: (b, h, qt, 0, 0)
    keys = lambda b, h, qt, kt, nkt: (
        b, h, jnp.minimum(kt, nkt[b, qt] - 1), 0)
    out = pl.pallas_call(
        functools.partial(_chunk_kernel, key_tile=key_tile, block=BS,
                          sm_scale=1.0 / math.sqrt(D)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, Hkv, n_qt, n_kt),
            in_specs=[
                pl.BlockSpec((None, None, None, R, D), cell),
                pl.BlockSpec((None, None, key_tile, D), keys),
                pl.BlockSpec((None, None, key_tile, D), keys),
                pl.BlockSpec((None, None, None, NBp, R), cell),
                pl.BlockSpec((None, None, 1, R),
                             lambda b, h, qt, kt, nkt: (b, qt, 0, 0))],
            out_specs=pl.BlockSpec((None, None, None, D, R), cell),
            scratch_shapes=[pltpu.VMEM((1, R), jnp.float32),
                            pltpu.VMEM((1, R), jnp.float32),
                            pltpu.VMEM((D, R), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, n_qt, D, R), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary"),
            vmem_limit_bytes=64 << 20),
        name="sparse_paged_attention", interpret=interpret,
    )(nkt.astype(jnp.int32), qr, view(k_pool), view(v_pool), sel, row_pos)
    out = out.reshape(B, Hkv, n_qt, D, tokens, G).transpose(0, 2, 4, 1, 5, 3)
    return out.reshape(B, Sp, H, D)[:, :S]


# ---------------------------------------------------------- step attention


def _step_attention(q, k_pool, v_pool, tables, positions, live, selected,
                    sizes: SparseSizes, impl: str):
    """One query a row: every (row, kv head) pair becomes a row of its own
    whose page table holds the pages of its chosen blocks in ascending
    order — the last of them the block its position is in, so the paged
    kernel's causal mask cuts exactly behind the query."""
    B, _, H, D = q.shape
    Hkv = k_pool.shape[2] // D
    G = H // Hkv
    R, BS = sizes.pages_per_block, sizes.block_size
    sel = selected[:, 0]                                  # [B, Hkv, NB]
    NB = sel.shape[-1]
    width = min(sizes.max_chosen_blocks(), NB)
    blk = jnp.arange(NB, dtype=jnp.int32)
    order = jnp.sort(jnp.where(sel, blk, NB + blk), axis=-1)[..., :width]
    held = order < NB
    logical = (jnp.where(held, order, 0)[..., None] * R
               + jnp.arange(R, dtype=jnp.int32)).reshape(B, Hkv, width * R)
    own = jnp.take_along_axis(
        jnp.broadcast_to(tables[:, None], (B, Hkv, tables.shape[1])),
        logical, axis=-1)
    own = jnp.where(jnp.repeat(held, R, axis=-1), own, 0)
    t = positions[:, :1]                                  # [B, 1]
    lengths = (sel.sum(axis=-1, dtype=jnp.int32) - 1) * BS + t % BS
    lengths = jnp.where(live[:, None], lengths, -1)
    # row (b, g) carries the heads of group g; the others' lanes are zero
    mine = (jnp.arange(H) // G)[None, :] == jnp.arange(Hkv)[:, None]
    rows = jnp.where(mine[None, :, None, :, None], q[:, None], 0)
    o = paged_attention(
        rows.reshape(B * Hkv, 1, H, D), k_pool, v_pool,
        own.reshape(B * Hkv, width * R), lengths.reshape(B * Hkv),
        impl=impl, name="sparse_paged_attention")
    o = o.reshape(B, Hkv, 1, Hkv, G, D)
    idx = jnp.arange(Hkv)
    return o[:, idx, :, idx].transpose(1, 2, 0, 3, 4).reshape(B, 1, H, D)


# ------------------------------------------------------------------ the op


def sparse_attention(q, k_pool, v_pool, means, tables, positions, lengths,
                     sizes: SparseSizes, *, impl: str,
                     return_selected: bool = False):
    """Attention of q [B, S, H, D] at ``positions`` [B, S] over the chosen
    blocks of each row's context, through its page table. k_pool/v_pool:
    [N, T, Hkv * D]; means: [N, Hkv * D] float32, the pages' pooled rows
    (``update_page_means`` after the window's write); tables: [B, P];
    lengths: [B], as ``paged_attention`` takes them (a row whose window lies
    before position 0 attends nothing). ``impl``: what the paged kernel runs
    as for a step ('reference' | 'pallas'). Returns [B, S, H, D], and with
    ``return_selected`` the choice, bool [B, S, Hkv, NB]."""
    return _attention(q, k_pool, v_pool, means, tables, positions, lengths,
                      sizes, impl, return_selected, should_interpret())


# jitted as the kernels' own wrappers are: a model's layers of the kind and
# a program's groups of one shape are traced and lowered ONCE (the choice of
# blocks is many small ops; a replica pays their tracing before its first
# request)
@functools.partial(jax.jit, static_argnames=(
    "sizes", "impl", "return_selected", "interpret"))
def _attention(q, k_pool, v_pool, means, tables, positions, lengths, sizes,
               impl, return_selected, interpret):
    B, S, H, D = q.shape
    Hkv = k_pool.shape[2] // D
    P = tables.shape[1]
    check_pool(sizes, k_pool.shape[1], P)
    rows = means[tables]                                   # [B, P, Hkv * D]
    kc = 0.5 * (rows[:, :-1] + rows[:, 1:])
    padded = -(-(P - 1) // 128) * 128
    kc = jnp.pad(kc, ((0, 0), (0, padded - (P - 1)), (0, 0))).reshape(
        B, padded, Hkv, D).transpose(0, 2, 1, 3).astype(q.dtype)
    p = _pooled_scores(q, kc, positions, sizes, P - 1, interpret)
    selected = choose_blocks(
        block_scores(p, sizes, P // sizes.pages_per_block), positions, sizes)
    if S == 1:
        o = _step_attention(q, k_pool, v_pool, tables, positions,
                            lengths + 1 > 0, selected, sizes, impl)
    else:
        o = _chunk_attention(q, k_pool, v_pool, tables, positions, selected,
                             sizes, interpret)
    return (o, selected) if return_selected else o
