"""Incremental decoding: functional per-layer KV caches + greedy/temperature
sampling loop, all jit-compatible (static shapes, `lax.dynamic_update_slice`).

TPU-native counterpart of serving decode loops the reference leaves to
torch/vLLM inside Serve replicas (SURVEY §2.3 Serve row): the cache is a
pytree carried through `lax.while_loop`/scan, so one compiled program serves
any prompt length up to max_len.
"""

from __future__ import annotations

import dataclasses
import itertools
from functools import partial
from typing import Any, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.transformer import (ATTENTION, INDEXED, INDEXED_LATENT,
                                        LATENT, LINEAR, LOOP_PASS,
                                        MAMBA, NONE, OWN_PAGE_TOKENS,
                                        RETENTION,
                                        SLIDING, SPARSE, STATE_KINDS,
                                        STATE_MIXERS,
                                        TransformerConfig, _after_mixer,
                                        _gated_out, _mlp,
                                        _norm, _qkv, _residual, body_params,
                                        embed, exit_state,
                                        final_hidden, forward, holds_page,
                                        indexed_latent_mix,
                                        indexed_latent_project, indexed_mix,
                                        indexed_project, latent_finish,
                                        latent_mix, latent_project,
                                        layer_params,
                                        project, rope_table,
                                        sparse_mix, sparse_pool_pages,
                                        stacked_mlp, state_shapes,
                                        write_pages, written_pages)
from ray_tpu.ops.indexed_attention import index_width
from ray_tpu.ops.latent_attention import pool_width
from ray_tpu.ops.moe import held_index
from ray_tpu.ops.paged_attention import paged_attention
from ray_tpu.ops.sparse_attention import check_pool, update_page_means


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LayerKVCache:
    """Fixed-capacity cache for one layer. k/v: [B, max_len, Hkv, D] (a
    looped model's: ``rows`` of them stacked in front, one a (pass, layer),
    each HEADS-major, [rows, B, Hkv, max_len, D] — the order attention reads
    a row in, so the loop that carries the stack lays it out once:
    ``transformer._looped_cached``)."""

    k: Any
    v: Any
    length: Any  # scalar int32: tokens already cached

    @classmethod
    def zeros(cls, batch: int, max_len: int, kv_heads: int, head_dim: int,
              dtype=jnp.bfloat16, rows: Optional[int] = None
              ) -> "LayerKVCache":
        shape = (batch, max_len, kv_heads, head_dim)
        if rows is not None:
            shape = (rows, batch, kv_heads, max_len, head_dim)
        return cls(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   length=jnp.zeros((), jnp.int32))

    def update(self, k_new, v_new) -> Tuple["LayerKVCache", Any, Any]:
        """Append [B, S, Hkv, D] new keys/values; returns (new_cache, k_all,
        v_all) where k_all/v_all are the full fixed-size buffers."""
        k = lax.dynamic_update_slice(
            self.k, k_new.astype(self.k.dtype), (0, self.length, 0, 0))
        v = lax.dynamic_update_slice(
            self.v, v_new.astype(self.v.dtype), (0, self.length, 0, 0))
        new = LayerKVCache(k=k, v=v, length=self.length + k_new.shape[1])
        return new, k, v

    def mask_bias(self, q_len: int, window: Optional[int] = None, first=0):
        """Additive bias [1,1,1,q_len,max_len]: query i (global position
        length+first+i) may attend to cache slot j iff j <= its position
        (and, with a ``window``, j > its position - window)."""
        max_len = self.k.shape[1]
        qpos = self.length + first + jnp.arange(q_len)[:, None]
        jpos = jnp.arange(max_len)[None, :]
        allowed = jpos <= qpos
        if window is not None:
            allowed = jnp.logical_and(allowed, jpos > qpos - window)
        bias = jnp.where(allowed, 0.0, -1e30).astype(jnp.float32)
        return bias[None, None, None, :, :]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LinearState:
    """What a 'lightning-attn' layer keeps instead of keys and values: s
    [rows, H, D, D] float32, one row a sequence (the contiguous cache) or a
    slot (the serving pool, where the cursors are the caller's and
    ``length`` is None)."""

    s: Any
    length: Any = None

    def arrays(self):
        return {"s": self.s}


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RetentionState:
    """What a 'power-retention' layer keeps: s [rows, Hkv, tiles, D, lanes]
    and the normaliser z [rows, Hkv, tiles, 1, lanes], float32
    (``ops.power_retention.state_shapes``), one row a sequence or a slot as
    ``LinearState`` has it."""

    s: Any
    z: Any
    length: Any = None

    def arrays(self):
        return {"s": self.s, "z": self.z}


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class MambaState:
    """What a 'mamba2' layer keeps: the convolution's last inputs conv
    [rows, taps - 1, width] and the scan's state ssm [rows, G, N, lanes],
    float32 (``ops.ssm.state_shapes``), one row a sequence or a slot as
    ``LinearState`` has it."""

    conv: Any
    ssm: Any
    length: Any = None

    def arrays(self):
        return {"conv": self.conv, "ssm": self.ssm}


_STATES = {LINEAR: LinearState, RETENTION: RetentionState, MAMBA: MambaState}


def init_state(cfg: TransformerConfig, kind: str, rows: int, length=None):
    """The zero state of a layer of a kind in ``STATE_KINDS`` for ``rows``
    sequences (``transformer.state_shapes`` says what that is)."""
    return _STATES[kind](length=length, **{
        name: jnp.zeros(shape, jnp.float32)
        for name, shape in state_shapes(cfg, kind, rows).items()})


def init_caches(cfg: TransformerConfig, batch: int, max_len: int,
                dtype=None) -> List[Any]:
    """A contiguous cache a layer, by the layer's kind (None for a layer
    without a mixer)."""
    dtype = dtype or cfg.dtype
    # a cursor a layer, each an array of its own: the caches are DONATED to
    # the programs that fill them, and no buffer can be given twice
    zero = lambda: jnp.zeros((), jnp.int32)
    if cfg.looped:
        # ONE cache, a (pass, layer) a leading row of k and v: what the
        # loop over passes and layers carries (``transformer
        # ._looped_cached``). Whole tiles of positions (16 rows of bf16):
        # short of them the chip's own layout of the array puts the heads
        # behind the positions, and the loop lays the stack out anew
        rows = cfg.loop_passes * cfg.num_layers
        return [LayerKVCache.zeros(batch, -(-max_len // 16) * 16,
                                   cfg.kv_heads, cfg.head_dim, dtype,
                                   rows=rows)]

    def one(kind):
        if kind == NONE:
            return None
        if kind in (ATTENTION, SLIDING):
            # a window layer's contiguous cache keeps every position too:
            # the window is in the mask (the serving pool is what forgets)
            return LayerKVCache.zeros(batch, max_len, cfg.kv_heads,
                                      cfg.head_dim, dtype)
        if kind in STATE_KINDS:
            return init_state(cfg, kind, batch, zero())
        if kind == INDEXED:
            # a pool of its own too, the index keys in it beside K and V
            return dataclasses.replace(IndexedPagedKVCache.zeros(
                1 + batch * -(-max_len // OWN_PAGE_TOKENS), OWN_PAGE_TOKENS,
                cfg.kv_heads, cfg.head_dim, cfg.indexer.indexer_head_dim,
                dtype), length=zero())
        if kind == LATENT:
            # a pool of its own of latent-and-key rows, no K or V
            return dataclasses.replace(LatentPagedKVCache.zeros(
                1 + batch * -(-max_len // OWN_PAGE_TOKENS), OWN_PAGE_TOKENS,
                cfg.latent_kv_rank, cfg.latent_rope_dim, dtype), length=zero())
        if kind == INDEXED_LATENT:
            # the same, the index keys' rows beside the latents'
            return dataclasses.replace(IndexedLatentPagedKVCache.zeros(
                1 + batch * -(-max_len // OWN_PAGE_TOKENS), OWN_PAGE_TOKENS,
                cfg.latent_kv_rank, cfg.latent_rope_dim,
                cfg.indexer.indexer_head_dim, dtype), length=zero())
        # a pool of its own: page 0 the garbage page, then a sequence's
        # pages in order, so its page table is the identity
        return dataclasses.replace(SparsePagedKVCache.zeros(
            1 + batch * sparse_pool_pages(cfg, max_len),
            cfg.sparse.kernel_stride, cfg.kv_heads, cfg.head_dim, dtype),
            length=zero())

    return [one(kind) for kind in cfg.kinds]


def _length(caches):
    """The tokens the contiguous caches hold: every layer that keeps one
    counts the same."""
    return next(c.length for c in caches if c is not None)


def prefill(cfg: TransformerConfig, params, tokens, caches):
    """Run the prompt through the model, filling caches.
    Returns (logits_last [B, vocab], caches). The head sees the last
    position alone: a long prompt's logits are never made whole."""
    positions = jnp.arange(tokens.shape[1])[None, :] + _length(caches)
    hidden, caches = forward(cfg, params, tokens, positions=positions,
                             kv_caches=caches, return_hidden=True)
    return project(cfg, params, hidden[:, -1:])[:, 0], caches


def decode_step(cfg: TransformerConfig, params, token, caches):
    """One token step. token: [B, 1]. Returns (logits [B, vocab], caches)."""
    positions = _length(caches) + jnp.zeros((token.shape[0], 1), jnp.int32)
    logits, caches = forward(cfg, params, token, positions=positions,
                             kv_caches=caches)
    return logits[:, -1], caches


def sample_token(logits, temperature, seeds, positions, top_k: int = 0):
    """THE device sampler: logits [rows, vocab] -> ids [rows] int32, every
    row by its own ``temperature`` [rows] float32. A row at 0 takes the
    argmax of its float32 logits (the first maximum wins, as NumPy's does).
    A row above 0 draws from ``softmax(logits / T)`` (of the ``top_k``
    largest, if given) with a key folded from its entry of ``seeds``
    [rows] uint32 and of ``positions`` [rows] int32, the index in its
    sequence of the token being drawn: a request's stream follows from its
    seed alone, whichever row or batch it rides in. A call all of whose
    rows are at 0 draws no random bits."""
    logits = logits.astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def draw():
        def one(row, t, seed, position):
            row = row / t
            if top_k > 0:
                row = jnp.where(row < lax.top_k(row, top_k)[0][-1], -1e30,
                                row)
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.key(0), seed), position)
            return jax.random.categorical(key, row).astype(jnp.int32)

        drawn = jax.vmap(one)(logits, jnp.where(temperature > 0,
                                                temperature, 1.0),
                              seeds, positions)
        return jnp.where(temperature > 0, drawn, greedy)

    return lax.cond(jnp.any(temperature > 0), draw, lambda: greedy)


# ---------------------------------------------------------------------------
# slotted KV arena — a contiguous [slots, max_len] cache whose cursors live
# on the device. The speculative DRAFTER (serve/_private/speculative.py) is
# its only user: the serving scheduler runs the paged programs below. One
# fixed-shape decode program steps EVERY slot each iteration, so the program
# shape never changes while the active set churns.


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SlotKVCache:
    """Per-layer slot arena. k/v: [slots, max_len, Hkv, D]; lengths: [slots]
    int32 — each slot is an independent sequence with its own write cursor."""

    k: Any
    v: Any
    lengths: Any

    @classmethod
    def zeros(cls, slots: int, max_len: int, kv_heads: int, head_dim: int,
              dtype=jnp.bfloat16) -> "SlotKVCache":
        return cls(
            k=jnp.zeros((slots, max_len, kv_heads, head_dim), dtype),
            v=jnp.zeros((slots, max_len, kv_heads, head_dim), dtype),
            lengths=jnp.zeros((slots,), jnp.int32),
        )


def init_slot_caches(cfg: TransformerConfig, slots: int, max_len: int,
                     dtype=None) -> List[SlotKVCache]:
    if max_len > cfg.max_seq_len:
        # rope/learned position tables are sized cfg.max_seq_len; a longer
        # arena would gather clamped positions and decode silently wrong
        raise ValueError(
            f"slot arena max_len ({max_len}) exceeds cfg.max_seq_len "
            f"({cfg.max_seq_len})")
    if set(cfg.kinds) - {ATTENTION, SLIDING} or cfg.looped:
        raise ValueError(
            "the slot arena holds keys and values alone, once a layer (the "
            "speculative drafter's cache): no model with layers of "
            f"{cfg.layer_kinds} or loop_passes={cfg.loop_passes}")
    dtype = dtype or cfg.dtype
    return [SlotKVCache.zeros(slots, max_len, cfg.kv_heads, cfg.head_dim,
                              dtype) for _ in range(cfg.num_layers)]


def reset_slot(caches: List[SlotKVCache], slot: int) -> List[SlotKVCache]:
    """Recycle a retired slot: just rewind its write cursor. Stale k/v need
    no scrub — writes are contiguous-from-0 and forward() updates the cache
    *before* attending, so every position a new sequence attends to has been
    freshly written by that sequence."""
    return [dataclasses.replace(c, lengths=c.lengths.at[slot].set(0))
            for c in caches]


def prefill_into_slot(cfg: TransformerConfig, params, tokens, real_len,
                      slot, caches):
    """One prefill chunk into ONE slot. tokens: [1, C] — the next C prompt
    tokens, zero-padded past ``real_len`` (so every chunk size compiles to
    the same program). Writes k/v at [cursor, cursor+C) and advances the
    slot's cursor by ``real_len`` only: pad positions are overwritten by the
    next chunk/decode write before anything can attend to them (update runs
    before attention, and the causal mask keeps real queries at or below
    their own position). Returns (logits [vocab] at the last REAL token,
    caches) — only the final chunk's logits are meaningful.

    Caller contract: cursor + C must fit in the arena (dynamic_update_slice
    clamps out-of-range starts, which would silently shift the write onto
    earlier real positions) — the scheduler enforces it at admission.
    """
    rows = [LayerKVCache(
        k=lax.dynamic_slice_in_dim(c.k, slot, 1, axis=0),
        v=lax.dynamic_slice_in_dim(c.v, slot, 1, axis=0),
        length=lax.dynamic_slice(c.lengths, (slot,), (1,))[0])
        for c in caches]
    positions = jnp.arange(tokens.shape[1])[None, :] + rows[0].length
    logits, new_rows = forward(cfg, params, tokens, positions=positions,
                               kv_caches=rows)
    last = lax.dynamic_index_in_dim(logits[0], real_len - 1, keepdims=False)
    new_caches = [
        SlotKVCache(
            k=lax.dynamic_update_slice_in_dim(c.k, r.k, slot, axis=0),
            v=lax.dynamic_update_slice_in_dim(c.v, r.v, slot, axis=0),
            lengths=c.lengths.at[slot].add(real_len))
        for c, r in zip(caches, new_rows)]
    return last, new_caches


def slot_decode_step(cfg: TransformerConfig, params, tokens, active, caches):
    """One fixed-shape decode step over the WHOLE slot arena.

    tokens: [slots] int32 — each decoding slot's next input token.
    active: [slots] int32 — 1 for slots mid-decode, 0 for free/prefilling
    slots. Inactive slots run the same compute on garbage: their logits are
    never consumed, their cursor does not advance, and their stale-position
    write is overwritten before any sequence attends to it (same contiguous-
    write/update-before-attend invariant as prefill_into_slot).

    Returns (logits [slots, vocab], caches).
    """
    def one(tok, act, row):
        rows = [LayerKVCache(k=c.k[None], v=c.v[None], length=c.lengths)
                for c in row]
        positions = rows[0].length + jnp.zeros((1, 1), jnp.int32)
        logits, new_rows = forward(cfg, params, tok[None, None],
                                   positions=positions, kv_caches=rows)
        out = [SlotKVCache(k=r.k[0], v=r.v[0], lengths=c.lengths + act)
               for c, r in zip(row, new_rows)]
        return logits[0, -1], out

    return jax.vmap(one, in_axes=(0, 0, 0))(tokens, active, caches)


# ---------------------------------------------------------------------------
# paged KV arena — what the serving scheduler (serve/_private/continuous.py)
# runs. KV storage is a pool [num_pages, page_tokens, Hkv * D] per layer (a
# token's kv heads joined on the minor axis: a page is then ONE contiguous
# run of whole 128-lane rows, which the paged kernel streams in
# double-buffered blocks of whole rows and serves every kv head from — see
# ops/paged_attention.py); a slot owns a PAGE TABLE ([pages_per_slot] int32
# of physical page ids) instead of a contiguous worst-case range, so
# long/idle sequences reserve no memory they don't use and read-only pages
# can be SHARED between slots (the prefix cache). Every program is one
# forward, ``_paged_forward_inplace``: each layer writes the new tokens' k/v
# straight into their pages through a WRITE table and attends THROUGH the
# READ table (``ops.paged_attention``), so no contiguous view of a slot ever
# exists and a step's cost follows the pages a sequence holds (the layer
# kinds that keep more than keys and values in a page or beside it — pooled
# key rows for 'minicpm4', an index key a token for 'indexed_attention', a
# latent and a rotated key and NO keys or values for 'latent_attention', a
# state a slot for 'lightning-attn' and 'power-retention', a pool of their
# own for 'sliding_attention' — are said at ``init_paged_caches``). ``attn``
# names the op's implementation ('reference' | 'pallas') and has no default:
# ``ops.paged_attention.resolve_impl`` picks it from the platform and the
# model's shapes. The tests' oracle is the sequential cache above
# (``prefill`` + ``decode_step``).
#
# Page 0 is RESERVED as the garbage page: read-table entries for logical
# pages a slot has not allocated point at it (their positions are >= the
# slot's cursor, so the causal mask zeroes them exactly), and write-table
# entries for SHARED or unallocated pages redirect there so a slot can never
# scribble on a page it does not own. The scheduler maintains the tables
# host-side and guarantees the page covering every position written by a
# program is allocated and owned before the call.


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedKVCache:
    """One layer's page pool. k/v: [num_pages, page_tokens, Hkv * D]. The
    pool holds pages and nothing else: which slot is where in its sequence
    (the cursor, in LOGICAL tokens) is the caller's state, handed to every
    program as an argument like the page tables."""

    k: Any
    v: Any

    @classmethod
    def zeros(cls, num_pages: int, page_tokens: int, kv_heads: int,
              head_dim: int, dtype=jnp.bfloat16) -> "PagedKVCache":
        return cls(
            k=jnp.zeros((num_pages, page_tokens, kv_heads * head_dim), dtype),
            v=jnp.zeros((num_pages, page_tokens, kv_heads * head_dim), dtype),
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LoopPagedKVCache:
    """A LOOPED model's page pools, all of them, as ONE pair: k/v
    [num_pages, passes * layers, page_tokens, Hkv * D]. A page holds its
    token span once a (pass, layer) — pool ``t * layers + l`` is what layer
    l wrote in pass t — under the one page table: page ``p`` is the same
    tokens in every pool, so the allocator, the prefix cache and whoever
    copies pages in or out by their ids (``pool[ids]``, as of a pool alone)
    know nothing of passes. The serving loop carries the pair and writes it
    in place (``_paged_forward_loop``); a (pass, layer)'s page is as
    contiguous a run of whole rows as a page of a pool alone."""

    k: Any
    v: Any

    @classmethod
    def zeros(cls, num_pages: int, pools: int, page_tokens: int,
              kv_heads: int, head_dim: int,
              dtype=jnp.bfloat16) -> "LoopPagedKVCache":
        shape = (num_pages, pools, page_tokens, kv_heads * head_dim)
        return cls(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SparsePagedKVCache:
    """A 'minicpm4' layer's page pool: k and v as ``PagedKVCache`` holds
    them, and beside them ``means`` [num_pages, Hkv * D] float32, each
    page's pooled key row (what the layer's selection scores). The same
    layout is that layer's CONTIGUOUS cache (``init_caches``), which then
    carries its ``length``; in the serving pool the cursors are the
    caller's and it is None."""

    k: Any
    v: Any
    means: Any
    length: Any = None

    @classmethod
    def zeros(cls, num_pages: int, page_tokens: int, kv_heads: int,
              head_dim: int, dtype=jnp.bfloat16) -> "SparsePagedKVCache":
        pool = PagedKVCache.zeros(num_pages, page_tokens, kv_heads, head_dim,
                                  dtype)
        return cls(k=pool.k, v=pool.v, means=jnp.zeros(
            (num_pages, kv_heads * head_dim), jnp.float32))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class IndexedPagedKVCache:
    """An 'indexed_attention' layer's page pool: k and v as ``PagedKVCache``
    holds them, and beside them ``ik`` [num_pages, page_tokens, W], each
    token's index key (what the layer's indexer scores) and zeros up to
    whole 128-lane tiles (``ops.indexed_attention.index_row``: 64 -> 128;
    the chip holds a 64-lane row in as many, and writes one only by laying
    the whole pool out anew), under the same page table: a page that is
    spliced, shared or freed takes its index keys along. The same layout
    is that layer's CONTIGUOUS cache
    (``init_caches``), which then carries its ``length``; in the serving
    pool the cursors are the caller's and it is None."""

    k: Any
    v: Any
    ik: Any
    length: Any = None

    @classmethod
    def zeros(cls, num_pages: int, page_tokens: int, kv_heads: int,
              head_dim: int, index_dim: int,
              dtype=jnp.bfloat16) -> "IndexedPagedKVCache":
        pool = PagedKVCache.zeros(num_pages, page_tokens, kv_heads, head_dim,
                                  dtype)
        return cls(k=pool.k, v=pool.v, ik=jnp.zeros(
            (num_pages, page_tokens, index_width(index_dim)), dtype))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LatentPagedKVCache:
    """A 'latent_attention' layer's page pool: ``ckr`` [num_pages,
    page_tokens, width], a row a token — its latent after its norm (rank
    values), the one rotated key its heads share (rope values), and zeros up
    to whole 128-lane tiles (``ops.latent_attention.join``: 512 + 64 -> 640;
    the chip holds a 576-lane row, or a 64-lane one beside a 512-lane one,
    in as many) — no keys, no values: a cached forward attends the latents
    themselves. It lies under the page table every page-holding kind uses,
    so a page is spliced, shared or freed as theirs are. ``k`` names it for
    whoever asks a pool for its page size. The same layout is that layer's
    CONTIGUOUS cache (``init_caches``), which then carries its ``length``;
    in the serving pool the cursors are the caller's and it is None."""

    ckr: Any
    length: Any = None

    @property
    def k(self):
        return self.ckr

    @classmethod
    def zeros(cls, num_pages: int, page_tokens: int, rank: int, rope: int,
              dtype=jnp.bfloat16) -> "LatentPagedKVCache":
        return cls(ckr=jnp.zeros(
            (num_pages, page_tokens, pool_width(rank, rope)), dtype))


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class IndexedLatentPagedKVCache:
    """An 'indexed_latent_attention' layer's page pool: TWO arrays a page,
    ``ckr`` as ``LatentPagedKVCache`` holds it (a token's latent and shared
    rotated key in one row of whole lane tiles) and ``ik`` as
    ``IndexedPagedKVCache`` does (the token's index key, whole lane tiles
    too), under the one page table: a page that is spliced, shared or freed
    takes its index keys along with its latents. ``k`` names the first for
    whoever asks a pool for its page size. The same layout is that layer's
    CONTIGUOUS cache (``init_caches``), which then carries its ``length``."""

    ckr: Any
    ik: Any
    length: Any = None

    @property
    def k(self):
        return self.ckr

    @classmethod
    def zeros(cls, num_pages: int, page_tokens: int, rank: int, rope: int,
              index_dim: int, dtype=jnp.bfloat16
              ) -> "IndexedLatentPagedKVCache":
        return cls(
            ckr=jnp.zeros((num_pages, page_tokens, pool_width(rank, rope)),
                          dtype),
            ik=jnp.zeros((num_pages, page_tokens, index_width(index_dim)),
                         dtype))


def init_paged_caches(cfg: TransformerConfig, num_pages: int,
                      page_tokens: int, pages_per_slot: int,
                      dtype=None, slots: Optional[int] = None,
                      window_pages: Optional[int] = None) -> List[Any]:
    """The serving pool, a layer at a time and by the layer's kind: pages
    for an attention layer (with pooled key rows for a 'minicpm4' one, with
    an index key a token for an 'indexed_attention' one; a row of a latent
    and a rotated key a token, and nothing else, for a 'latent_attention'
    one, and both of those rows for an 'indexed_latent_attention' one), a
    state a slot (``slots`` of them) for a layer of a kind in
    ``STATE_KINDS``. A model none of whose layers holds a page has no pool:
    ``num_pages`` may then be anything, and nothing is made of it. A
    'sliding_attention' layer's pool has a page count of its own,
    ``window_pages`` (its page 0 the garbage page too): its pages go
    through tables of their own (``pool_of``), and how many a slot keeps is
    the caller's business. A looped model's pools (a layer's, once a pass)
    are ONE pair under the one table, a list of one
    (``LoopPagedKVCache``)."""
    if page_tokens < 1:
        raise ValueError(f"page_tokens must be >= 1, got {page_tokens}")
    if num_pages < 2 and cfg.holds_pages:
        # page 0 is the reserved garbage page; an arena with no
        # allocatable page cannot hold any sequence
        raise ValueError(f"num_pages must be >= 2, got {num_pages}")
    if pages_per_slot * page_tokens > cfg.max_seq_len:
        # rope/learned position tables are sized cfg.max_seq_len; a longer
        # logical view would gather clamped positions and decode silently
        # wrong
        raise ValueError(
            f"pages_per_slot * page_tokens ({pages_per_slot * page_tokens}) "
            f"exceeds cfg.max_seq_len ({cfg.max_seq_len})")
    if cfg.output_norms and not cfg.looped:
        # the norms behind the sublayers are in the looped forward's body
        # (``_after_mixer``) and not in ``_paged_forward_inplace``'s
        raise ValueError("output_norms without loop_passes > 1: the paged "
                         "programs norm a sublayer's output in the looped "
                         "forward alone")
    if SPARSE in cfg.kinds:
        check_pool(cfg.sparse, page_tokens, pages_per_slot)
    if cfg.recurrent and not slots:
        raise ValueError("a model with layers that keep a state a slot "
                         f"({_state_kinds(cfg)}): init_paged_caches needs "
                         "slots")
    if SLIDING in cfg.kinds and (window_pages is None or window_pages < 2):
        raise ValueError("a model with 'sliding_attention' layers: "
                         "init_paged_caches needs window_pages >= 2, got "
                         f"{window_pages}")
    dtype = dtype or cfg.dtype
    if cfg.looped:
        return [LoopPagedKVCache.zeros(
            num_pages, cfg.loop_passes * cfg.num_layers, page_tokens,
            cfg.kv_heads, cfg.head_dim, dtype)]

    def one(kind):
        if kind == NONE:
            return None
        if kind in STATE_KINDS:
            return init_state(cfg, kind, slots)
        if kind == INDEXED:
            return IndexedPagedKVCache.zeros(
                num_pages, page_tokens, cfg.kv_heads, cfg.head_dim,
                cfg.indexer.indexer_head_dim, dtype)
        if kind == LATENT:
            return LatentPagedKVCache.zeros(
                num_pages, page_tokens, cfg.latent_kv_rank,
                cfg.latent_rope_dim, dtype)
        if kind == INDEXED_LATENT:
            return IndexedLatentPagedKVCache.zeros(
                num_pages, page_tokens, cfg.latent_kv_rank,
                cfg.latent_rope_dim, cfg.indexer.indexer_head_dim, dtype)
        pool = SparsePagedKVCache if kind == SPARSE else PagedKVCache
        return pool.zeros(window_pages if kind == SLIDING else num_pages,
                          page_tokens, cfg.kv_heads, cfg.head_dim, dtype)

    return [one(kind) for kind in cfg.kinds]


def _state_kinds(cfg: TransformerConfig) -> str:
    """The model's kinds that keep a state, for a message."""
    return ", ".join(repr(k) for k in STATE_KINDS if k in cfg.kinds)


def pool_of(kind: str) -> str:
    """The pool a page-holding layer's pages are in, by the name its tables
    go under: the window layers' own, or the one every other kind shares."""
    return SLIDING if kind == SLIDING else ATTENTION


def pool_tables(tables, kind: str):
    """A layer's page tables out of a program's: an array as it stands (a
    model of one pool), else the entry of the layer's pool in a dict by
    ``pool_of`` (a model with 'sliding_attention' layers beside others)."""
    return tables[pool_of(kind)] if isinstance(tables, dict) else tables


class _Rows(NamedTuple):
    """One group of rows of a paged program: what meets a layer's pages or
    states at ONE shape. tokens/positions/valid: [S, K]; lengths: [S]
    attention cursors; read_tables/write_tables: [S, P] (None for a model
    that holds no page; a dict of them by pool, ``pool_tables``, for one
    whose layers hold pages in two). For the layers that keep a state a slot
    (``STATE_KINDS``) the group is a step's — a row a slot over ALL slots'
    states, of which the rows not ``active`` [S] keep theirs bitwise — or,
    with ``slot``, a chunk's: its one row continues that slot's own states,
    taken as zero when the chunk starts at position 0 (a new sequence needs
    no reset beforehand), by its ``real_len`` real tokens."""

    tokens: Any
    positions: Any
    lengths: Any
    read_tables: Any
    write_tables: Any
    valid: Any
    active: Any = None
    slot: Any = None
    real_len: Any = None


class StepRows(NamedTuple):
    """The decode step's rows a prefill chunk's program takes along
    (``paged_prefill_into_slot``): ``paged_decode_step``'s arrays of the
    same names over ``[slots]``; the rows' tokens are the chunk's ``ids``."""

    active: Any
    cursors: Any
    read_tables: Any
    write_tables: Any
    temperature: Any
    seeds: Any


def _unless_idle(group: _Rows, several: bool, call, kept):
    """``call(kept) -> (made, kept)``: what a layer does with ONE group's
    rows. A step's rows that ride in a chunk's program (``several`` groups)
    may have none live — a first prompt's chunks, the whole time to the
    first token at low load — and the call is then skipped inside the
    program: ``made`` comes back as zeros, ``kept`` as it came, and a chunk
    pays nothing for the rows it did not take along."""
    if not several or group.active is None:
        return call(kept)
    made = jax.eval_shape(call, kept)[0]
    return lax.cond(
        jnp.any(group.active > 0), call,
        lambda kept: (jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype),
                                   made), kept), kept)


def _mix_states(cfg, mix, p, group: _Rows, rows, state, several: bool):
    """One group's rows (``rows``: what the kind's projection made of them)
    through a state layer's ``state`` ({name: [slots, ...]}, every slot's)
    -> (o, state). A chunk's group cuts its slot's states out and puts them
    back; a step's goes over all of them in place."""
    if group.slot is None:
        return _unless_idle(
            group, several,
            lambda state: mix(cfg, p, rows, state, active=group.active),
            state)
    own = {n: jnp.where(group.positions[0, 0] == 0, 0.0,
                        lax.dynamic_slice_in_dim(s, group.slot, 1, axis=0))
           for n, s in state.items()}
    o, own = mix(cfg, p, rows, own, real_len=group.real_len)
    return o, {n: lax.dynamic_update_slice_in_dim(state[n], s, group.slot,
                                                  axis=0)
               for n, s in own.items()}


def _one_batch(groups: List[_Rows]):
    """(whether there are SEVERAL groups, ``batch``, ``split``): the groups'
    rows as the one batch a paged forward takes through a layer's weights,
    and back."""
    several = len(groups) > 1
    lead = (1, -1) if several else groups[0].tokens.shape

    def batch(parts):
        """The groups' rows as the one batch: a lone group as it stands."""
        parts = [p.reshape(lead + p.shape[2:]) for p in parts]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)

    ends = list(itertools.accumulate(g.tokens.size for g in groups))

    def split(rows):
        """The batch's rows group by group, each in its group's shape."""
        if not several:
            return [rows]
        return [rows[:, end - g.tokens.size:end].reshape(
            g.tokens.shape + rows.shape[2:]) for g, end in zip(groups, ends)]

    return several, batch, split


def _embedded(cfg, params, tokens, positions):
    x = embed(cfg, params, tokens)
    if cfg.pos == "learned":
        x = x + params["pos_embed"]["table"].astype(cfg.dtype)[positions]
    return x


def _paged_forward_loop(cfg: TransformerConfig, params, groups: List[_Rows],
                        caches, impl, sampled):
    """The serving forward of a LOOPED model (``cfg.loop_passes`` > 1): the
    program is ONE layer's body under a loop over the pass and the layer,
    whatever the depth. ``_paged_forward_inplace``'s rule holds inside the
    body: the groups' rows go through the norms, ``_qkv``, ``wo``, the
    output norm and the feed-forward as one batch (a fused turn reads the
    weights once A PASS); their k/v are written through the write tables
    into pool ``t * L + l`` of the stacked pair (``LoopPagedKVCache``), which
    is the loop's carry and is written in place; and each group attends
    THAT pool through its read table, the kernel told which (``ops
    .paged_attention(pool_index=)``): the keys and values a query of pass t
    attends are those pass t wrote. The stacked weights are what the loop
    scans (``body_params``); the final norm closes every pass
    (``final_hidden``) and its output enters the next.

    ``sampled(x)`` cuts the rows the caller samples or scores out of a
    pass's state. Returns (their state behind the final norm of EVERY pass,
    [T, ...] — the caller's gate picks the pass: ``_sampled_logits`` —,
    caches)."""
    (pool,) = caches
    _, batch, split = _one_batch(groups)
    tokens = batch([g.tokens for g in groups])
    positions = batch([g.positions for g in groups])
    x = _embedded(cfg, params, tokens, positions)
    rope = rope_table(cfg)
    T = pool.k.shape[2]
    pages = batch([written_pages(g.write_tables, g.positions, T)
                   for g in groups])
    offs = positions % T
    L = cfg.num_layers

    def layer(carry, xs):
        x, ck, cv = carry
        p, which = xs
        q, k, v = _qkv(cfg, p["attn"], _norm(cfg, p["ln1"], x), rope,
                       positions)
        ck = write_pages(ck, k, pages, offs, which)
        cv = write_pages(cv, v, pages, offs, which)
        o = batch([paged_attention(q_g, ck, cv, g.read_tables, g.lengths,
                                   impl=impl, pool_index=which)
                   for g, q_g in zip(groups, split(q))])
        a = jnp.einsum("bshk,hkd->bsd", o, p["attn"]["wo"].astype(cfg.dtype))
        return (_after_mixer(cfg, p, x, a)[0], ck, cv), None

    def one_pass(carry, t):
        with jax.named_scope(LOOP_PASS):
            (x, ck, cv), _ = lax.scan(
                layer, carry, (body_params(cfg, params),
                               t * L + jnp.arange(L, dtype=jnp.int32)))
            x = final_hidden(cfg, params, x)
        return (x, ck, cv), sampled(x)

    (_, ck, cv), states = lax.scan(
        one_pass, (x, pool.k, pool.v),
        jnp.arange(cfg.loop_passes, dtype=jnp.int32))
    return states, [LoopPagedKVCache(k=ck, v=cv)]


def _sampled_logits(cfg: TransformerConfig, params, groups: List[_Rows],
                    caches, impl, rows, live, taps):
    """The paged forward and the head of a program that samples: ``rows(
    hidden)`` cuts the rows it samples [B, S, d] out of the forward's, and
    ``live()`` [B * S] says whose sample somebody takes (asked for by a
    looped model alone). Returns (their logits [B, S, vocab], caches, what
    the program can tell beside ids and caches):
    an expert model's counts and routes (``_paged_forward_inplace``), and a
    LOOPED model's ``{"exit_pass": [B * S] int32}`` — the logits are of the
    pass each row's exit gate picks (``transformer.exit_state``, over every
    pass's state of the sampled rows), and that pass is told, 0 for a row
    that is not ``live``."""
    if not cfg.looped:
        hidden, caches, moe = _paged_forward_inplace(cfg, params, groups,
                                                     caches, impl, taps=taps)
        return _head(cfg, params, rows(hidden)), caches, moe
    states, caches = _paged_forward_loop(cfg, params, groups, caches, impl,
                                         rows)
    x, exits = exit_state(cfg, params, states)
    return project(cfg, params, x), caches, {
        "exit_pass": jnp.where(live(), exits.reshape(-1), 0)}


def _paged_forward_inplace(cfg: TransformerConfig, params,
                           groups: List[_Rows], caches, impl, *, taps=None):
    """The serving forward: one pass over the rows of ``groups``. ONE RULE
    for every kind of layer: the rows go through the norms, the projections
    (q/k/v, gates, ``wo``) and the MLP or expert layer as one batch, and
    meet what the layer KEEPS a group at a time, each group at its own
    shape. An attention layer (1) writes the rows' k/v DIRECTLY into their
    pages — ``pool.at[page, offset].set`` through the write tables,
    write-before-attend, so XLA updates the donated pool in place — and (2)
    attends through the read tables via ``ops.paged_attention(impl=)``, a
    call a group; a 'minicpm4' layer writes the same way and recomputes the
    pooled rows of the pages written, then attends the blocks it chooses, a
    call a group (``transformer.sparse_mix``); an 'indexed_attention' layer
    writes its index keys with the keys and values, then scores them,
    picks and attends its tokens, a call a group
    (``transformer.indexed_mix``); a 'latent_attention' layer writes a
    latent and a rotated key a token and attends the latents, the keys' and
    values' up-projections absorbed, a call a group
    (``transformer.latent_mix``); an 'indexed_latent_attention' layer
    writes that row and an index key's, then scores the index keys, picks
    and attends the picked LATENTS, a call a group
    (``transformer.indexed_latent_mix``); a
    'lightning-attn' or 'power-retention' layer (``transformer
    .STATE_MIXERS``) reads and writes its states instead, a kernel call a
    group (``_Rows`` says whose states a group's rows meet). Layer math
    mirrors ``transformer._block``.

    One group (``_Rows``: a step's or a verify's [S, K] window over all
    slots, a chunk's [1, C]) is the whole batch as it stands. SEVERAL — a
    chunk and the step's rows it takes along — are ONE batch ``[1, sum of S
    x K]`` outside those calls, read the weights once, and their k/v land in
    the pool in ONE write. No two rows that matter may share a position or
    a state: the caller sends every row whose write must not land to the
    garbage page through its group's write table and marks it not active.
    Positions on unallocated/shared pages redirect there the same way.
    ``valid`` marks the rows that carry a live token (not a slot without a
    sequence, not a chunk's padding): the expert layer routes the others
    nowhere. ``taps``: a list that is given each 'minicpm4' or
    'indexed_attention' or 'indexed_latent_attention' layer's choice, a
    group at a time (debug).
    Returns (hidden, caches, moe): the rows after the last layer, BEFORE the
    final norm, [S, K, d] (several groups: [1, rows, d], group after
    group) — the caller norms and projects the rows it samples (``_head``);
    moe is None for a dense model, else ``{"counts": [L, E], "routes": [L,
    *rows, k]}`` over the L EXPERT layers (leading dense layers choose
    nothing) — the rows each layer's experts received (they sum to valid
    rows x k a layer: no row is dropped; several groups: [L, groups, E], the
    rows each GROUP sent them) and the experts each row chose."""
    several, batch, split = _one_batch(groups)
    tokens = batch([g.tokens for g in groups])
    positions = batch([g.positions for g in groups])
    valid = batch([g.valid for g in groups])
    x = _embedded(cfg, params, tokens, positions)
    rope = rope_table(cfg)
    if cfg.holds_pages:
        T = next(c.k.shape[1] for c, kind in zip(caches, cfg.kinds)
                 if holds_page(kind))
        # the pages the rows land on, a pool at a time (one, but for a
        # model with window layers)
        pages = {pool: batch([written_pages(pool_tables(g.write_tables, pool),
                                            g.positions, T) for g in groups])
                 for pool in dict.fromkeys(
                     pool_of(kind) for kind in cfg.kinds
                     if holds_page(kind))}
        offs = positions % T
    new_caches = []
    moe_layers = []
    for i, kind in enumerate(cfg.kinds):
        p = layer_params(cfg, params, i)
        c = caches[i]
        if kind == NONE:  # a layer that is a feed-forward alone
            new_caches.append(None)
        else:
            ap = p["attn"]
            h = _norm(cfg, p["ln1"], x)
            if kind in STATE_KINDS:
                project, mix, finish = STATE_MIXERS[kind]
                state, outs = c.arrays(), []
                for g, *rows in zip(groups, *map(
                        split, project(cfg, ap, h, positions))):
                    o, state = _mix_states(cfg, mix, ap, g, rows, state,
                                           several)
                    outs.append(o)
                a = finish(cfg, ap, h, batch(outs))
                new_caches.append(_STATES[kind](**state))
            elif kind == INDEXED:
                attending, new = indexed_project(cfg, ap, h, rope, positions)
                pools = tuple(
                    write_pages(pool, made, pages[pool_of(kind)], offs)
                    for pool, made in zip((c.k, c.v, c.ik), new))
                outs, chosen = zip(*(_unless_idle(g, several, lambda _: (
                    indexed_mix(cfg, rows, pools,
                                pool_tables(g.read_tables, kind), g.positions,
                                g.lengths, impl=impl), ()), ())[0]
                    for g, *rows in zip(groups, *map(split, attending))))
                if taps is not None:
                    taps.extend(chosen)
                a = jnp.einsum("bshk,hkd->bsd", batch(outs),
                               ap["wo"].astype(cfg.dtype))
                new_caches.append(IndexedPagedKVCache(*pools))
            elif kind == LATENT:
                attending, (row,), _ = latent_project(cfg, ap, h, positions)
                pools = (write_pages(c.ckr, row, pages[pool_of(kind)], offs),)
                # a call a group, the step's under one name and the chunk's
                # under another (``latent_mix``); absorbed either way
                outs = [_unless_idle(g, several, lambda _: (
                    latent_mix(cfg, ap, rows, pools,
                               pool_tables(g.read_tables, kind), g.lengths,
                               impl=impl), ()), ())[0]
                    for g, *rows in zip(groups, *map(split, attending))]
                a = latent_finish(cfg, ap, batch(outs))
                new_caches.append(LatentPagedKVCache(*pools))
            elif kind == INDEXED_LATENT:
                attending, new = indexed_latent_project(cfg, ap, h, positions)
                pools = tuple(
                    write_pages(pool, made, pages[pool_of(kind)], offs)
                    for pool, made in zip((c.ckr, c.ik), new))
                outs, chosen = zip(*(_unless_idle(g, several, lambda _: (
                    indexed_latent_mix(cfg, ap, rows, pools,
                                       pool_tables(g.read_tables, kind),
                                       g.positions, g.lengths, impl=impl),
                    ()), ())[0]
                    for g, *rows in zip(groups, *map(split, attending))))
                if taps is not None:
                    taps.extend(chosen)
                a = latent_finish(cfg, ap, batch(outs))
                new_caches.append(IndexedLatentPagedKVCache(*pools))
            else:
                q, k, v = _qkv(cfg, ap, h, None if kind == SPARSE else rope,
                               positions, kind)
                ck = write_pages(c.k, k, pages[pool_of(kind)], offs)
                cv = write_pages(c.v, v, pages[pool_of(kind)], offs)
                if kind == SPARSE:
                    pools = (ck, cv, update_page_means(c.means, ck, *(
                        (g.write_tables, g.positions) for g in groups)))
                    outs, chosen = zip(*(_unless_idle(g, several, lambda _: (
                        sparse_mix(cfg, q_g, pools, g.read_tables, g.positions,
                                   g.lengths, impl=impl), ()), ())[0]
                        for g, q_g in zip(groups, split(q))))
                    if taps is not None:
                        taps.extend(chosen)
                    a = _gated_out(cfg, ap, h, batch(outs))
                    new_caches.append(SparsePagedKVCache(*pools))
                else:
                    # a window layer's call has a name of its own, so a trace
                    # tells its kernel from the full layers'
                    o = batch([paged_attention(
                        q_g, ck, cv, pool_tables(g.read_tables, kind),
                        g.lengths, impl=impl, window=cfg.window(kind),
                        name=("window_attention" if kind == SLIDING
                              else "paged_attention"))
                        for g, q_g in zip(groups, split(q))])
                    a = jnp.einsum("bshk,hkd->bsd", o,
                                   ap["wo"].astype(cfg.dtype))
                    new_caches.append(PagedKVCache(k=ck, v=cv))
            x = _residual(cfg, x, a)
        if cfg.mlp_of(i) == NONE:  # a layer that is a mixer alone
            continue
        mlp_p, layer = stacked_mlp(cfg, params, p, i)
        m, _, moe = _mlp(cfg, mlp_p, _norm(cfg, p["ln2"], x), valid, layer,
                         cfg.mlp_of(i))
        x = _residual(cfg, x, m)
        if moe is not None and several:
            # the experts saw one batch; who sent them which rows is still
            # told a group, as if each group had been a program of its own
            # (of the experts held here, where the layer is a chip's share)
            moe["counts"] = jnp.stack([
                jnp.zeros_like(moe["counts"]).at[held_index(
                    routes, live, cfg.moe_num_experts, cfg.held)].add(
                        1, mode="drop")
                for live, routes in zip(split(valid), split(moe["routes"]))])
        if moe is not None:  # a leading dense layer has none
            moe_layers.append(moe)
    moe = (jax.tree.map(lambda *a: jnp.stack(a), *moe_layers)
           if moe_layers else None)
    return x, new_caches, moe


def _head(cfg: TransformerConfig, params, hidden):
    """hidden [B, S, d] as ``_paged_forward_inplace`` returns it -> logits
    [B, S, vocab]: the final norm and the output projection, of the rows a
    program samples or scores and of no other."""
    return project(cfg, params, final_hidden(cfg, params, hidden))


def _paged_outputs(first, caches, told, logits, taps=None,
                   groups: int = 1):
    """What a paged program returns: ``(first, caches)``, then what it was
    asked to tell beside them (``told``: with ``moe_info`` the expert
    layers' counts and routes, with ``loop_info`` the sampled rows' exit
    passes; None: nothing), then the ``logits`` the
    ids in ``first`` were sampled from where the caller asked for them
    (else None), and last what the 'minicpm4' layers chose (``taps``,
    stacked; ``groups`` > 1: a tuple, a stack a group of rows) where it
    asked for that (the same for the tokens the 'indexed_attention' layers
    picked, bool [layers of the kind, rows, K, context])."""
    if taps is not None:
        taps = (jnp.stack(taps) if groups == 1 else tuple(
            jnp.stack(taps[g::groups]) for g in range(groups)))
    return ((first, caches) + (() if told is None else (told,))
            + (() if logits is None else (logits,))
            + (() if taps is None else (taps,)))


def _check_info(cfg: TransformerConfig, moe_info: bool,
                loop_info: bool = False):
    if moe_info and cfg.mlp != "moe":
        raise ValueError("moe_info needs mlp='moe': a dense model has no "
                         "expert layer to count")
    if loop_info and not cfg.looped:
        raise ValueError("loop_info needs loop_passes > 1: a model that goes "
                         "through its stack once leaves at no pass")


def paged_prefill_into_slot(cfg: TransformerConfig, params, tokens, real_len,
                            cursor, read_row, write_row,
                            caches: List[Any], ids, slot,
                            temperature, seed, step: Optional[StepRows],
                            state_slot=None, *, attn: str,
                            moe_info: bool = False, logits: bool = False,
                            selected: bool = False, loop_info: bool = False):
    """One prefill chunk into ONE slot, through its page table, and with it
    the decode step of the rows that are live (``step``): a turn that holds
    a chunk reads the weights once. tokens: [1, C] — the next C prompt
    tokens, zero-padded past ``real_len`` (so every chunk size compiles to
    the same program). The chunk lands at logical positions [cursor, cursor
    + C) of the slot whose two rows these are: its k/v written straight
    into their pages, attention through the read table
    (``_paged_forward_inplace``). cursor: int32 scalar, the tokens already
    resident (0 cold, the spliced length after a prefix-cache hit); the
    caller advances it by ``real_len``. read_row/write_row: [P] int32
    (None for a model that holds no page) —
    shared (prefix-cache) pages appear in read_row but are redirected to the
    garbage page in write_row, so their content is immutable here. ``attn``:
    the implementation ``ops.paged_attention`` runs ('reference' |
    'pallas').

    The chunk samples (``sample_token``, by ``temperature`` and ``seed``,
    scalars) the token that follows its last REAL row, and where the chunk
    is the prompt's last puts it where the next decode step reads it: ids
    [slots] int32 is the vector ``paged_decode_step`` takes as its tokens,
    ``slot`` the row that takes the sampled id, -1 for a chunk that is not
    the last. The first token so reaches the step without a visit to the
    host.

    ``step`` (``StepRows``, or None: the chunk goes alone, and the ids
    vector comes back as it came but for ``slot``): what
    ``paged_decode_step`` would be given next, its tokens being ``ids``.
    Whatever the kinds of the model's layers, the C chunk rows and the
    [slots] step rows are ONE batch through every projection, the MLP or
    expert layer and ONE write of k/v; what a layer keeps is met in two
    calls a layer, ``[1, C]`` through the slot's row of the tables or on
    the slot's own states and ``[slots, 1]`` through all tables or over all
    slots' states (``_paged_forward_inplace``); the head sees the chunk's
    last real row and the step rows, not the C. An active row's next token
    replaces its entry of ids (sampled at position cursors + 1) exactly as
    the step alone would have it, and the caller advances its cursor by
    one. A row that is not active — the chunk's own slot among them, whose
    cursor IS the chunk's first position — attends nothing, is routed to no
    expert, writes to the garbage page, whatever its tables hold, and keeps
    its states bitwise: the chunk's positions see one write, the chunk's,
    and its slot's states one update. Where NO row is active, what a
    layer does with the step's rows alone (the pass over every slot's
    states, the choice of blocks) is skipped inside the program.

    ``state_slot``: the slot itself, whichever chunk this is — where the
    model has layers that keep a state (``STATE_KINDS``), their states of
    that slot are what
    the chunk continues (from zero when ``cursor`` is 0) and leaves advanced
    by ``real_len`` tokens.

    Caller contract (scheduler-enforced): every page covering the REAL
    tokens [cursor, cursor + real_len) is allocated and OWNED (write_row
    == read_row there); pad positions beyond real_len may fall on
    unallocated entries — their writes redirect to the garbage page and
    their reads are causally masked. cursor + C fits the logical view. No
    active step row is the chunk's slot.

    Returns (ids [slots], caches); with ``moe_info`` (mlp='moe') a third
    value, the expert layers' ``{"counts": [L, 2, E], "routes": [L, 1, rows,
    k]}``: the rows the experts received from the chunk and from the step
    (the kernel ran once a layer over both; without ``step`` [L, E]), rows
    the C of the chunk and then the step's (the chunk's padding past
    ``real_len`` and the rows not active are routed nowhere and not
    counted); with ``logits`` the float32-castable logits the ids were
    sampled from come next, [vocab] at the chunk's last REAL token, and
    with ``step`` [1 + slots, vocab]: that row, then the step's (tests
    compare them with an oracle; the scheduler never asks); with
    ``selected`` the blocks the 'minicpm4' layers chose, bool [layers of
    the kind, 1, C, Hkv, NB], last (with ``step`` a pair: the chunk's, and
    the step rows' [layers of the kind, slots, 1, Hkv, NB]); with
    ``loop_info`` (``loop_passes`` > 1) the third value is ``{"exit_pass":
    [1 + slots] int32}`` (without ``step`` [1]): the pass each SAMPLED row
    left at — the chunk's last real row where the chunk is the prompt's
    last (``slot`` >= 0), then the step's live rows — and 0 for a row whose
    sample nobody takes."""
    _check_info(cfg, moe_info, loop_info)
    if cfg.recurrent and state_slot is None:
        raise ValueError(f"a model with {_state_kinds(cfg)} layers needs "
                         "state_slot: the slot whose states the chunk "
                         "continues")
    taps = [] if selected else None
    C = tokens.shape[1]
    steps = jnp.arange(C, dtype=jnp.int32)[None, :]
    row = lambda table: jax.tree.map(lambda t: t[None], table)
    groups = [_Rows(tokens, steps + cursor, jnp.reshape(cursor, (1,)),
                    row(read_row), row(write_row), steps < real_len,
                    slot=state_slot, real_len=real_len)]
    sample = (jnp.reshape(temperature, (1,)), jnp.reshape(seed, (1,)),
              jnp.reshape(cursor + real_len, (1,)))
    if step is not None:
        live = step.active > 0
        groups.append(_Rows(
            ids[:, None], step.cursors[:, None],
            jnp.where(live, step.cursors, -1), step.read_tables,
            jax.tree.map(lambda t: jnp.where(live[:, None], t, 0),
                         step.write_tables),
            live[:, None], active=step.active))
        sample = tuple(jnp.concatenate(pair) for pair in zip(sample, (
            jnp.where(live, step.temperature, 0.0), step.seeds,
            step.cursors + 1)))
    # the rows that are sampled: the chunk's last real one, then the step's
    rows = lambda hidden: jnp.concatenate(
        [lax.dynamic_slice_in_dim(hidden, real_len - 1, 1, axis=1),
         hidden[:, C:]], axis=1)
    # whose sample somebody takes: the chunk's, if it is its prompt's last
    taken = lambda: jnp.concatenate(
        [jnp.reshape(slot >= 0, (1,))] + ([] if step is None else [live]))
    sampled_logits, new_caches, told = _sampled_logits(
        cfg, params, groups, caches, attn, rows, taken, taps)
    sampled_logits = sampled_logits[0]
    sampled = sample_token(sampled_logits, *sample)
    if step is not None:
        ids = jnp.where(live, sampled[1:], ids)
    ids = jnp.where(jnp.arange(ids.shape[0]) == slot, sampled[0], ids)
    shown = sampled_logits[0] if step is None else sampled_logits
    return _paged_outputs(ids, new_caches,
                          told if moe_info or loop_info else None,
                          shown if logits else None, taps, len(groups))


def paged_decode_step(cfg: TransformerConfig, params, tokens, active,
                      cursors, read_tables, write_tables,
                      caches: List[Any], temperature, seeds, *,
                      attn: str, moe_info: bool = False,
                      logits: bool = False, selected: bool = False,
                      loop_info: bool = False):
    """One fixed-shape decode step over the whole arena, through page
    tables. tokens/active/cursors: [slots] int32; read_tables/write_tables:
    [slots, P] int32. Row s's token is written at ``pool[page, offset]`` of
    logical position cursors[s] and attends [0, cursors[s]] through the
    read table; the caller advances the cursors of its active rows by one.
    An inactive row attends nothing (it streams no page, and the expert
    layer routes it nowhere), but it WRITES at its cursor like any other:
    the caller's tables send that write to the garbage page, or to a
    position the row's own sequence writes again before attending it. A
    'lightning-attn' or 'power-retention' layer's state has no such second
    chance, so an inactive row's state is left bitwise as it was. For a
    model that holds no page the two tables are None.
    ``attn``: the implementation ``ops.paged_attention`` runs ('reference'
    | 'pallas').

    The step samples (``sample_token``; temperature [slots] float32, seeds
    [slots] uint32, the position of the new token cursors + 1) and returns
    ids [slots] int32: an active row's next token, an inactive row's
    ``tokens`` entry as it came. The result is the next step's ``tokens``
    as it stands, so a token reaches the step that consumes it without a
    visit to the host.

    Returns (ids [slots], caches); with ``moe_info`` (mlp='moe') a third
    value, the expert layers' ``{"counts": [L, E], "routes": [L, slots, 1,
    k]}`` over the active rows; with ``logits`` the logits [slots, vocab]
    the ids were sampled from come next (tests compare them with an
    oracle; the scheduler never asks); with ``selected`` the blocks the
    'minicpm4' layers chose, bool [layers of the kind, slots, 1, Hkv, NB],
    last; with ``loop_info`` (``loop_passes`` > 1) the third value is
    ``{"exit_pass": [slots] int32}``, the pass each active row left at (0
    for a row that is not active)."""
    _check_info(cfg, moe_info, loop_info)
    taps = [] if selected else None
    groups = [_Rows(tokens[:, None], cursors[:, None],
                    jnp.where(active > 0, cursors, -1), read_tables,
                    write_tables, active[:, None] > 0, active=active)]
    all_logits, new_caches, told = _sampled_logits(
        cfg, params, groups, caches, attn, lambda hidden: hidden,
        lambda: active > 0, taps)
    all_logits = all_logits[:, 0]
    sampled = sample_token(all_logits,
                           jnp.where(active > 0, temperature, 0.0), seeds,
                           cursors + 1)
    ids = jnp.where(active > 0, sampled, tokens)
    return _paged_outputs(ids, new_caches,
                          told if moe_info or loop_info else None,
                          all_logits if logits else None, taps)


def paged_verify_step(cfg: TransformerConfig, params, tokens, active,
                      cursors, read_tables, write_tables,
                      caches: List[PagedKVCache], *, attn: str,
                      moe_info: bool = False):
    """Speculative-decoding verify: score K candidate tokens per slot in
    ONE fixed-shape call over the slots axis. active: [slots] int32, the
    window rows of each slot that carry a token (0 for a row without a live
    sequence, which attends nothing and whose logits are dropped). cursors:
    [slots] int32, as in ``paged_decode_step``. tokens: [slots, K] int32 —
    each slot's [next_token, d_1..d_{K-1}] placed at logical positions
    [cursor, cursor + K); logits[s, j] is the target model's distribution
    over the token FOLLOWING position cursor + j, i.e. the distribution the
    sequential ``paged_decode_step`` loop would produce after accepting
    d_1..d_j: each query row reduces over blocks of pages in ascending order
    under a full-width mask, exactly the reduction a K=1 decode performs.
    ``attn``: the implementation ``ops.paged_attention`` runs ('reference' |
    'pallas').

    How far a cursor advances is the caller's decision, made afterwards
    (accept-prefix + corrected resample): accepted slots move to cursor +
    accepted + 1, rejected tails are rewound by simply not advancing past
    them. KV for all K positions IS written — rejected positions hold stale
    values that the next round's writes overwrite before anything attends
    to them (the update-before-attend invariant); shared / unallocated
    write entries redirect to the garbage page, so a verify can never
    scribble on prefix-cache pages.

    Returns (logits [slots, K, vocab], caches); with ``moe_info``
    (mlp='moe') a third value, the expert layers' ``{"counts": [L, E],
    "routes": [L, slots, K, k]}`` over the used rows."""
    _check_info(cfg, moe_info)
    if cfg.recurrent:
        raise ValueError(
            f"paged_verify_step cannot run a model with {_state_kinds(cfg)} "
            "layers: a rejected draft would have to rewind their states, "
            "and no snapshot is kept")
    if cfg.looped:
        raise ValueError(
            "paged_verify_step cannot run a model with loop_passes > 1: no "
            "drafter proposes for one (its slot arena holds one (K, V) a "
            "layer)")
    K = tokens.shape[1]
    steps = jnp.arange(K, dtype=jnp.int32)[None]
    hidden, new_caches, moe = _paged_forward_inplace(
        cfg, params, [_Rows(tokens, cursors[:, None] + steps,
                            jnp.where(active > 0, cursors, -K), read_tables,
                            write_tables, steps < active[:, None])],
        caches, attn)
    return _paged_outputs(_head(cfg, params, hidden), new_caches,
                          moe if moe_info else None, None)


@partial(jax.jit, static_argnums=(0, 4, 5, 6))
def generate(cfg: TransformerConfig, params, prompt, key,
             max_new_tokens: int, temperature: float = 0.0, top_k: int = 0):
    """prompt [B, S] -> generated [B, max_new_tokens] (greedy or sampled).
    One compiled program: prefill + lax.scan over decode steps."""
    batch, prompt_len = prompt.shape
    if prompt_len + max_new_tokens > cfg.max_seq_len:
        # Position tables are sized cfg.max_seq_len; past that, gather clamps
        # and decodes silently wrong. Fail loudly at trace time instead.
        raise ValueError(
            f"prompt_len ({prompt_len}) + max_new_tokens ({max_new_tokens}) "
            f"exceeds cfg.max_seq_len ({cfg.max_seq_len})"
        )
    caches = init_caches(cfg, batch, prompt_len + max_new_tokens)
    logits, caches = prefill(cfg, params, prompt, caches)
    temperatures = jnp.full((batch,), temperature, jnp.float32)
    seeds = jax.random.bits(key, (batch,), jnp.uint32)

    def body(carry, position):
        logits, caches = carry
        tok = sample_token(logits, temperatures, seeds,
                           jnp.full((batch,), position), top_k)
        logits, caches = decode_step(cfg, params, tok[:, None], caches)
        return (logits, caches), tok

    (_, _), toks = lax.scan(body, (logits, caches),
                            prompt_len + jnp.arange(max_new_tokens))
    return toks.T  # [B, T]
