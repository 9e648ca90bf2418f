"""What the scheduler's programs do, counted on the host by layer kind.

``Work`` is built once from the model's config and the scheduler's sizes;
the scheduler tells it of every attention-bearing program call
(``record``), hands it what an expert model's programs return beside ids
and caches (``routed``) and lets it sample the pools a turn (``sample``);
``stats()`` is the kinds' share of ``scheduler_stats()``. Host-side mirror
arithmetic on cursors and shapes, no device readback on the hot loop: a
program's device time is read from a profiler trace, by its name.

A kind's counters are said once, in ``_KINDS``: the function that counts one
call of the kind's layers (a layer-call: one layer in one program run) and
the keys it owns, which a model shows if and only if it has layers of the
kind ('lightning-attn', 'power-retention', 'mamba2', 'minicpm4',
'indexed_attention', 'sliding_attention', 'latent_attention',
'indexed_latent_attention'; plain 'attention' is counted by
``attn_*`` alone); ``attn_*`` always (they count pages: a true 0 for a model
without). What a token leaves in a page, by kind, is ``token_bytes``. A
LOOPED model (``loop_passes`` > 1) shows ``loop_*`` beside them, and no
other does: its ``attn_*`` and ``token_bytes`` count every pass's rows.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional

import numpy as np

from ray_tpu._private.metrics import Counter
from ray_tpu.models.transformer import (ATTENTION, INDEXED, INDEXED_LATENT,
                                        LATENT, LATENT_KINDS, LINEAR, MAMBA,
                                        RETENTION, SLIDING, SPARSE,
                                        STATE_KINDS, holds_page, state_shapes)
from ray_tpu.ops.indexed_attention import (SELECT_ROWS, cell_tokens,
                                           context_tokens, select_lanes)
from ray_tpu.ops.latent_attention import latent_tiles, pool_width
from ray_tpu.ops.moe import SWIGLU, walk_lengths
from ray_tpu.ops.paged_attention import streamed_tokens, tile_sizes

_m_attn_bytes = Counter(
    "ray_tpu_serve_attn_bytes_moved_total",
    "KV-cache bytes the paged attention lane streamed per program call "
    "(host-side mirror arithmetic, labelled by implementation: whole "
    "blocks of pages up to each sequence's cursor)")


def _linear(work, n, layers, qk, cursors, real):
    """Layer-calls of the chunked scan; live rows x layers of the one-row
    update."""
    n["linear_step_rows" if qk == 1 else "linear_chunk_calls"] += (
        layers * len(cursors))


def _retention(work, n, layers, qk, cursors, real):
    """As ``_linear``, and the real tokens the chunk calls carried."""
    calls = layers * len(cursors)
    if qk == 1:
        n["retention_step_rows"] += calls
    else:
        n["retention_chunk_calls"] += calls
        n["retention_chunk_tokens"] += calls * real


def _ssm(work, n, layers, qk, cursors, real):
    """As ``_retention``, and the bytes the least handling of the states
    moves: a live row's two states (the convolution's inputs and the scan's
    state) of a layer read once and written once, a step's row or a chunk's
    slot alike."""
    calls = layers * len(cursors)
    if qk == 1:
        n["ssm_step_rows"] += calls
    else:
        n["ssm_chunk_calls"] += calls
        n["ssm_chunk_tokens"] += calls * real
    n["ssm_state_bytes_moved"] += 2 * calls * work.ssm_row_bytes


def _sparse(work, n, layers, qk, cursors, real):
    """Block-selected layers: what each query attends is a function of its
    position alone (``SparseSizes.attended_tokens``). Query rows x layers
    (real tokens of a chunk, live rows of a step), those at or under
    ``dense_len``, the tokens of the blocks they attended over the tokens of
    their contexts, and of both a step's share. Returns (attended, fetched)
    a layer as the paged kernel's are counted: a step streams each row's
    chosen blocks once a K/V group, in whole kernel blocks of the compacted
    table; a chunk (one row) streams its slot's context up to each tile of
    32 queries, which attend their mean choice of it."""
    cfg, sizes = work.cfg, work.cfg.sparse
    t = np.asarray(cursors)[:, None] + np.arange(real)  # [rows, real]
    att = sizes.attended_tokens(t)
    attended, context = int(att.sum()), int((t + 1).sum())
    n["sparse_rows"] += layers * t.size
    n["sparse_rows_dense"] += layers * int((t + 1 <= sizes.dense_len).sum())
    n["sparse_tokens_attended"] += layers * attended
    n["sparse_tokens_context"] += layers * context
    if qk == 1:
        n["sparse_step_tokens_attended"] += layers * attended
        n["sparse_step_tokens_context"] += layers * context
        block = work.sparse_step_block
        return (cfg.kv_heads * attended,
                cfg.kv_heads * int((-(-att // block)).sum()) * block)
    starts = np.arange(0, real, 32)
    ends = np.minimum(starts + 32, real)
    return (sum(int(att[0, lo:hi].mean()) for lo, hi in zip(starts, ends)),
            int((-(-(t[0, 0] + ends) // 512)).sum()) * 512)


def _window(work, n, layers, qk, cursors, real):
    """Window layers beside full ones: what the mask admits, by kind of
    layer and by work, summed over rows and layers — the keys a decode row
    reads (``*_step_keys``), the (query, key) pairs of a chunk's real rows
    (``*_chunk_pairs``)."""
    t = np.asarray(cursors)[:, None] + np.arange(real)  # [rows, real]
    what = "step_keys" if qk == 1 else "chunk_pairs"
    n["full_attn_" + what] += (work.paged_layers - layers) * int(
        (t + 1).sum())
    n["window_attn_" + what] += layers * int(
        np.minimum(t + 1, work.cfg.sliding_window).sum())


def _indexed(work, n, layers, qk, cursors, real):
    """Token-selected layers: query rows x layers' contexts, each scored
    whole by the indexer (``indexed_tokens_scored``: index keys read and
    dotted with every index head) and attended as far as ``topk``
    (``IndexerSizes.attended_tokens``, a function of the position alone),
    and of the three a step's share. Returns (attended, fetched) a layer as
    the paged kernel's are counted: a step reads its rows' chosen tokens
    once, gathered into a run of their own (every K/V head shares the
    choice); a chunk (one row) streams its slot's context up to each CELL
    of query tiles (``ops.indexed_attention.cell_tokens``: a key tile is
    copied in once a cell) in whole key tiles of 512, which attend their
    mean choice of it."""
    sizes = work.cfg.indexer
    # the selection's kernel: a chunk's rows are all ``qk`` of its tokens;
    # a step's are the slots, the live ones at their cursors and the others
    # at 0 — one tile of them, so the largest cursor is its reach (of more
    # slots than a tile holds, every tile is counted at that reach)
    rows = np.arange(qk) + cursors[0] if qk > 1 else np.full(
        work.slots, max(cursors, default=0))
    lanes = select_lanes(rows, work.indexed_context, np)  # [tiles]
    n["indexed_select_lanes"] += layers * SELECT_ROWS * int(lanes.sum())
    n["indexed_select_lanes_table"] += (
        layers * SELECT_ROWS * len(lanes) * work.indexed_context)
    t = np.asarray(cursors)[:, None] + np.arange(real)  # [rows, real]
    attended = int(sizes.attended_tokens(t).sum())
    context = int((t + 1).sum())
    n["indexed_tokens_scored"] += layers * context
    n["indexed_tokens_attended"] += layers * attended
    n["indexed_tokens_context"] += layers * context
    if qk == 1:
        n["indexed_step_tokens_attended"] += layers * attended
        n["indexed_step_tokens_context"] += layers * context
        return attended, attended
    starts = np.arange(0, real, work.indexed_cell_tokens)
    ends = np.minimum(starts + work.indexed_cell_tokens, real)
    att = sizes.attended_tokens(t[0])
    return (sum(int(att[lo:hi].mean()) for lo, hi in zip(starts, ends)),
            int((-(-(t[0, 0] + ends) // 512)).sum()) * 512)


def _latent(work, n, layers, qk, cursors, real):
    """Latent layers: what a step's rows and a chunk's queries attend, by
    position alone, summed over rows and layers — the latents of their
    contexts (``latent_tokens_context``), of which a step's share
    (``latent_step_tokens_context``) and a chunk's (query, key) pairs
    (``latent_chunk_pairs``: every head of a pair costs ``2 (rank + rope) + 2
    rank`` operations), and the bytes the least reading moves
    (``latent_bytes_moved``: a step's row its whole context, a chunk its
    context once, and each its own tokens written, ``rank + rope`` values a
    token: the pool's padding is not work). What the kernel streams
    in whole blocks is counted by ``attn_*`` as for every paged kind, a
    slot's blocks once a CELL of the kernel's own rule (``Work.record``
    hands ``streamed_tokens`` the kind's ``latent_tiles``: all of a chunk's
    queries where the paged kernel's rule counts a 64-token tile)."""
    t = np.asarray(cursors)[:, None] + np.arange(real)  # [rows, real]
    context = int((t + 1).sum())
    n["latent_tokens_context"] += layers * context
    if qk == 1:
        n["latent_step_tokens_context"] += layers * context
        read = context
    else:
        n["latent_chunk_pairs"] += layers * context
        read = int(t[:, -1].sum()) + len(cursors)
    n["latent_bytes_moved"] += layers * (read + t.size) * work.itemsize * (
        work.cfg.latent_kv_rank + work.cfg.latent_rope_dim)


def _picked(work, n, layers, qk, cursors, real):
    """Latent layers with an indexer: the (query, key) pairs the index heads
    score (``picked_index_pairs``: every query row its whole causal context,
    every layer) of which a step's (``picked_step_index_pairs``), the pairs
    attended (``picked_chosen_pairs``: ``min(t + 1, topk)`` a query, a
    function of its position alone) of which a step's
    (``picked_step_chosen_pairs``), the bytes the chosen latent rows are
    (``picked_latent_bytes``: ``rank + rope`` values a chosen pair, read
    once a query and serving every head) and the index keys' the scoring
    reads at least (``picked_index_key_bytes``: a step's row its whole
    context, a chunk its context once, ``index_head_dim`` values a token),
    and what the chunk's kernel does itself where it runs (the 'pallas'
    lane; ``ops.picked_latent_attention``, step 3): the chosen rows it moved
    out of a slot's context (``picked_rows_in_kernel``: a chunk's share of
    ``picked_chosen_pairs``; a step's rows are gathered) and the context
    tokens whose pages it copied in for that (``picked_context_tokens_
    copied``: whole pages up to the chunk's last position, a layer).
    Returns (attended, fetched) a layer as the paged kernel's are counted:
    the chosen rows, each fetched once."""
    sizes, cfg = work.cfg.indexer, work.cfg
    t = np.asarray(cursors)[:, None] + np.arange(real)  # [rows, real]
    attended = int(sizes.attended_tokens(t).sum())
    context = int((t + 1).sum())
    n["picked_index_pairs"] += layers * context
    n["picked_chosen_pairs"] += layers * attended
    if qk == 1:
        n["picked_step_index_pairs"] += layers * context
        n["picked_step_chosen_pairs"] += layers * attended
        scored = context
    else:
        scored = int(t[:, -1].sum()) + len(cursors)
        if work.lane == "pallas":
            T, P = work._page_tokens, work._pages_per_slot
            last = np.asarray(cursors) + qk - 1
            n["picked_rows_in_kernel"] += layers * attended
            n["picked_context_tokens_copied"] += layers * T * int(
                np.minimum(last // T + 1, P).sum())
    n["picked_latent_bytes"] += layers * attended * work.itemsize * (
        cfg.latent_kv_rank + cfg.latent_rope_dim)
    n["picked_index_key_bytes"] += (layers * scored * work.itemsize
                                    * sizes.indexer_head_dim)
    return attended, attended


def token_bytes(cfg, kind: str, itemsize: int) -> int:
    """Bytes the attended rows of one token take in a page of a layer of
    ``kind``: K and V of all K/V heads, or for the latent kinds the one
    row of a latent and a rotated key (``ops.latent_attention.join``, its
    padding to whole lane tiles held and moved too) that is both (an
    'indexed_latent_attention' layer's index key, beside it, is not
    attended) — once a pass of a looped stack, each of which leaves its own
    (``cfg.loop_passes``; 1: once)."""
    if kind in LATENT_KINDS:
        return pool_width(cfg.latent_kv_rank,
                          cfg.latent_rope_dim) * itemsize
    return 2 * cfg.kv_heads * cfg.head_dim * itemsize * cfg.loop_passes


# kind -> (what counts one call of its layers, the keys it owns); the last
# two of the window kind's are ``sample``'s
_KINDS = {
    LINEAR: (_linear, ("linear_chunk_calls", "linear_step_rows")),
    RETENTION: (_retention, ("retention_chunk_calls",
                             "retention_chunk_tokens", "retention_step_rows")),
    MAMBA: (_ssm, ("ssm_chunk_calls", "ssm_chunk_tokens", "ssm_step_rows",
                   "ssm_state_bytes_moved")),
    SPARSE: (_sparse, ("sparse_rows", "sparse_rows_dense",
                       "sparse_tokens_attended", "sparse_tokens_context",
                       "sparse_step_tokens_attended",
                       "sparse_step_tokens_context")),
    INDEXED: (_indexed, ("indexed_tokens_scored", "indexed_tokens_attended",
                         "indexed_tokens_context",
                         "indexed_step_tokens_attended",
                         "indexed_step_tokens_context",
                         "indexed_select_lanes",
                         "indexed_select_lanes_table")),
    LATENT: (_latent, ("latent_tokens_context", "latent_step_tokens_context",
                       "latent_chunk_pairs", "latent_bytes_moved")),
    INDEXED_LATENT: (_picked, ("picked_index_pairs",
                               "picked_step_index_pairs",
                               "picked_chosen_pairs",
                               "picked_step_chosen_pairs",
                               "picked_latent_bytes",
                               "picked_index_key_bytes",
                               "picked_rows_in_kernel",
                               "picked_context_tokens_copied")),
    SLIDING: (_window, ("window_attn_step_keys", "full_attn_step_keys",
                        "window_attn_chunk_pairs", "full_attn_chunk_pairs",
                        "window_tokens_held", "window_tokens_unreleased")),
}
_ATTN = ("attn_bytes_moved", "attn_tokens_attended", "attn_tokens_fetched")
_EXPERTS = ("moe_live_rows", "moe_layer_calls", "moe_rows_routed",
            "moe_experts_hit", "moe_max_expert_rows",
            # the kernel's walk (``ops.moe._visits``): a program's row groups
            # are ONE kernel call a layer; of its grid's visits those that
            # carry rows, the rest padding
            "moe_kernel_calls", "moe_visits", "moe_grid_visits")
# beside them where the expert layers have a shared expert: the rows it took
# (every live row of every expert layer-call, times the shared experts)
_SHARED = "moe_shared_rows"
# and where the layer is a chip's share of its experts (``cfg.held``): every
# choice the live rows made, of which ``moe_rows_routed`` landed here
_CHOSEN = "moe_routes_chosen"
# a looped model's: passes x program runs, layer applications (passes x
# layers x program runs), and beside them the sampled rows by the pass they
# left at, ``_EXIT`` + "1" .. the last pass
_LOOP = ("loop_passes", "loop_layer_calls")
_EXIT = "loop_exit_pass_"


class Work:
    """The counters of one scheduler. ``lane``: the paged-attention
    implementation that runs (``resolve_impl``'s answer); ``itemsize``: the
    bytes of one element of the K/V pools."""

    def __init__(self, cfg, *, slots: int, page_tokens: int,
                 pages_per_slot: int, lane: str, itemsize: int):
        self.cfg, self.lane, self.itemsize = cfg, lane, itemsize
        self._page_tokens, self._pages_per_slot = page_tokens, pages_per_slot
        kinds = cfg.kinds
        self._kinds = [(_KINDS[kind][0], kinds.count(kind))
                       for kind in _KINDS if kind in kinds]
        # the pools a page holds rows in: a layer's, once a pass
        self.paged_layers = cfg.loop_passes * sum(
            holds_page(kind) for kind in kinds)
        self._window_layers = kinds.count(SLIDING)
        # all kv heads of one token's K (or V) row, and the query rows
        # that share it; a latent layer's one row a token, in two halves for
        # the same arithmetic, shared by every head
        latent = bool(set(LATENT_KINDS) & set(kinds))
        self._row_bytes = token_bytes(cfg, LATENT if latent else ATTENTION,
                                      itemsize) // (2 * cfg.loop_passes)
        self._group = cfg.num_heads // (1 if latent else cfg.kv_heads)
        # the dense latent kernel's own tiles (``_tiles``): not the picked
        # form's, which counts its own fetches
        self._latent = LATENT in kinds
        if SPARSE in kinds:
            # tokens of one block of the step's kernel over a row's table
            # of chosen pages (``sparse_attention._step_attention``)
            sizes = cfg.sparse
            self.sparse_step_block = page_tokens * tile_sizes(
                1, cfg.num_heads // cfg.kv_heads, page_tokens,
                sizes.max_chosen_blocks() * sizes.pages_per_block,
                self._row_bytes)[0]
        if INDEXED in kinds:
            self.indexed_cell_tokens = cell_tokens(cfg.num_heads)
            self.slots = slots
            self.indexed_context = context_tokens(pages_per_slot,
                                                  page_tokens)
        if MAMBA in kinds:
            # one row's two float32 states of ONE layer
            self.ssm_row_bytes = 4 * sum(
                math.prod(shape) for shape in state_shapes(
                    cfg, MAMBA, 1).values())
        # a float32 state a slot a layer that keeps one, by kind
        state_bytes = 4 * sum(
            math.prod(shape) for kind in kinds if kind in STATE_KINDS
            for shape in state_shapes(cfg, kind, slots).values())
        # an expert model's programs are asked for the rows each expert
        # received (``moe_info``), a looped model's for the pass each
        # sampled row left at (``loop_info``): handed to ``routed``
        self.counts_experts = cfg.mlp == "moe"
        self.program_keywords = {
            **({"moe_info": True} if self.counts_experts else {}),
            **({"loop_info": True} if cfg.looped else {})}
        self._n = dict.fromkeys(
            _ATTN + tuple(key for kind in _KINDS if kind in kinds
                          for key in _KINDS[kind][1])
            + (_EXPERTS if self.counts_experts else ())
            + ((_SHARED,) if cfg.moe_shared_experts else ())
            + ((_CHOSEN,) if cfg.held else ())
            + ((_LOOP + tuple(_EXIT + str(t) for t in range(
                1, cfg.loop_passes + 1))) if cfg.looped else ()), 0)
        if state_bytes:
            self._n.update(state_slots=slots, state_bytes=state_bytes)

    def _tiles(self, qk: int):
        """The latent kernel's own (pages a block, query tokens a cell) of a
        K = ``qk`` call; None for every other kind: ``tile_sizes``'."""
        if not self._latent:
            return None
        cfg = self.cfg
        return latent_tiles(
            qk, cfg.num_heads, self._page_tokens, self._pages_per_slot,
            pool_width(cfg.latent_kv_rank, cfg.latent_rope_dim),
            self.itemsize)

    def record(self, qk: int, cursors: List[int], idle_rows: int = 0,
               real: Optional[int] = None) -> None:
        """One attention-bearing program call: a K = ``qk`` window for every
        row of ``cursors`` (its attention cursor), ``idle_rows`` other rows
        that attend nothing, ``real`` real tokens of the window (a chunk's;
        default all). Every kind the model has counts its own; then what
        the call streamed through the page tables: ``attn_tokens_attended``
        over ``attn_tokens_fetched`` is the block fill share of the layers
        without a window (per layer: every such layer repeats the same
        fetches; where a kind chooses its blocks, as that kind says),
        ``attn_bytes_moved`` the K and V rows read through the tables plus
        the ``qk`` freshly written rows a slot, over every layer that holds
        pages."""
        n, real, streamed = self._n, qk if real is None else real, None
        for count, layers in self._kinds:
            streamed = count(self, n, layers, qk, cursors, real) or streamed
        if not self.paged_layers:
            return
        row = self._row_bytes
        dense = lambda window: streamed_tokens(
            self.lane, qk, cursors, idle_rows,
            self._group, self._page_tokens, self._pages_per_slot, row,
            window, self._tiles(qk))
        attended, fetched = streamed or dense(None)
        n["attn_tokens_attended"] += attended
        n["attn_tokens_fetched"] += fetched
        written = (len(cursors) + idle_rows) * qk
        windowed = self._window_layers
        moved = 2 * (self.paged_layers - windowed) * row * (fetched + written)
        if windowed:
            moved += 2 * windowed * row * (
                dense(self.cfg.sliding_window)[1] + written)
        n["attn_bytes_moved"] += moved
        _m_attn_bytes.inc(moved, labels={"lane": self.lane})

    def routed(self, returned: tuple, live_rows: int,
               step: bool = True) -> None:
        """Add up one finished program's expert counts, the first of what it
        ``returned`` beside ids and caches (call after a wait on that
        program: the copy below then waits for nothing), beside the
        ``live_rows`` the host handed it: ``moe_rows_routed`` == live rows x
        top-k x layers exactly, or a row was dropped — where the layer is a
        chip's share of its experts (``cfg.held``) that holds of
        ``moe_routes_chosen``, the routes that landed here plus those the
        device counted as left out, and ``moe_rows_routed`` and the counts
        behind it are over the experts held. A chunk's program that
        took the step along tells the two groups' rows apart ([layers, 2,
        experts]): each is a layer-call of its own, the step's only where a
        row was live (``step``) — and both are ONE call of the experts'
        kernel a layer (``moe_kernel_calls``), whose walk over the call's
        static pairs (``routes``' shape) ``ops.moe.walk_lengths`` repeats:
        ``moe_visits`` of ``moe_grid_visits`` carried rows. A LOOPED model's
        program returns the pass each sampled row left at instead (0: a row
        nobody samples): one run of ``loop_passes`` passes over every
        layer, its rows counted by exit pass."""
        n = self._n
        cfg = self.cfg
        if cfg.looped:
            exits = np.asarray(returned[0]["exit_pass"]).ravel()
            n["loop_passes"] += cfg.loop_passes
            n["loop_layer_calls"] += cfg.loop_passes * cfg.num_layers
            for t, rows in enumerate(np.bincount(
                    exits, minlength=cfg.loop_passes + 1)[1:], start=1):
                n[_EXIT + str(t)] += int(rows)
            return
        c = np.asarray(returned[0]["counts"])  # [layers, experts]
        # the experts saw the program's rows as one batch, whoever sent them
        visits, grid = walk_lengths(
            c if c.ndim == 2 else c.sum(axis=1),
            math.prod(returned[0]["routes"].shape[1:]), cfg.embed_dim,
            cfg.mlp_width("moe"), np.dtype(cfg.dtype).itemsize,
            2 + (cfg.moe_activation == SWIGLU))
        n["moe_kernel_calls"] += c.shape[0]
        n["moe_visits"] += visits
        n["moe_grid_visits"] += c.shape[0] * grid
        if c.ndim == 3:
            c = c[:, :1 + step].reshape(-1, c.shape[-1])
        n["moe_live_rows"] += live_rows
        n["moe_layer_calls"] += c.shape[0]
        n["moe_rows_routed"] += int(c.sum())
        n["moe_experts_hit"] += int((c > 0).sum())
        n["moe_max_expert_rows"] += int(c.max(axis=1).sum())
        chosen = int(c.sum())
        if _CHOSEN in n:
            chosen += int(np.asarray(returned[0]["left_out"]).sum())
            n[_CHOSEN] += chosen
        if _SHARED in n:  # every live row of every layer-call took it
            n[_SHARED] += (self.cfg.moe_shared_experts * chosen
                           // self.cfg.moe_top_k)

    def sample(self, pools) -> None:
        """A turn's sample, behind its releases and allocations: the tokens
        a pool with a window holds, beside those it would hold of the same
        sequences had nothing been released."""
        for pool in pools:
            if pool.window is not None:
                T = pool.arena.page_tokens
                self._n["window_tokens_held"] += T * pool.arena.pages_in_use
                self._n["window_tokens_unreleased"] += T * pool.filled

    def stats(self) -> Dict[str, int]:
        return dict(self._n)
