"""Continuous (iteration-level) batching scheduler for LLM serve replicas.

The scheduler owns ``slots`` sequence slots over a PAGED KV pool
(``models.decode.PagedKVCache``: pages of ``page_tokens`` tokens, a page
table a slot) and drives ONE fixed-shape jitted decode step over all slots
per iteration:

  * new requests are admitted into free slots *between* decode iterations
    and prefilled in ``prefill_chunk``-token chunks (one chunk per
    iteration), so a long prompt can never stall in-flight decodes;
  * finished / EOS / cancelled sequences retire their slot (and pages)
    immediately — the freed slot is re-admitted on the very next iteration;
  * every sampled token streams out to its request's asyncio queue the
    iteration it is produced, so streaming and non-streaming consumers ride
    the same batched program (no per-stream single-sequence decode loops).

The device-side program shapes are compiled once (``paged_prefill_chunk``,
``paged_decode_step``) and the host-side loop only decides *which* sequences
occupy which slots and pages. All jax work runs on the scheduler's own
thread — the replica's asyncio event loop only ever touches queues.

The loop runs ONE STEP AHEAD of what it has read. Both programs sample
(``decode.sample_token``: argmax at temperature 0, else a draw keyed by the
request's seed and the token's position) and hand the ids on in a device
vector ``[slots]`` that the next program takes as its tokens, so a token
never visits the host on its way to the step that consumes it. A turn is:
admit; pick the chunk that is due, if one is; build the decode rows of step
n+1 from what the host knows without step n's result (cursors advance by
one a live row; a row whose budget step n exhausts is not in n+1); dispatch
ONE program; THEN wait for the ids of the program of the turn before (4
bytes a slot), emit and retire. What the host learns only from an id — EOS
— and what it learns between turns — a cancellation, an exhausted pool —
therefore arrives one step late: the row may ride in one more program,
whose id is discarded (``discarded_rows``) and never emitted
(``_release_slot_resources`` says why its K/V write harms nobody). The
speculative path needs whole logits on the host to accept and resample, so
it reads before every dispatch.

A TURN READS THE WEIGHTS ONCE, whatever the kinds of the model's layers.
Where there is a chunk the one program is the chunk's
(``paged_prefill_chunk``), and it takes the turn's decode rows along: chunk
rows and step rows are one batch through every projection and the MLP or
expert layer, and only what a layer keeps — its pages, or its states a
slot — is met a group at a time, at the chunk's shape and at the step's
(``decode.paged_prefill_into_slot``). Where there is none it is the plain
step (``paged_decode_step``). Such a program counts for what it carried: in
``prefill_chunks`` and ``prefill_tokens``, and, if a row was live, in
``decode_steps`` too (``fused_turns`` counts those, ``fused_step_rows``
their rows). A prompt whose last chunk rides in it decodes from the NEXT
turn on: its first token exists only at this program's end.

The scheduler measures the gap it makes. A sampled token becomes an emitted
one at the READ of its program (``_collect``; a speculative round's tokens
in ``_decode_spec``): the tokens of one read take ONE stamp and leave in one
call. Every token after its sequence's first has a gap, this read's stamp
less the stamp of the read that emitted the one before it, counted by what
the device was given in between: the scheduler keeps the prompt tokens it
has dispatched (``prefill_tokens``), notes the count on every launch, and a
gap is beside prefill if the count rose between the two programs that
sampled the two tokens (``gap_prefill_*``), else plain (``gap_plain_*``).
By work, never by a program's name: the program that carries prompt rows
and decode rows counts the same way as the two it replaced.

There is one KV layout and one path to the kernel. A slot owns a page table
instead of a contiguous worst-case ``arena_len`` range, so long/idle
sequences reserve no memory they never use; each program writes the new
tokens' k/v into their pages and attends through the table
(``ops.paged_attention``, whose ``resolve_impl`` picks the kernel on a TPU
and the pure-JAX reference elsewhere, once, at build). The device holds the
pages and nothing else: each slot's cursor is ``_Seq.cursor`` here on the
host, handed to every program as an argument beside the tables, so an
admission, a retirement or a rejected draft runs no device program. On top
of paging a PREFIX/RADIX CACHE (``serve/_private/paging.RadixCache``) makes
admitting a request whose prompt shares a cached prefix a page-table
splice + cursor jump instead of a re-prefill; eviction is LRU over
refcount-0 nodes under arena pressure.

The FLEET phase on top: (1) the radix cache's chain-hash digest is exported
through ``prefix_digest()`` so the router can steer prompts to the replica
already holding their prefix; (2) a request that arrives with a
``fleet_hint`` (holder replica handle + matched depth) PULLS the matched
prefix pages from the holder before admission — the pull runs on a
dedicated worker thread (the scheduler thread never blocks on a peer), the
pulled KV is spliced into the local arena + radix tree, and admission then
hits it like any local prefix; a failed or timed-out pull falls back to a
cold prefill, bit-identical by construction; (3) speculative decoding: a
``speculative.Drafter`` proposes up to ``spec_k`` tokens per slot and ONE
fixed-shape ``paged_verify_step`` call (the third and only third compiled
program) scores them all, with exact accept-prefix + corrected-resample
semantics (temperature-0 output is the sequential greedy path's, token for
token).

LAYERS OF OTHER KINDS (``TransformerConfig.layer_kinds``). The pool is by
kind: pages for an attention layer, pages plus a pooled key row a page for a
'minicpm4' layer (which attends the blocks it chooses), a fixed float32 state
a slot for a 'lightning-attn' or 'power-retention' layer. A chunk continues
its slot's states and takes them as zero when it starts at position 0, so an
admission still runs no device program; a step, alone or along in a chunk's
program, leaves the states of a row that is free or mid-prefill bitwise
alone. What a token leaves in such a state cannot be cut
at a page boundary or rewound, and no snapshot is kept: the prefix cache,
prefix export / migration and the speculative programs refuse a model with
such layers when the scheduler is built.

WINDOW LAYERS ('sliding_attention'). Their pages are a pool and an arena of
their own beside the full layers', through tables of their own (every paged
program takes both pairs, ``_tables``). A slot holds there the pages its
window still covers and no more, whatever the context: before a turn
allocates what its rows will write (``_ensure_pages``) the pages wholly
behind ``cursor - sliding_window + 1`` go back to the arena, their table
entries point at the garbage page, and the kernel's walk starts behind them
(``ops.paged_attention``), so nothing ever reads them. The pool's size
follows from ``slots``, the window, ``prefill_chunk`` and ``page_tokens``
(``window + chunk`` tokens and a page a slot): no option sets it, and
``kv_pages`` keeps meaning the full layers' pool. A spliced prefix would
need the window layers' last window of it kept too, and a rejected draft's
released pages back: the prefix cache, prefix export / migration and the
speculative programs refuse a model with such layers when the scheduler is
built.

Knobs: ``RAY_TPU_SERVE_SLOTS`` (slots), ``RAY_TPU_SERVE_PREFILL_CHUNK``
(prefill chunk tokens), ``RAY_TPU_SERVE_PAGE_TOKENS``,
``RAY_TPU_SERVE_KV_PAGES`` (0 = size the pool to every slot's worst case),
``RAY_TPU_SERVE_PREFIX_CACHE``, ``RAY_TPU_SERVE_MIGRATION_BUDGET`` (pages
per cross-replica pull), ``RAY_TPU_SERVE_SPEC_K`` (draft tokens per verify
round), ``RAY_TPU_SERVE_DRAFTER`` (drafter preset; ``"self"`` shares the
target's weights); all overridable per-deployment via LLMServer init.
"""

from __future__ import annotations

import math
import threading
import time
from collections import deque
from queue import Empty as _QueueEmpty
from queue import Queue as _Queue
from typing import Any, Dict, List, Optional

from ray_tpu._private import flight
from ray_tpu._private.metrics import Counter, Gauge, Histogram

# The scheduler thread's time, cut into leaf phases by ONE clock
# (``flight.PhaseClock``): a transition closes the open phase and opens the
# next. Each phase is a SPAN in the flight ring, a host event of an open
# ``jax.profiler`` session (so an idle gap of the device trace is labelled
# by the phase that overlaps it) and cumulative seconds in ``stats()``
# (``phase_<name>_s``). No phase encloses another and none adds a device
# wait: the two ``*.wait`` phases stand directly before a host read that
# would block anyway, the read of a program dispatched a turn earlier. The
# transitions of a loop turn do not grow with the number of slots.
PHASES = (
    "serve.admit",           # commands, migrations, admission, slot reset
    "serve.prefill",         # one chunk picked and built (dispatched, alone)
    "serve.prefill.wait",    # the device works on the chunk's program read
    "serve.decode.prepare",  # the rows' arrays, pages, tables; the dispatch
    "serve.decode.wait",     # the device works on the plain step read from
    "serve.decode.fetch",    # a program's ids (4 bytes a slot) to the host
    "serve.sample",          # whose token is whose; the rows to discard
    "serve.emit",            # hand-off to the consumers' event loop, retire
    "serve.park",            # nothing to do: waiting to be woken
    "serve.verify",          # speculative path: the verify call and fetch
    "serve.migrate",         # fleet path: splicing pulled prefix pages
)
(_P_ADMIT, _P_PREFILL, _P_PREFILL_WAIT, _P_PREPARE, _P_WAIT, _P_FETCH,
 _P_SAMPLE, _P_EMIT, _P_PARK, _P_VERIFY, _P_MIGRATE) = range(len(PHASES))
_PHASE_KEYS = tuple("phase_" + n[len("serve."):].replace(".", "_") + "_s"
                    for n in PHASES)

# a request's life, as instants that carry the request's id
_F_QUEUED = flight.intern("serve.req.queued")
_F_ADMIT = flight.intern("serve.admit")
_F_FIRST_TOKEN = flight.intern("serve.first_token")
_F_RETIRE = flight.intern("serve.retire")
# a loop turn that took longer than _STALL_NS while a slot was live; the
# instant's argument is ``microseconds << 8 | index into PHASES`` of the
# phase that held most of it
_F_STALL = flight.intern("serve.stall")
# the thread read a result with no program queued behind it: the device
# stands idle until the next dispatch (argument: the programs read)
_F_DRAIN = flight.intern("serve.drain")
# one read that emitted tokens: a SPAN from the previous emitting read's stamp
# to this one's, and an instant whose argument packs what the device was
# given in between (``unpack_turn``). Ring only, never a profiler host event:
# a span that encloses the phases would take every idle-gap label from them
_F_TURN = flight.intern("serve.turn")
_STALL_NS = 1_000_000_000  # four slow turns of 0.16-0.27 s (PERF.md)

_m_steps = Counter(
    "ray_tpu_serve_decode_steps_total",
    "Batched slot-arena decode iterations executed")
_m_prefill_chunks = Counter(
    "ray_tpu_serve_prefill_chunks_total",
    "Chunked prefill programs executed")
_m_tokens = Counter(
    "ray_tpu_serve_tokens_generated_total",
    "Tokens sampled and streamed out of the slot arena")
_m_admitted = Counter(
    "ray_tpu_serve_seqs_admitted_total",
    "Sequences admitted into a KV arena slot")
_m_retired = Counter(
    "ray_tpu_serve_seqs_retired_total",
    "Sequences retired from their slot (finished/EOS/cancelled/error)")
_m_active = Gauge(
    "ray_tpu_serve_slots_active",
    "KV arena slots currently holding a live sequence")
_m_attn_bytes = Counter(
    "ray_tpu_serve_attn_bytes_moved_total",
    "KV-cache bytes the paged attention lane streamed per program call "
    "(host-side mirror arithmetic, labelled by implementation: whole "
    "blocks of pages up to each sequence's cursor)")
_m_queue_depth = Gauge(
    "ray_tpu_serve_queue_depth",
    "Requests waiting for a free KV arena slot")
_m_queue_wait = Histogram(
    "ray_tpu_serve_queue_wait_seconds",
    "Time a request waited from submit to admission into a slot")

# sequence states
_QUEUED = "queued"
_PREFILL = "prefill"
_DECODE = "decode"
_DONE = "done"


class SchedulerClosedError(RuntimeError):
    pass


def unpack_turn(arg: int) -> Dict[str, Any]:
    """What a ``serve.turn`` instant's argument packs: the turn's kind
    ("prefill" if prompt tokens were dispatched since the previous emitting
    read's program, else "plain"), the tokens the read emitted, and those
    prompt tokens."""
    return {"kind": "prefill" if arg & 0xFF else "plain",
            "rows": (arg >> 8) & 0xFFFF, "prompt_tokens": arg >> 24}


def _program(fn, name: str, cfg, **keywords):
    """``fn(cfg, *args, **keywords)`` as a function called ``name``: what
    ``jax.jit`` compiles is the XLA module ``jit_<name>``, which is how a
    profiler trace tells the scheduler's programs apart (a
    ``functools.partial`` has no name: ``jit__unknown``)."""
    def program(*args):
        return fn(cfg, *args, **keywords)
    program.__name__ = program.__qualname__ = name
    return program


class _Seq:
    """One in-flight generation request and its consumer-side queue."""

    __slots__ = ("prompt", "remaining_prompt", "max_new", "temperature",
                 "seed", "slot", "state", "n_generated", "n_launched",
                 "next_token",
                 "queue", "loop", "cancelled", "rid", "t_submit", "t_admit",
                 "t_first_token", "t_emit", "prefill_mark", "rng",
                 "cached_len", "cursor",
                 "owned_pages", "radix_node",
                 "table_fill", "window_pages", "window_fill",
                 "fleet_hint", "migration_node",
                 "drafter_len", "drafter_pending")

    def __init__(self, prompt: List[int], max_new: int, temperature: float,
                 seed: int, loop, queue):
        self.rid = 0  # set at submit; carried by this request's instants
        self.prompt = prompt
        self.remaining_prompt = list(prompt)
        self.max_new = max_new
        self.temperature = temperature
        self.seed = seed & 0xFFFFFFFF  # a sampling key folds 32 bits in
        self.slot: Optional[int] = None
        self.state = _QUEUED
        self.n_generated = 0           # tokens read and emitted
        # tokens whose program has been dispatched, read or not: the host
        # knows a sequence's length a step before it knows its last token
        self.n_launched = 0
        self.next_token: Optional[int] = None  # newest token read
        self.queue = queue
        self.loop = loop
        self.cancelled = False
        self.t_submit = time.monotonic()
        self.t_admit: Optional[float] = None
        self.t_first_token: Optional[float] = None
        # the read that emitted the newest token: its stamp (the recorder's
        # clock, 0 with the recorder off) and ``prefill_tokens`` as the
        # program that sampled the token was dispatched
        self.t_emit = 0
        self.prefill_mark = 0
        self.rng = None  # the speculative path's numpy Generator (T > 0)
        # ---- paged-arena bookkeeping (the device holds pages only) ----
        self.cached_len = 0            # spliced prefix tokens (page-aligned)
        self.cursor = 0                # tokens resident: THE slot's cursor
        self.owned_pages: List[int] = []  # pages this slot must free
        self.radix_node = None         # ref-counted prefix-cache node
        self.table_fill = 0            # logical pages present in the table
        # the window layers' pool: the pages held, oldest first (the logical
        # pages [window_fill - len(window_pages), window_fill))
        self.window_pages: deque = deque()
        self.window_fill = 0
        # ---- fleet phase (ISSUE 18) ----
        self.fleet_hint = None         # {"handle", "tokens"} from the router
        self.migration_node = None     # pin on a just-migrated prefix span
        # ---- speculative decoding (per-slot drafter sync state) ----
        self.drafter_len = -1          # drafter's valid context length
        self.drafter_pending: List[int] = []  # tokens drafter must catch up


def _deliver(batch) -> None:
    """On a consumers' event loop: one program's items into their queues."""
    for seq, item in batch:
        seq.queue.put_nowait(item)


class _Launched:
    """One dispatched program whose result the host has not read yet."""

    __slots__ = ("serial", "prefill_mark", "step", "chunk", "ids", "rows",
                 "moe", "live_rows")

    def __init__(self, serial: int, prefill_mark: int, step: bool,
                 chunk: bool, ids, rows: List[_Seq], moe, live_rows: int):
        self.serial = serial        # its number among the dispatched programs
        # prompt tokens dispatched so far, this program's own among them
        self.prefill_mark = prefill_mark
        self.step = step            # it advanced decode rows
        self.chunk = chunk          # it carried a prefill chunk (or both)
        self.ids = ids              # the program's [slots] ids, on the device
        # the sequences it sampled a token for: the decode rows and, behind
        # them, the prompt whose last chunk it carried
        self.rows = rows
        self.moe = moe              # an expert model's counts, on the device
        self.live_rows = live_rows  # the live rows the host handed it


class _LiveRows:
    """The decode rows of one turn, as the host built them: the sequences
    that take a token (``live``) and the step's arrays over ``[slots]``."""

    __slots__ = ("active", "temperature", "seeds", "live")

    def __init__(self, slots: int):
        import numpy as np

        self.active = np.zeros(slots, np.int32)
        self.temperature = np.zeros(slots, np.float32)
        self.seeds = np.zeros(slots, np.uint32)
        self.live: List[_Seq] = []


class ContinuousScheduler:
    """Continuous-batching decode scheduler over a paged KV pool.

    ``params`` are the (device-resident) model parameters shared by every
    program; the scheduler owns the page pool and two jitted programs —
    a prefill chunk (``paged_prefill_into_slot``, one compiled shape:
    [1, prefill_chunk], with the [slots] decode rows along) and a decode
    step (``paged_decode_step``,
    [slots]), which runs the turns that hold no chunk — both with donated
    caches so the pool updates in place instead of being copied per
    iteration. It also owns every slot's page table and
    cursor and passes them with each call; the sampled ids stay on the
    device (``_ids``, never donated: the host reads each vector one program
    later). ``attn``: the paged-attention
    implementation, ``None`` for ``ops.paged_attention.resolve_impl``'s
    answer (the kernel on a TPU, the reference elsewhere).
    """

    def __init__(self, cfg, params, *, slots: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 arena_len: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 cache_dtype=None,
                 page_tokens: Optional[int] = None,
                 kv_pages: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 drafter=None,
                 spec_k: Optional[int] = None,
                 migration_budget: Optional[int] = None,
                 attn: Optional[str] = None):
        import numpy as np
        import jax

        from ray_tpu._private.config import global_config
        from ray_tpu.models.decode import (init_paged_caches,
                                           paged_decode_step,
                                           paged_prefill_into_slot,
                                           paged_verify_step, pool_of)
        from ray_tpu.models.transformer import (ATTENTION, LINEAR, RETENTION,
                                                SLIDING, SPARSE, STATE_KINDS,
                                                state_shapes)
        from ray_tpu.ops.paged_attention import resolve_impl
        from ray_tpu.serve._private.paging import PageArena, RadixCache

        conf = global_config()
        self.cfg = cfg
        self.params = params
        # `is None` (not `or`): an explicit 0 must hit the validation
        # below, not silently take the config default (the PR-8 depth=0
        # lesson)
        self.slots = int(conf.serve_slots if slots is None else slots)
        self.prefill_chunk = int(conf.serve_prefill_chunk
                                 if prefill_chunk is None else prefill_chunk)
        self.arena_len = int(cfg.max_seq_len if arena_len is None
                             else arena_len)
        self.eos_id = eos_id
        if self.slots < 1:
            raise ValueError(f"slots must be >= 1, got {self.slots}")
        if self.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {self.prefill_chunk}")
        if self.prefill_chunk > self.arena_len:
            raise ValueError(
                f"prefill_chunk ({self.prefill_chunk}) exceeds the arena "
                f"length ({self.arena_len})")
        self._jax = jax
        self.page_tokens = int(conf.serve_page_tokens
                               if page_tokens is None else page_tokens)
        if self.page_tokens < 1:
            # explicit 0 (arg or RAY_TPU_SERVE_PAGE_TOKENS=0) raises —
            # never silently the config default through a falsy `or`
            raise ValueError(
                f"page_tokens must be >= 1, got {self.page_tokens}")
        if self.arena_len % self.page_tokens != 0:
            raise ValueError(
                f"arena_len ({self.arena_len}) must be a multiple of "
                f"page_tokens ({self.page_tokens})")
        self._pages_per_slot = self.arena_len // self.page_tokens
        kvp = int(conf.serve_kv_pages if kv_pages is None else kv_pages)
        if kvp < 0:
            raise ValueError(f"kv_pages must be >= 0, got {kvp}")
        if kvp == 0:
            # auto: the worst case (every slot could fill its whole
            # logical range) + the reserved garbage page
            kvp = self.slots * self._pages_per_slot + 1
        self.num_pages = kvp
        self._arena = PageArena(self.num_pages, self.page_tokens)
        use_prefix = (conf.serve_prefix_cache if prefix_cache is None
                      else bool(prefix_cache))
        self._radix = RadixCache(self._arena) if use_prefix else None
        # host-side page tables: logical page j of slot s lives at
        # physical page read_tables[s, j]; 0 = the garbage page
        # (unallocated reads are causally masked, redirected writes
        # are absorbed)
        self._read_tables = np.zeros(
            (self.slots, self._pages_per_slot), np.int32)
        self._write_tables = np.zeros(
            (self.slots, self._pages_per_slot), np.int32)
        # the implementation resolves ONCE at build — an unknown value
        # fails the constructor, not some later decode step, and stats()
        # always names what really runs
        self.attn_lane = resolve_impl(cfg, attn)
        # a model none of whose layers holds a page: memory is a state a
        # slot and a request is bounded by arena_len alone. Of the pages
        # above (the knobs were checked as given) one a slot is left that is
        # never handed out: no pool on the device, no table uploaded, no
        # allocator work a turn
        self._paged = cfg.holds_pages
        if not self._paged:
            self.page_tokens, self._pages_per_slot = self.arena_len, 1
            self.num_pages = 1
            self._arena = PageArena(1, self.arena_len, pageless=True)
            self._read_tables = np.zeros((self.slots, 1), np.int32)
            self._write_tables = np.zeros((self.slots, 1), np.int32)
        if cfg.recurrent:
            # a spliced prefix would need the states as they stood at its
            # last token, a rejected draft their rewind: no snapshot is kept
            if prefix_cache:
                raise ValueError(
                    "prefix_cache=True cannot serve a model with layers "
                    "that keep a state a slot ('lightning-attn', "
                    "'power-retention'): their state at a prefix's end is "
                    "not kept")
            if drafter is not None:
                raise ValueError(
                    "speculative decoding cannot serve a model with layers "
                    "that keep a state a slot ('lightning-attn', "
                    "'power-retention'): a rejected draft would have to "
                    "rewind their states")
            self._radix = None  # the configured default cannot apply
        # window layers: a pool and an arena of their own, sized by what a
        # slot can hold of it at once — the window behind a chunk's first
        # row, the chunk, and a page for where the two begin inside one
        self._window = cfg.sliding_window if SLIDING in cfg.kinds else 0
        self._window_arena = None
        if self._window:
            if prefix_cache:
                raise ValueError(
                    "prefix_cache=True cannot serve a model with "
                    "'sliding_attention' layers: a spliced prefix would need "
                    "their last window of it kept too, and they release it")
            if drafter is not None:
                raise ValueError(
                    "speculative decoding cannot serve a model with "
                    "'sliding_attention' layers: a rejected draft would "
                    "need the pages released behind it back")
            self._radix = None  # the configured default cannot apply
            slot_pages = min(
                self._pages_per_slot,
                -(-(self._window + self.prefill_chunk) // self.page_tokens)
                + 1)
            self._window_arena = PageArena(
                self.slots * slot_pages + 1, self.page_tokens, pool="window")
            self._window_read_tables = np.zeros_like(self._read_tables)
            self._window_write_tables = np.zeros_like(self._write_tables)
            # the tables' names: the full layers' pool, the window layers'
            self._pools = (pool_of(ATTENTION), pool_of(SLIDING))
        # an expert layer's programs hand the rows each expert received
        # back with the ids
        self._moe = cfg.mlp == "moe"
        program_kw = {"attn": self.attn_lane}
        if self._moe:
            program_kw["moe_info"] = True
        self._no_rows = _LiveRows(self.slots)  # for a chunk that takes none
        # donated caches: the pool mutates in place across iterations;
        # the tables are tiny per-call host->device uploads
        self._prefill = jax.jit(
            _program(paged_prefill_into_slot, "paged_prefill_chunk", cfg,
                     **program_kw), donate_argnums=(6,))
        self._step = jax.jit(
            _program(paged_decode_step, "paged_decode_step", cfg,
                     **program_kw), donate_argnums=(6,))
        self._caches = init_paged_caches(
            cfg, self.num_pages, self.page_tokens,
            self._pages_per_slot, cache_dtype, slots=self.slots,
            window_pages=(self._window_arena.num_pages if self._window
                          else None))
        self._kv_itemsize = int(jax.numpy.dtype(
            cache_dtype or cfg.dtype).itemsize)
        # the pool by kind: layers that hold pages, layers that hold a state
        # a slot, and among the first those that attend chosen blocks
        kinds = cfg.kinds
        self._n_linear = kinds.count(LINEAR)
        self._n_retention = kinds.count(RETENTION)
        self._n_sparse = kinds.count(SPARSE)
        self._n_paged = sum(kind not in STATE_KINDS for kind in kinds)
        self._n_window = kinds.count(SLIDING)
        self._state_bytes = 4 * sum(
            math.prod(shape) for kind in kinds if kind in STATE_KINDS
            for shape in state_shapes(cfg, kind, self.slots).values())
        if self._n_sparse:
            # tokens of one block of the step's kernel over a row's table
            # of chosen pages (``sparse_attention._step_attention``)
            from ray_tpu.ops.paged_attention import tile_sizes

            sizes = cfg.sparse
            self._sparse_step_block = self.page_tokens * tile_sizes(
                1, cfg.num_heads // cfg.kv_heads, self.page_tokens,
                sizes.max_chosen_blocks() * sizes.pages_per_block,
                cfg.kv_heads * cfg.head_dim * self._kv_itemsize)[0]
        # the newest token of every slot, as the programs left it: a chunk
        # that ends a prompt sets its row, a step replaces its active rows,
        # and the next step takes the vector as its tokens
        self._ids = jax.numpy.zeros((self.slots,), jax.numpy.int32)
        # programs dispatched and not read yet, oldest first; the loop
        # reads a turn behind what it dispatches
        self._inflight: deque = deque()
        self._serial = 0           # programs dispatched so far
        self._steps_unread = 0     # decode steps among _inflight
        self._outbox: Optional[Dict[Any, list]] = None  # see _emit
        # the read whose tokens are being emitted (_begin_read): its stamp
        # and its program's prefill_mark; and the stamp and mark of the
        # read that emitted before it
        self._read_stamp = 0
        self._read_mark = 0
        self._turn_stamp = 0
        self._turn_mark = 0
        # what stats() shows of the counts below, as of the last read: one
        # tuple, replaced whole, so a reader on another thread never sees a
        # token counted and its gap not yet (tokens, first tokens, plain,
        # plain ns, prefill, prefill ns)
        self._emitted = (0, 0, 0, 0, 0, 0)
        self._n_runahead = 0
        self._n_drains = 0
        self._n_discarded = 0
        # ---- speculative decoding (ISSUE 18): the drafter proposes, one
        # extra fixed-shape verify program scores — the two-compiles
        # contract becomes exactly three with speculation on
        self.spec_k = int(conf.serve_spec_k if spec_k is None else spec_k)
        if self.spec_k < 1:
            # explicit 0 (arg or RAY_TPU_SERVE_SPEC_K=0) raises — never
            # silently the config default through a falsy `or`
            raise ValueError(f"spec_k must be >= 1, got {self.spec_k}")
        self.migration_budget = int(conf.serve_migration_budget
                                    if migration_budget is None
                                    else migration_budget)
        if self.migration_budget < 1:
            raise ValueError(f"migration_budget must be >= 1, got "
                             f"{self.migration_budget}")
        self._drafter = drafter
        self._verify = None
        if drafter is not None:
            if drafter.slots != self.slots:
                raise ValueError(
                    f"drafter has {drafter.slots} slots, scheduler has "
                    f"{self.slots} — they must share the slot numbering")
            self._verify = jax.jit(
                _program(paged_verify_step, "paged_verify_step", cfg,
                         **program_kw), donate_argnums=(6,))
        # ---- cross-replica page migration (ISSUE 18): a dedicated
        # worker thread does the blocking peer pull; the scheduler thread
        # only splices finished results between iterations. _commands
        # carries EXPORT requests from peer replicas (RPC threads) onto
        # the scheduler thread, which owns the radix tree and the caches.
        self._migrating: List[_Seq] = []
        self._mig_requests: _Queue = _Queue()
        self._mig_results: _Queue = _Queue()
        self._mig_thread: Optional[threading.Thread] = None
        self._commands: deque = deque()
        self._n_migrations = 0
        self._n_migrated_pages = 0
        self._n_migration_failures = 0
        self._n_spec_rounds = 0
        self._n_drafted = 0
        self._n_accepted = 0
        self._n_spec_emitted = 0
        self._slot_seqs: List[Optional[_Seq]] = [None] * self.slots
        self._prefill_rr = 0  # round-robin cursor over prefilling slots
        self._pending: deque = deque()
        self._lock = threading.Lock()
        self._wake = threading.Event()
        self._closed = False
        self._error: Optional[BaseException] = None
        # stats (host-side; mirrored into the process metric registry)
        self._n_steps = 0
        self._n_prefill_chunks = 0
        # prompt tokens dispatched (a chunk's real tokens), and every token
        # after a sequence's first by what the device was given since the
        # program that sampled the one before it: decode work only
        # (plain), or prompt tokens too (prefill); nanoseconds between the
        # two reads that emitted them
        self._n_prefill_tokens = 0
        self._n_gap_plain = 0
        self._gap_plain_ns = 0
        self._n_gap_prefill = 0
        self._gap_prefill_ns = 0
        self._n_turns = 0  # loop turns that dispatched or read a program
        # chunk programs that carried at least one live decode row, and the
        # rows they carried
        self._n_fused_turns = 0
        self._n_fused_step_rows = 0
        self._n_admitted = 0
        self._n_retired = 0
        self._n_tokens = 0
        self._n_attn_bytes = 0
        self._n_attn_attended = 0
        self._n_attn_fetched = 0
        # window layers beside full ones: what the mask admits by kind and
        # by work (keys a decode row reads, (query, key) pairs of a chunk's
        # real rows; summed over rows and layers), the pages released from
        # behind a window, and, a turn, the tokens their pool holds beside
        # those it would hold without release
        self._n_window_step_keys = 0
        self._n_full_step_keys = 0
        self._n_window_chunk_pairs = 0
        self._n_full_chunk_pairs = 0
        self._n_window_released = 0
        self._n_window_tokens_held = 0
        self._n_window_tokens_unreleased = 0
        # layers of other kinds (a layer-call: one layer in one program run)
        self._n_linear_chunk_calls = 0
        self._n_linear_step_rows = 0
        self._n_retention_chunk_calls = 0
        self._n_retention_chunk_tokens = 0
        self._n_retention_step_rows = 0
        self._n_sparse_rows = 0
        self._n_sparse_rows_dense = 0
        self._n_sparse_attended = 0
        self._n_sparse_context = 0
        self._n_sparse_step_attended = 0
        self._n_sparse_step_context = 0
        # expert layers (mlp='moe'): what the device's counts add up to,
        # beside the live rows the host handed it
        self._n_moe_live_rows = 0
        self._n_moe_layer_calls = 0
        self._n_moe_rows_routed = 0
        self._n_moe_experts_hit = 0
        self._n_moe_max_expert_rows = 0
        self._n_prefix_hit_tokens = 0
        self._admitted_mid_flight = 0
        self._max_active_slots = 0
        self._peak_queue_depth = 0
        # a request's life and the loop's own time (README "Observability")
        self._n_submitted = 0
        self._queue_wait_s = 0.0
        self._first_token_wait_s = 0.0
        self._n_first_tokens = 0
        self._stall_s = 0.0
        self._n_stalls = 0
        self._stall_phase = ""
        self._clock = flight.PhaseClock(PHASES)
        self._thread = threading.Thread(
            target=self._run, name="serve-continuous-scheduler", daemon=True)
        self._thread.start()

    # ------------------------------------------------------------- submit

    def max_prompt_len(self, max_new: int) -> int:
        """Longest admissible prompt for a given generation budget: the
        padded prefill chunks AND prompt+new tokens must fit the arena.
        Page-aware: with a paged pool smaller than one slot's worst case,
        the whole-pool page budget also caps a single sequence — an
        over-budget request is rejected loudly at submit, before any
        pages are allocated."""
        c = self.prefill_chunk
        effective = self.arena_len
        if self._paged:
            effective = min(effective,
                            self._arena.usable_pages * self.page_tokens)
        # with speculation on, a verify round near the end of generation
        # writes up to spec_k positions past the final cursor — reserve
        # them so the window's writes can never clip onto the slot's
        # last real page
        reserve = self.spec_k if self._drafter is not None else 0
        by_pad = (effective // c) * c
        return min(by_pad, effective - max_new - reserve)

    def submit(self, prompt_ids: List[int], *, max_new_tokens: int,
               temperature: float = 0.0, seed: int = 0,
               loop=None, queue=None, fleet_hint=None,
               request_id: Optional[int] = None) -> _Seq:
        """Enqueue a generation. Tokens/end/error events arrive on ``queue``
        via ``loop.call_soon_threadsafe`` as ``("tok", id, stamp)``,
        ``("end", reason, stamp)`` or ``("err", message, stamp)`` tuples;
        ``stamp`` is the recorder's clock (``perf_counter_ns``) at the read
        that emitted the item (one stamp for all the items of one read), 0
        with the recorder off. Thread/loop-safe.

        ``request_id`` is what the request's flight instants
        (``serve.req.queued/admit/first_token/retire``) carry; the replica
        passes its own request counter, and without one the scheduler
        counts submissions.

        ``fleet_hint`` (router-attached): ``{"handle": holder_replica,
        "tokens": matched_depth}`` — before admission the scheduler pulls
        the matched prefix pages from the holder and splices them locally;
        any pull failure degrades to a cold prefill."""
        if not prompt_ids:
            raise ValueError("prompt must be non-empty")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if len(prompt_ids) > self.max_prompt_len(max_new_tokens):
            raise ValueError(
                f"prompt of {len(prompt_ids)} tokens + {max_new_tokens} new "
                f"tokens does not fit a {self.arena_len}-token arena slot "
                f"(prefill pads prompts to {self.prefill_chunk}-token "
                f"chunks)")
        seq = _Seq(list(prompt_ids), max_new_tokens, temperature, seed,
                   loop, queue)
        if fleet_hint and self._radix is not None:
            seq.fleet_hint = dict(fleet_hint)
        with self._lock:
            if self._closed:
                raise SchedulerClosedError(
                    "scheduler is shut down" if self._error is None
                    else f"scheduler failed: {self._error!r}")
            self._n_submitted += 1
            seq.rid = (self._n_submitted if request_id is None
                       else int(request_id))
            self._pending.append(seq)
            self._peak_queue_depth = max(self._peak_queue_depth,
                                         len(self._pending))
            _m_queue_depth.set(float(len(self._pending)))
        flight.instant(_F_QUEUED, seq.rid)
        self._wake.set()
        return seq

    def cancel(self, seq: _Seq) -> None:
        """Mark a sequence cancelled; its slot retires on the next
        iteration (pending sequences are dropped at admission)."""
        seq.cancelled = True
        self._wake.set()

    # -------------------------------------------------------------- loop

    def _emit(self, seq: _Seq, kind: str, value) -> None:
        """Hand one item to the consumer's event loop, stamped with the
        recorder's clock so the receiving side can count how long it lay
        between the two threads (``stream_lag_s`` in the replica). The
        items of one read carry the read's ONE stamp and leave together."""
        if seq.loop is None or seq.queue is None:
            return
        if self._outbox is not None:
            # a program's tokens leave together (_end_read): one wake-up of
            # the consumers' loop a read, not one a token
            self._outbox.setdefault(seq.loop, []).append(
                (seq, (kind, value, self._read_stamp)))
            return
        try:
            seq.loop.call_soon_threadsafe(seq.queue.put_nowait,
                                          (kind, value, flight.now()))
        except RuntimeError:
            # consumer's loop is gone — nobody is listening; retire quietly
            seq.cancelled = True

    def _begin_read(self, prefill_mark: int) -> None:
        """Open the emission of one read (a dispatched program's ids, or a
        speculative round's accepted tokens): the one place a sampled token
        becomes an emitted one. Everything emitted until ``_end_read``
        carries ONE stamp, the end of all its gaps, and ``prefill_mark``,
        the prompt tokens dispatched up to and including the program that
        sampled it: what decides each gap's kind."""
        self._outbox = {}
        self._read_stamp = flight.now()
        self._read_mark = prefill_mark

    def _end_read(self) -> None:
        """Count the read's tokens once and put the turn down in the flight
        ring; then send what ``_emit`` gathered since ``_begin_read``, in
        order, with one call into each consumers' loop. The hand-over stays
        the phase's LAST act: it wakes a thread that wants the interpreter
        lock, and what follows it here would wait for that thread."""
        emitted = self._n_tokens - self._emitted[0]
        if emitted:
            _m_tokens.inc(emitted)
            stamp, mark = self._read_stamp, self._read_mark
            prompt_tokens = mark - self._turn_mark
            flight.span_between(_F_TURN, self._turn_stamp, stamp)
            flight.instant(_F_TURN, prompt_tokens << 24
                           | min(emitted, 0xFFFF) << 8 | (prompt_tokens > 0))
            self._turn_stamp, self._turn_mark = stamp, mark
            self._emitted = (self._n_tokens, self._n_first_tokens,
                             self._n_gap_plain, self._gap_plain_ns,
                             self._n_gap_prefill, self._gap_prefill_ns)
        outbox, self._outbox = self._outbox, None
        for loop, batch in outbox.items():
            try:
                loop.call_soon_threadsafe(_deliver, batch)
            except RuntimeError:  # that loop is gone: nobody listens
                for seq, _ in batch:
                    seq.cancelled = True

    def _release_slot_resources(self, seq: _Seq) -> None:
        """Teardown for one slot: drop the prefix-cache ref, free owned
        pages, and zero the page-table rows (so an inactive slot's decode
        write touches only the garbage page).

        The sequence may still ride in ONE step in flight (EOS, a
        cancellation and an exhausted pool reach the loop a step late).
        That step took COPIES of the tables as they stood when it was
        dispatched, so the stale row writes its k/v at its own ``cursor``,
        on its own last page or the garbage page, never on a shared prefix
        page (those are write-redirected). The device runs programs in
        order: a page freed here and handed to another sequence is written
        by the stale row BEFORE any program of the new owner, and the new
        owner writes every position before it attends it (the
        update-before-attend invariant of ``_cursors``). The stale row's id
        is discarded when it is read (``_collect``)."""
        if seq.slot is None:
            return
        slot = seq.slot
        if seq.radix_node is not None:
            self._radix.release(seq.radix_node)
            seq.radix_node = None
        if seq.owned_pages:
            self._arena.free(seq.owned_pages)
            seq.owned_pages = []
        seq.table_fill = 0
        self._read_tables[slot, :] = 0
        self._write_tables[slot, :] = 0
        if self._window:
            self._window_arena.free(list(seq.window_pages))
            seq.window_pages.clear()
            seq.window_fill = 0
            self._window_read_tables[slot, :] = 0
            self._window_write_tables[slot, :] = 0

    def _release_migration_ref(self, seq: _Seq) -> None:
        """A migrated-prefix pin must drop no matter how the sequence
        ends — including cancellation BEFORE it ever took a slot (the
        node ref is held while the sequence waits in the pending queue)."""
        if seq.migration_node is not None and self._radix is not None:
            self._radix.release(seq.migration_node)
            seq.migration_node = None

    def _retire(self, seq: _Seq, reason: str) -> None:
        self._release_migration_ref(seq)
        self._release_slot_resources(seq)
        if seq.slot is not None:
            self._slot_seqs[seq.slot] = None
            seq.slot = None
        flight.instant(_F_RETIRE, seq.rid)
        seq.state = _DONE
        self._n_retired += 1
        _m_retired.inc()
        self._emit(seq, "end", reason)

    def _fail(self, seq: _Seq, msg: str) -> None:
        self._release_migration_ref(seq)
        self._release_slot_resources(seq)
        if seq.slot is not None:
            self._slot_seqs[seq.slot] = None
            seq.slot = None
        flight.instant(_F_RETIRE, seq.rid)
        seq.state = _DONE
        self._n_retired += 1
        _m_retired.inc()
        self._emit(seq, "err", msg)

    def _ensure_pages(self, seq: _Seq, upto: int) -> bool:
        """Grow the slot's page table so its logical view covers
        [0, upto) tokens, evicting LRU unreferenced prefix-cache nodes
        under pressure. On exhaustion the SEQUENCE fails cleanly (the
        scheduler and its other slots keep running). Returns True if the
        pages are present."""
        from ray_tpu.serve._private.paging import OutOfPagesError

        if not self._paged:
            return True  # arena_len bounded the request at submit
        need = -(-upto // self.page_tokens)
        if self._window and not self._ensure_window_pages(seq, need):
            return False
        missing = need - seq.table_fill
        if missing <= 0:
            return True
        try:
            pages = self._arena.alloc(missing)
        except OutOfPagesError:
            if self._radix is not None:
                self._radix.evict(missing - self._arena.free_pages)
            try:
                pages = self._arena.alloc(missing)
            except OutOfPagesError:
                self._fail(seq, f"kv arena out of pages (need {missing} "
                                f"more, {self._arena.free_pages} free of "
                                f"{self._arena.usable_pages}; nothing "
                                f"evictable)")
                return False
        slot = seq.slot
        for j, p in enumerate(pages, start=seq.table_fill):
            self._read_tables[slot, j] = p
            self._write_tables[slot, j] = p
        seq.owned_pages.extend(pages)
        seq.table_fill = need
        return True

    def _ensure_window_pages(self, seq: _Seq, need: int) -> bool:
        """The window layers' half of ``_ensure_pages``: give back the pages
        wholly behind the window of the next row the slot's programs run
        (``seq.cursor``: every program dispatched so far took COPIES of the
        tables, and the device runs them before whatever is dispatched
        next), then grow the slot's window table to ``need`` logical pages.
        A released page's entries point at the garbage page; the kernel's
        walk starts behind it. Returns True if the pages are present."""
        from ray_tpu.serve._private.paging import (F_WINDOW_RELEASE,
                                                   OutOfPagesError,
                                                   m_window_pages_released)

        slot, held = seq.slot, seq.window_pages
        first_kept = max(seq.cursor - self._window + 1, 0) // self.page_tokens
        front = seq.window_fill - len(held)
        if first_kept > front:
            n = min(first_kept - front, len(held))
            self._window_arena.free([held.popleft() for _ in range(n)])
            self._window_read_tables[slot, front:front + n] = 0
            self._window_write_tables[slot, front:front + n] = 0
            self._n_window_released += n
            m_window_pages_released.inc(n)
            flight.instant(F_WINDOW_RELEASE, n)
        missing = need - seq.window_fill
        if missing <= 0:
            return True
        try:
            pages = self._window_arena.alloc(missing)
        except OutOfPagesError:
            arena = self._window_arena
            self._fail(seq, f"window kv arena out of pages (need {missing} "
                            f"more, {arena.free_pages} free of "
                            f"{arena.usable_pages})")
            return False
        fill = seq.window_fill
        self._window_read_tables[slot, fill:need] = pages
        self._window_write_tables[slot, fill:need] = pages
        held.extend(pages)
        seq.window_fill = need
        return True

    def _emit_token(self, seq: _Seq, tok: int) -> bool:
        """Record + stream one sampled token of the open read
        (``_begin_read``); returns True if the sequence is finished (budget
        exhausted or EOS). A token that is not its sequence's first has a
        GAP, this read's stamp less the stamp of the read that emitted the
        one before it, counted beside prefill if any prompt token was
        dispatched after the program that sampled that one, up to and
        including the program that sampled this one (by the scheduler's own
        count at dispatch, never by which program ran), else plain."""
        seq.n_generated += 1
        self._n_tokens += 1
        stamp = self._read_stamp
        if seq.t_first_token is None:
            seq.t_first_token = time.monotonic()
            self._first_token_wait_s += seq.t_first_token - seq.t_admit
            self._n_first_tokens += 1
            flight.instant(_F_FIRST_TOKEN, seq.rid)
        else:
            # a stamp is 0 while the recorder is off: counted, not timed
            gap = stamp - seq.t_emit if stamp and seq.t_emit else 0
            if self._read_mark > seq.prefill_mark:
                self._n_gap_prefill += 1
                self._gap_prefill_ns += gap
            else:
                self._n_gap_plain += 1
                self._gap_plain_ns += gap
        seq.t_emit = stamp
        seq.prefill_mark = self._read_mark
        self._emit(seq, "tok", tok)
        if self.eos_id is not None and tok == self.eos_id:
            return True
        return seq.n_generated >= seq.max_new

    def _splice_prefix(self, seq: _Seq) -> None:
        """Prefix-cache lookup at admission: splice the longest cached
        page-aligned prefix of the prompt into the slot's read table
        (write entries stay on the garbage page — shared pages are
        immutable) and jump the cursor past it. The last prompt token is
        never matched: it must re-prefill to produce the first sampled
        token's logits. The splice is clamped so the remaining tail's
        padded chunks still fit the logical view (chunks restart at the
        cursor, which is page- but not chunk-aligned)."""
        pages, matched, node = self._radix.match(seq.prompt[:-1])
        if matched == 0:
            self._radix.note_miss()
            return
        T, C = self.page_tokens, self.prefill_chunk
        keep = matched
        while keep > 0:
            rem = len(seq.prompt) - keep
            if keep + (-(-rem // C)) * C <= self.arena_len:
                break
            keep -= T
        if keep <= 0:
            # the whole match was clamped away — nothing avoided, so this
            # is a MISS for the hit-rate metrics
            self._radix.release(node)
            self._radix.note_miss()
            return
        self._radix.note_hit(keep)
        n = keep // T
        self._read_tables[seq.slot, :n] = pages[:n]
        seq.cached_len = keep
        seq.table_fill = n
        seq.radix_node = node
        self._n_prefix_hit_tokens += keep

    def _admit(self) -> None:
        """Seat pending requests in free slots. This is host bookkeeping
        alone (tables, the prefix splice, the cursor): the programs take the
        cursor as an argument, so no device program runs and no live slot
        waits for one."""
        while True:
            with self._lock:
                if not self._pending:
                    break
                free = next((i for i, s in enumerate(self._slot_seqs)
                             if s is None), None)
                if free is None:
                    break
                seq = self._pending.popleft()
                _m_queue_depth.set(float(len(self._pending)))
            if seq.cancelled:
                self._retire(seq, "cancelled")
                continue
            in_flight = any(s is not None for s in self._slot_seqs)
            seq.slot = free
            seq.state = _PREFILL
            self._slot_seqs[free] = seq
            seq.cached_len = 0
            seq.owned_pages = []
            seq.radix_node = None
            seq.table_fill = 0
            self._read_tables[free, :] = 0
            self._write_tables[free, :] = 0
            if self._window:
                seq.window_pages = deque()
                seq.window_fill = 0
                self._window_read_tables[free, :] = 0
                self._window_write_tables[free, :] = 0
            if self._radix is not None:
                self._splice_prefix(seq)
                # a migrated prefix was pinned only so eviction could
                # not race admission; the splice holds its own ref now
                self._release_migration_ref(seq)
            seq.cursor = seq.cached_len
            seq.remaining_prompt = seq.prompt[seq.cached_len:]
            self._n_admitted += 1
            seq.t_admit = time.monotonic()
            waited = seq.t_admit - seq.t_submit
            self._queue_wait_s += waited
            _m_queue_wait.observe(waited)
            flight.instant(_F_ADMIT, seq.rid)
            _m_admitted.inc()
            if in_flight:
                # the signal request-level flush-and-drain cannot produce:
                # an admission while other sequences are mid-generation
                self._admitted_mid_flight += 1

    def _record_attn(self, qk: int, cursors: List[int],
                     idle_rows: int = 0, real: Optional[int] = None) -> None:
        """Account what the paged attention streamed for one
        attention-bearing program call (its device time is read from a
        profiler trace, by the program's name and the kernel's).
        ``cursors``: the attention cursor of every slot row that attends a
        K = ``qk`` window; ``idle_rows``: the call's other rows, which the
        program marks as attending nothing; ``real``: the window's real
        tokens (a chunk's; default all). Pure host-side mirror arithmetic
        (``ops.paged_attention.streamed_tokens``) — no device readback on
        the hot loop. ``attn_tokens_attended`` over ``attn_tokens_fetched``
        is the block fill share (per layer: every layer that holds pages
        repeats the same fetches; a model all of whose such layers attend
        chosen blocks is counted by ``_record_sparse``). The layers that
        keep a state are counted by kind, a layer-call one layer in one
        program run: live rows x layers of the one-row update, layer-calls
        of the chunked scan (and, for 'power-retention', the real tokens
        they carried). For a model that holds no page the ``attn_*`` counts
        stay 0: they count pages."""
        from ray_tpu.ops.paged_attention import streamed_tokens

        cfg = self.cfg
        rows = len(cursors)
        if self._n_linear:
            if qk == 1:
                self._n_linear_step_rows += self._n_linear * rows
            else:
                self._n_linear_chunk_calls += self._n_linear * rows
        if self._n_retention:
            if qk == 1:
                self._n_retention_step_rows += self._n_retention * rows
            else:
                calls = self._n_retention * rows
                self._n_retention_chunk_calls += calls
                self._n_retention_chunk_tokens += calls * (
                    qk if real is None else real)
        if not self._n_paged:
            return
        row = cfg.kv_heads * cfg.head_dim * self._kv_itemsize
        streamed = lambda window: streamed_tokens(
            self.attn_lane, qk, cursors, idle_rows,
            cfg.num_heads // cfg.kv_heads, self.page_tokens,
            self._pages_per_slot, row, window)
        if self._n_sparse:
            attended, fetched = self._record_sparse(
                qk, cursors, qk if real is None else real)
        else:
            attended, fetched = streamed(None)
        self._n_attn_attended += attended
        self._n_attn_fetched += fetched
        # k + v pools, every layer that holds pages: the rows read through
        # the table plus the qk freshly-written rows per slot
        written = (rows + idle_rows) * qk
        moved = 2 * (self._n_paged - self._n_window) * row * (
            fetched + written)
        if self._n_window:
            self._record_window(qk, cursors, real)
            moved += 2 * self._n_window * row * (
                streamed(self._window)[1] + written)
        self._n_attn_bytes += moved
        _m_attn_bytes.inc(moved, labels={"lane": self.attn_lane})

    def _record_window(self, qk: int, cursors: List[int],
                       real: Optional[int]) -> None:
        """A model with window layers, one attention-bearing call: what the
        mask admits, by kind of layer and by work, summed over rows and
        layers — the keys a decode row reads (``*_step_keys``), the (query,
        key) pairs of a chunk's ``real`` rows (``*_chunk_pairs``). Host
        arithmetic on the cursors, no readback (``attn_tokens_*`` stay the
        full layers' streamed tokens)."""
        import numpy as np

        w, n_full = self._window, self._n_paged - self._n_window
        # the position of every real query row: a step's one a row
        t = (np.asarray(cursors, np.int64)[:, None]
             + np.arange(1 if qk == 1 else qk if real is None else real))
        full, seen = int((t + 1).sum()), int(np.minimum(t + 1, w).sum())
        if qk == 1:
            self._n_full_step_keys += n_full * full
            self._n_window_step_keys += self._n_window * seen
        else:
            self._n_full_chunk_pairs += n_full * full
            self._n_window_chunk_pairs += self._n_window * seen

    def _record_sparse(self, qk: int, cursors: List[int], real: int):
        """The block-selected layers' share of ``_record_attn``: what each
        query attends is a function of its position alone
        (``SparseSizes.attended_tokens``), so the host mirrors it. Returns
        (attended, fetched) token positions a layer, as the paged kernel's
        are counted (what a row or a query tile may attend over what is
        streamed for it): a step streams each row's chosen blocks once a
        K/V group, in whole kernel blocks of the compacted table; a chunk
        (one row) streams its slot's context up to each tile of 32 queries,
        which attend their mean choice of it."""
        import numpy as np

        cfg, sizes, layers = self.cfg, self.cfg.sparse, self._n_sparse
        # positions [rows, real]: a step's rows, or a chunk's real queries
        t = np.asarray(cursors)[:, None] + np.arange(real)
        att = sizes.attended_tokens(t)
        self._n_sparse_rows += layers * t.size
        self._n_sparse_rows_dense += layers * int(
            (t + 1 <= sizes.dense_len).sum())
        self._n_sparse_attended += layers * int(att.sum())
        self._n_sparse_context += layers * int((t + 1).sum())
        if qk == 1:
            self._n_sparse_step_attended += layers * int(att.sum())
            self._n_sparse_step_context += layers * int((t + 1).sum())
            block = self._sparse_step_block
            return (cfg.kv_heads * int(att.sum()),
                    cfg.kv_heads * int((-(-att // block)).sum()) * block)
        starts = np.arange(0, real, 32)
        ends = np.minimum(starts + 32, real)
        return (sum(int(att[0, lo:hi].mean()) for lo, hi in zip(starts, ends)),
                int((-(-(t[0, 0] + ends) // 512)).sum()) * 512)

    def _cursors(self):
        """``[slots]`` int32 for a decode or verify call: every seated
        sequence's ``cursor``, 0 for a free slot. The host's count is the
        only one; the device keeps none to reset or read back.

        The rows the plain step and the verify call mark inactive rely on
        this: such a row attends nothing (the program masks it by
        ``active``) but still writes at its cursor. A PREFILLING slot
        passes its true cursor, so the write lands on the position its next
        chunk writes before anything attends it (or, page not yet
        allocated, on the garbage page); a FREE slot's table rows are zero,
        so whatever it passes lands on the garbage page. The chunk's
        program, where the prefilling slot's cursor IS a position the same
        scatter writes, relies on nothing: it sends every row that is not
        active to the garbage page itself."""
        import numpy as np

        return np.fromiter((0 if s is None else s.cursor
                            for s in self._slot_seqs), np.int32, self.slots)

    def _tables(self, slot: Optional[int] = None):
        """(read, write) page tables for a program: all slots' or one
        ``slot``'s rows, as COPIES — dispatch is async and an upload may
        alias (CPU) or still be reading (TPU) the host buffer, while the
        host frees and hands out pages before anything waits for the
        program. (None, None) for a model that holds no page: nothing is
        uploaded. For a model with window layers each of the two is a dict,
        the full layers' table and the window layers' by the pool's name."""
        if not self._paged:
            return None, None
        rows = slice(None) if slot is None else slot
        if self._window:  # a pair a pool (``decode.pool_tables``)
            return tuple(
                {self._pools[0]: full[rows].copy(),
                 self._pools[1]: window[rows].copy()}
                for full, window in (
                    (self._read_tables, self._window_read_tables),
                    (self._write_tables, self._window_write_tables)))
        return (self._read_tables[rows].copy(),
                self._write_tables[rows].copy())

    def _launch(self, out, *, step: bool, chunk: bool, rows: List[_Seq],
                live_rows: int) -> None:
        """Take over what a paged program returned: the ids stay on the
        device for the next program, the pool is the next program's, and
        what the host has to read of it later (ids, an expert model's
        counts) is queued for ``_collect``: one record a program."""
        self._ids, self._caches = out[0], out[1]
        self._serial += 1
        moe = out[2]["counts"] if self._moe else None
        if rows or moe is not None:
            self._inflight.append(_Launched(
                self._serial, self._n_prefill_tokens, step, chunk, out[0],
                rows, moe, live_rows))

    def _moe_count(self, counts, live_rows: int, step: bool = True) -> None:
        """Add up one finished program's expert counts (call after a wait
        on that program: the copy below then waits for nothing). A chunk's
        program that took the step along tells the two groups' rows apart
        ([layers, 2, experts]): each is a layer-call of its own, as when
        they were two programs, the step's only where a row was live
        (``step``)."""
        import numpy as np

        c = np.asarray(counts)  # [layers, experts]
        if c.ndim == 3:
            c = c[:, :1 + step].reshape(-1, c.shape[-1])
        self._n_moe_live_rows += live_rows
        self._n_moe_layer_calls += c.shape[0]
        self._n_moe_rows_routed += int(c.sum())
        self._n_moe_experts_hit += int((c > 0).sum())
        self._n_moe_max_expert_rows += int(c.max(axis=1).sum())

    def _collect(self, n: int) -> bool:
        """Read the ``n`` oldest programs in flight: wait for each (the
        device runs them in order), fetch its ids, then emit every row's
        token and retire what ended. A row whose sequence ended while its
        program was in flight is discarded: never emitted, never counted in
        ``tokens_generated``. Returns True if anything was read."""
        import numpy as np

        if n <= 0:
            return False
        switch = self._clock.switch
        if self._inflight[n - 1].serial == self._serial:
            # nothing is queued behind what is read: the device idles
            # while the thread works
            self._n_drains += 1
            flight.instant(_F_DRAIN, n)
        for _ in range(n):
            rec = self._inflight.popleft()
            switch(_P_PREFILL_WAIT if rec.chunk else _P_WAIT)
            self._jax.block_until_ready(rec.ids)
            switch(_P_FETCH)
            ids = np.asarray(rec.ids)
            if rec.moe is not None:
                self._moe_count(rec.moe, rec.live_rows, rec.step)
            if rec.step:
                self._steps_unread -= 1
            if not rec.rows:
                continue
            switch(_P_SAMPLE)
            arrived = [(seq, int(ids[seq.slot])) for seq in rec.rows
                       if seq.state != _DONE]
            self._n_discarded += len(rec.rows) - len(arrived)
            switch(_P_EMIT)
            self._begin_read(rec.prefill_mark)
            try:
                for seq, tok in arrived:
                    if seq.cancelled:  # nobody listens: no token past it
                        self._n_discarded += 1
                        self._retire(seq, "cancelled")
                        continue
                    seq.next_token = tok
                    if self._emit_token(seq, tok):
                        self._retire(seq, "eos" if self.eos_id is not None
                                     and tok == self.eos_id else "length")
            finally:
                self._end_read()
        return True

    def _next_chunk(self):
        """Pick ONE prefilling sequence's next chunk, round-robin over
        slots — concurrent prompts interleave their chunks, so one long
        prompt cannot monopolize prefill (and decode never waits more than
        one chunk) — and see to its pages. Nothing is dispatched here.
        Returns (the sequence, the chunk's tokens [1, prefill_chunk], how
        many of them are real), or None where no prompt is pending."""
        import numpy as np

        start = self._prefill_rr
        for off in range(self.slots):
            i = (start + off) % self.slots
            seq = self._slot_seqs[i]
            if seq is None or seq.state != _PREFILL:
                continue
            self._clock.switch(_P_PREFILL)
            self._prefill_rr = (i + 1) % self.slots
            if seq.cancelled:
                self._retire(seq, "cancelled")
                continue
            # pages are needed only up to the REAL tokens this chunk
            # writes — pad positions beyond them land on unallocated
            # table entries, which the garbage-page write redirect
            # absorbs by design (don't fail a fitting sequence for
            # pad-only pages when the pool is tight)
            if not self._ensure_pages(
                    seq, seq.cursor + min(len(seq.remaining_prompt),
                                          self.prefill_chunk)):
                continue  # failed cleanly; other slots keep running
            chunk = seq.remaining_prompt[:self.prefill_chunk]
            seq.remaining_prompt = seq.remaining_prompt[self.prefill_chunk:]
            # NumPy, uploaded with the call: jnp.asarray of a list with a
            # dtype would run an eager convert program of its own a chunk
            return seq, np.asarray(
                [chunk + [0] * (self.prefill_chunk - len(chunk))],
                np.int32), len(chunk)
        return None

    def _dispatch_chunk(self, seq: _Seq, tokens, real: int,
                        rows: Optional[_LiveRows]) -> None:
        """Dispatch the program of the chunk ``_next_chunk`` picked. It
        takes the decode step along, over ONE read of the weights, for every
        model (the program meets each layer's pages or states a group of
        rows at a time: ``decode._paged_forward_inplace``): ``rows``,
        the turn's live decode rows (``_step_rows``), or with None no row
        active (the speculative loop, whose rows go through the verify
        program).

        Nothing is waited for: a prompt's last chunk samples the first token
        into ``_ids`` on the device, the sequence joins the decode rows of
        the NEXT program, and the host reads the token with the other ids
        (``_collect``). The program's device time is read from a profiler
        trace by its name, and the wait for it falls into the phase that
        reads its result."""
        import numpy as np

        from ray_tpu.models.decode import StepRows

        last = not seq.remaining_prompt
        rows = rows or self._no_rows
        live = rows.live
        step = StepRows(rows.active, self._cursors(), *self._tables(),
                        rows.temperature, rows.seeds)
        self._n_prefill_tokens += real
        self._launch(self._prefill(
            self.params, tokens, np.int32(real), np.int32(seq.cursor),
            *self._tables(seq.slot), self._caches, self._ids,
            np.int32(seq.slot if last else -1),
            np.float32(seq.temperature), np.uint32(seq.seed), step,
            np.int32(seq.slot)),
            step=bool(live), chunk=True,
            rows=live + [seq] if last else live, live_rows=real + len(live))
        self._record_attn(self.prefill_chunk, [seq.cursor], real=real)
        seq.cursor += real
        self._n_prefill_chunks += 1
        _m_prefill_chunks.inc()
        self._stepped(live)
        if live:
            self._n_fused_turns += 1
            self._n_fused_step_rows += len(live)
        if last:
            # prompt fully resident and its first token on the way: the
            # sequence rides in the next program's decode rows already
            if self._radix is not None:
                self._offer_prompt_pages(seq)
            seq.state = _DECODE
            seq.n_launched = 1

    def _offer_prompt_pages(self, seq: _Seq) -> None:
        """Prompt fully resident: offer its full pages to the radix cache
        so a later admit with the same prefix splices instead of
        re-prefilling. Pages the tree adopts become shared read-only
        (write-table entries redirect to the garbage page — they are
        never written again anyway: pads and decode tokens land at
        positions >= the prompt length, i.e. in later pages); spans
        another sequence cached first stay slot-owned duplicates. The
        slot swaps its admission-time node ref for the deeper inserted
        node, which pins the whole path against eviction while it
        decodes."""
        T = self.page_tokens
        ins_len = (len(seq.prompt) // T) * T
        if ins_len <= seq.cached_len:
            return
        n = ins_len // T
        slot = seq.slot
        offered = [int(x) for x in self._read_tables[slot, :n]]
        dups, node = self._radix.insert(seq.prompt[:ins_len], offered)
        adopted = set(offered) - set(dups)
        if adopted:
            seq.owned_pages = [p for p in seq.owned_pages
                               if p not in adopted]
            for j in range(n):
                if int(self._write_tables[slot, j]) in adopted:
                    self._write_tables[slot, j] = 0
        if node is not None:
            if seq.radix_node is not None:
                self._radix.release(seq.radix_node)
            seq.radix_node = node

    # ------------------------------------------- cross-replica migration

    def _requeue(self, seq: _Seq) -> None:
        with self._lock:
            self._pending.appendleft(seq)
            _m_queue_depth.set(float(len(self._pending)))

    def _ensure_mig_thread(self) -> None:
        if self._mig_thread is None:
            t = threading.Thread(target=self._migration_worker,
                                 name="serve-migration-puller", daemon=True)
            self._mig_thread = t
            t.start()

    def _migration_worker(self) -> None:
        """Blocking peer pulls live here, NEVER on the scheduler thread —
        a dead or slow holder must not stall in-flight decodes. The pull
        is replica→replica (PR-2 pull idiom): the controller is not on
        the data path."""
        import ray_tpu

        while True:
            item = self._mig_requests.get()
            if item is None:
                return
            seq, handle, tokens = item
            try:
                res = ray_tpu.get(handle.export_prefix.remote(list(tokens)),
                                  timeout=30.0)
            except Exception as e:  # noqa: BLE001 — any failure = cold path
                res = {"__error__": f"{type(e).__name__}: {e}"}
            self._mig_results.put((seq, res))
            self._wake.set()

    def _start_migrations(self) -> None:
        """Pre-admission pass: pending sequences carrying a router fleet
        hint are parked in ``_migrating`` while the worker pulls their
        prefix from the holder. The want-length is page-aligned, clamped
        to what is NOT already cached locally, and bounded by the
        migration budget — a hint that buys nothing re-queues for normal
        (cold or locally-warm) admission immediately."""
        if self._radix is None:
            return
        with self._lock:
            flagged = [s for s in self._pending if s.fleet_hint is not None]
            for s in flagged:
                self._pending.remove(s)
            if flagged:
                _m_queue_depth.set(float(len(self._pending)))
        for seq in flagged:
            hint = seq.fleet_hint or {}
            seq.fleet_hint = None
            handle = hint.get("handle")
            hint_tokens = int(hint.get("tokens") or 0)
            if seq.cancelled:
                self._retire(seq, "cancelled")
                continue
            T = self.page_tokens
            _pages, matched, node = self._radix.match(seq.prompt[:-1])
            if node is not None:
                self._radix.release(node)
            want = min(hint_tokens, len(seq.prompt) - 1)
            want = (want // T) * T
            want = min(want, matched + self.migration_budget * T)
            if handle is None or want <= matched:
                self._requeue(seq)
                continue
            self._ensure_mig_thread()
            self._migrating.append(seq)
            self._mig_requests.put((seq, handle, seq.prompt[:want]))

    def _finish_migrations(self) -> None:
        """Drain completed pulls (success or failure) and re-queue their
        sequences for normal admission — a successful splice means the
        admission-time ``_splice_prefix`` now hits the migrated span, a
        failed pull means a plain cold prefill. Either way the OUTPUT is
        the same tokens; migration only moves where the KV comes from."""
        while True:
            try:
                seq, res = self._mig_results.get_nowait()
            except _QueueEmpty:
                return
            try:
                self._migrating.remove(seq)
            except ValueError:
                pass
            if seq.state == _DONE:
                continue
            if seq.cancelled:
                self._retire(seq, "cancelled")
                continue
            ok = (isinstance(res, dict) and "__error__" not in res
                  and int(res.get("matched_len") or 0) > 0
                  and int(res.get("page_tokens") or 0) == self.page_tokens)
            if ok:
                self._clock.switch(_P_MIGRATE)
                try:
                    self._splice_migrated(seq, res)
                except Exception:  # noqa: BLE001 — abandon to cold prefill
                    self._n_migration_failures += 1
                self._clock.switch(_P_ADMIT)
            else:
                self._n_migration_failures += 1
            self._requeue(seq)

    def _splice_migrated(self, seq: _Seq, res: Dict[str, Any]) -> None:
        """Copy pulled prefix KV into freshly-allocated local pages and
        insert the span into the radix tree (pinned via the sequence's
        ``migration_node`` until admission splices it). Any failure —
        allocation, shape, dtype — propagates to the caller, which counts
        it and lets the sequence prefill cold; nothing here is ever
        half-applied: pages are only reachable once ``insert`` succeeds."""
        import dataclasses

        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.serve._private.affinity import (m_migrated_pages,
                                                     m_migrations)
        from ray_tpu.serve._private.paging import OutOfPagesError

        T = self.page_tokens
        matched = (int(res["matched_len"]) // T) * T
        n = matched // T
        if n <= 0:
            raise ValueError("empty migration payload")
        try:
            pages = self._arena.alloc(n)
        except OutOfPagesError:
            self._radix.evict(n - self._arena.free_pages)
            pages = self._arena.alloc(n)
        try:
            idx = jnp.asarray(np.asarray(pages, np.int32))
            out = []
            for li, c in enumerate(self._caches):
                k = jnp.asarray(np.asarray(res["k"][li]), c.k.dtype)
                v = jnp.asarray(np.asarray(res["v"][li]), c.v.dtype)
                out.append(dataclasses.replace(
                    c, k=c.k.at[idx].set(k), v=c.v.at[idx].set(v)))
            self._jax.block_until_ready(out[0].k)
            self._caches = out
            dups, node = self._radix.insert(seq.prompt[:matched], pages)
        except BaseException:
            self._arena.free(pages)
            raise
        if dups:
            # spans another sequence cached while we pulled: keep theirs
            self._arena.free(dups)
        if node is not None:
            seq.migration_node = node
        self._n_migrations += 1
        self._n_migrated_pages += n - len(dups)
        m_migrations.inc()
        m_migrated_pages.inc(n - len(dups))

    # -------------------------------------------------- prefix export

    def export_prefix(self, tokens: List[int],
                      timeout_s: float = 30.0) -> Dict[str, Any]:
        """Serve a migration pull FROM a peer replica. Called on an RPC
        thread; the actual radix match + device gather must run on the
        scheduler thread (sole owner of the tree and the donated caches),
        so this enqueues a command and waits. The matched node is pinned
        only for the duration of the gather."""
        if self.cfg.recurrent:
            raise ValueError(
                "a model with layers that keep a state a slot exports no "
                "prefix: pages alone, if it holds any, do not continue a "
                "sequence")
        if self._window:
            raise ValueError(
                "a model with 'sliding_attention' layers exports no prefix: "
                "the full layers' pages alone do not continue a sequence, "
                "and the window layers' are released behind the window")
        if self._radix is None:
            return {"matched_len": 0, "page_tokens": self.page_tokens,
                    "k": [], "v": []}
        box: Dict[str, Any] = {}
        done = threading.Event()
        with self._lock:
            if self._closed:
                raise SchedulerClosedError("scheduler is shut down")
            self._commands.append((list(tokens), box, done))
        self._wake.set()
        if not done.wait(timeout=timeout_s):
            raise TimeoutError(
                f"export_prefix timed out after {timeout_s:.0f}s")
        if "error" in box:
            raise RuntimeError(box["error"])
        return box["result"]

    def _process_commands(self) -> None:
        while self._commands:
            try:
                tokens, box, done = self._commands.popleft()
            except IndexError:
                return
            try:
                box["result"] = self._export_prefix_now(tokens)
            except BaseException as e:  # noqa: BLE001 — crosses threads
                box["error"] = f"{type(e).__name__}: {e}"
            done.set()

    def _export_prefix_now(self, tokens: List[int]) -> Dict[str, Any]:
        import numpy as np

        pages, matched, node = self._radix.match(tokens)
        if matched == 0:
            return {"matched_len": 0, "page_tokens": self.page_tokens,
                    "k": [], "v": []}
        n = matched // self.page_tokens
        idx = np.asarray(pages[:n], np.int32)
        ks, vs = [], []
        try:
            for c in self._caches:
                ks.append(np.asarray(c.k[idx]))
                vs.append(np.asarray(c.v[idx]))
        finally:
            self._radix.release(node)
        return {"matched_len": n * self.page_tokens,
                "page_tokens": self.page_tokens, "k": ks, "v": vs}

    def prefix_digest(self) -> Dict[str, Any]:
        """Chain-hash digest of the radix cache for the affinity router.
        Probed OFF the scheduler thread (the stats path), so the rare
        mid-mutation dict iteration is retried rather than locked — the
        digest is advisory; a stale read costs one cold prefill at most."""
        if self._radix is None:
            return {}
        for _ in range(8):
            try:
                return self._radix.digest()
            except RuntimeError:
                continue
        return {}

    # ------------------------------------------------ speculative decode

    def _prime_drafter(self, seq: _Seq) -> None:
        """First speculative round for a freshly-decoding slot: give the
        drafter the sequence's full context up to the cursor. A drafter
        sharing the target's params ADOPTS the paged KV by gather (prefix
        splices included — the TTFT win survives); a distinct drafter
        must run the prompt through its own model."""
        if self._drafter.shares_target:
            self._drafter.adopt_from_paged(
                seq.slot, self._caches, self._read_tables[seq.slot],
                int(seq.cursor), self.page_tokens)
        else:
            self._drafter.prefill_prompt(seq.slot, seq.prompt,
                                         self.prefill_chunk)
        seq.drafter_len = int(seq.cursor)
        seq.drafter_pending = []

    def _decode_spec(self) -> bool:
        """One speculative round over every DECODE slot: exactly
        ``spec_k`` batched drafter steps propose tokens, ONE fixed-shape
        ``paged_verify_step`` scores every proposal, and exact
        accept-prefix + corrected-resample emits 1..spec_k+1 tokens per
        live sequence. A rejection just does not advance ``seq.cursor``
        past it — pages are never freed or mutated by one; stale KV past a
        cursor is causally masked until overwritten.

        Drafter sync: the drafter always steps ``spec_k`` times (fixed
        program shapes), but after a fully-accepted round it first
        catches up on the accepted token it never consumed
        (``drafter_pending``), producing one fewer draft that round.

        This is the one synchronous loop left: acceptance and the corrected
        resample need the window's whole logits on the host, so the round
        reads everything in flight (a prompt's first token among it) before
        it drafts, and its own result before it returns."""
        import numpy as np

        import jax.numpy as jnp

        from ray_tpu.serve._private.speculative import (_softmax,
                                                        accept_greedy,
                                                        accept_sample,
                                                        m_spec_accepted,
                                                        m_spec_drafted)

        k = self.spec_k
        K = k + 1
        switch = self._clock.switch
        self._collect(len(self._inflight))
        switch(_P_PREPARE)  # drafting is this path's preparation
        live: List[_Seq] = []
        for seq in self._slot_seqs:
            if seq is None or seq.state != _DECODE:
                continue
            if seq.cancelled:
                self._retire(seq, "cancelled")
                continue
            # the verify window writes positions [cursor, cursor + K)
            if not self._ensure_pages(seq, seq.cursor + K):
                continue
            live.append(seq)
        if not live:
            return False
        for seq in live:
            if seq.drafter_len < 0:
                self._prime_drafter(seq)
        # ---- draft: k batched drafter steps, sampled host-side --------
        feed = {s.slot: list(s.drafter_pending) + [s.next_token]
                for s in live}
        pend0 = {s.slot: list(s.drafter_pending) for s in live}
        drafts: Dict[int, List[int]] = {s.slot: [] for s in live}
        dprobs: Dict[int, List[Any]] = {s.slot: [] for s in live}
        toks = np.zeros(self.slots, np.int32)
        active = np.zeros(self.slots, np.int32)
        for s in live:
            active[s.slot] = 1
        for _ in range(k):
            for s in live:
                sl = s.slot
                toks[sl] = feed[sl].pop(0) if feed[sl] else drafts[sl][-1]
            la = self._drafter.step(toks, active)
            for s in live:
                sl = s.slot
                if feed[sl]:
                    continue  # still catching up; not at the draft frontier
                if s.temperature <= 0.0:
                    d = int(la[sl].argmax())
                else:
                    if s.rng is None:
                        s.rng = np.random.default_rng(s.seed)
                    p = _softmax(la[sl], s.temperature)
                    dprobs[sl].append(p)
                    d = int(s.rng.choice(len(p), p=p))
                drafts[sl].append(d)
        # ---- verify: ONE fixed-shape K-token target call --------------
        vt = np.zeros((self.slots, K), np.int32)
        used = np.zeros(self.slots, np.int32)  # window rows with a token
        for s in live:
            row = [s.next_token] + drafts[s.slot]
            vt[s.slot, :len(row)] = row
            used[s.slot] = len(row)
        switch(_P_VERIFY)
        out = self._verify(
            self.params, jnp.asarray(vt), jnp.asarray(used), self._cursors(),
            jnp.asarray(self._read_tables),
            jnp.asarray(self._write_tables), self._caches)
        self._caches = out[1]
        va = np.asarray(out[0])
        self._n_drains += 1  # read with nothing queued behind it
        if self._moe:
            self._moe_count(out[2]["counts"], int(used.sum()))
        switch(_P_EMIT)  # acceptance and emission
        self._record_attn(K, [s.cursor for s in live],
                          self.slots - len(live))
        self._n_steps += 1
        _m_steps.inc()
        self._n_spec_rounds += 1
        self._max_active_slots = max(self._max_active_slots, len(live))
        # ---- exact acceptance: the cursor moves past what was accepted -
        dlen = self._drafter.lengths().copy()
        # the round's accepted tokens are ONE read: one stamp, one hand-over;
        # the verify call came after every chunk dispatched so far
        self._begin_read(self._n_prefill_tokens)
        try:
            for s in live:
                sl = s.slot
                ds = drafts[sl]
                old = s.cursor
                nxt = s.next_token
                if s.temperature <= 0.0:
                    a, emitted = accept_greedy(ds, va[sl])
                else:
                    if s.rng is None:
                        s.rng = np.random.default_rng(s.seed)
                    pt = [_softmax(va[sl, j], s.temperature)
                          for j in range(len(ds) + 1)]
                    a, emitted = accept_sample(ds, dprobs[sl], pt, s.rng)
                self._n_drafted += len(ds)
                self._n_accepted += a
                if ds:
                    m_spec_drafted.inc(len(ds))
                if a:
                    m_spec_accepted.inc(a)
                new_cursor = old + a + 1
                s.cursor = new_cursor
                # drafter sync: positions [L0, L0 + k) were consumed this
                # round; the valid prefix stops at the last accepted position,
                # and whatever accepted tokens the drafter missed become next
                # round's catch-up feed
                L0 = s.drafter_len
                valid = min(L0 + k, new_cursor)
                hist = pend0[sl] + [nxt] + list(ds[:a])
                s.drafter_pending = hist[valid - L0:new_cursor - L0]
                s.drafter_len = valid
                dlen[sl] = valid
                finished = False
                for tok in emitted:
                    s.next_token = tok
                    self._n_spec_emitted += 1
                    if self._emit_token(s, tok):
                        finished = True
                        break
                if finished:
                    self._retire(s, "eos" if self.eos_id is not None
                                 and s.next_token == self.eos_id else "length")
        finally:
            self._end_read()
        self._drafter.set_lengths(dlen)
        return True

    def _step_rows(self) -> _LiveRows:
        """The turn's decode rows, from what the host knows without the
        previous program's result: every DECODE slot that still has a token
        to take and a page to write it on."""
        self._clock.switch(_P_PREPARE)
        rows = _LiveRows(self.slots)
        for i, seq in enumerate(self._slot_seqs):
            if seq is None or seq.state != _DECODE:
                continue
            if seq.cancelled:
                self._retire(seq, "cancelled")
                continue
            if seq.n_launched >= seq.max_new:
                continue  # its last token is in flight: nothing to add
            if not self._ensure_pages(seq, seq.cursor + 1):
                continue  # this sequence failed cleanly; others continue
            rows.active[i] = 1
            rows.temperature[i] = seq.temperature
            rows.seeds[i] = seq.seed
            rows.live.append(seq)
        return rows

    def _stepped(self, live: List[_Seq]) -> None:
        """Behind the dispatch of a program that ran the step's attention
        call over ``live`` (the plain step, or a chunk's program that took
        the rows along): count the call, and move the live rows on by the
        token that is now on its way."""
        self._record_attn(1, [s.cursor for s in live], self.slots - len(live))
        if not live:
            return
        if self._steps_unread:
            self._n_runahead += 1
        self._steps_unread += 1
        for seq in live:
            seq.cursor += 1
            seq.n_launched += 1
        self._n_steps += 1
        _m_steps.inc()
        self._max_active_slots = max(self._max_active_slots, len(live))

    def _turn(self, behind: int) -> bool:
        """One turn of the loop, one step ahead: pick the chunk that is due,
        build the decode rows, dispatch ONE program — the chunk's, with the
        rows, where there is a chunk, else the plain step over the rows: one
        rule for every model, whatever the kinds of its layers — and THEN
        read the ``behind`` programs dispatched in earlier turns
        (``_collect``). The rows' tokens are ``_ids``, on the device since
        the programs that sampled them. Returns True if a program was
        dispatched or a result read."""
        chunk = self._next_chunk()
        rows = self._step_rows()
        if self._window and (chunk is not None or rows.live):
            # a turn's sample, behind its releases and allocations: what the
            # window layers' pool holds, beside what it would hold of the
            # same sequences had nothing been released
            T = self.page_tokens
            self._n_window_tokens_held += T * self._window_arena.pages_in_use
            self._n_window_tokens_unreleased += T * sum(
                s.window_fill for s in self._slot_seqs if s is not None)
        if chunk is not None:
            self._dispatch_chunk(*chunk, rows)
        elif rows.live:
            # the tables go up as COPIES (see _tables)
            self._launch(self._step(
                self.params, self._ids, rows.active, self._cursors(),
                *self._tables(), self._caches, rows.temperature, rows.seeds),
                step=True, chunk=False, rows=rows.live,
                live_rows=len(rows.live))
            self._stepped(rows.live)
        return (self._collect(behind) or chunk is not None
                or bool(rows.live))

    def _run(self) -> None:
        clock = self._clock
        t_turn = 0  # when the previous turn ended (0: no turn to compare)
        try:
            while True:
                clock.switch(_P_ADMIT)
                t_now, held_by, _ = clock.lap()
                if t_turn and t_now - t_turn > _STALL_NS and any(
                        s is not None for s in self._slot_seqs):
                    self._note_stall(t_now - t_turn, held_by)
                t_turn = t_now
                with self._lock:
                    if self._closed:
                        break
                self._process_commands()
                self._finish_migrations()
                self._start_migrations()
                self._admit()
                behind = len(self._inflight)
                if self._drafter is not None:
                    chunk = self._next_chunk()
                    if chunk is not None:
                        self._dispatch_chunk(*chunk, None)
                    did = self._decode_spec() or chunk is not None
                else:
                    did = self._turn(behind)
                _m_active.set(float(sum(
                    1 for s in self._slot_seqs if s is not None)))
                if did:
                    self._n_turns += 1
                else:
                    clock.switch(_P_PARK)
                    self._turn_stamp = 0  # no turn spans a pause
                    with self._lock:
                        idle = (not self._pending and not self._commands
                                and not self._migrating and all(
                                    s is None or s.cancelled
                                    for s in self._slot_seqs))
                        if idle:
                            self._wake.clear()
                    self._wake.wait(timeout=1.0)
        except BaseException as e:  # noqa: BLE001 — crosses to consumers
            self._error = e
            with self._lock:
                self._closed = True
            for seq in list(self._slot_seqs):
                if seq is not None:
                    self._fail(seq, f"{type(e).__name__}: {e}")
            with self._lock:
                pending = list(self._pending)
                self._pending.clear()
            for seq in pending:
                self._fail(seq, f"{type(e).__name__}: {e}")
            for seq in list(self._migrating):
                self._fail(seq, f"{type(e).__name__}: {e}")
            self._migrating.clear()
            self._drain_commands("scheduler crashed")
        finally:
            clock.stop()
            with self._lock:
                self._closed = True
            _m_active.set(0.0)

    def _note_stall(self, ns: int, phase: int) -> None:
        """A loop turn, end to end, took longer than ``_STALL_NS`` while a
        slot was live: count the time beyond the limit and which phase
        held most of the turn."""
        self._stall_s += (ns - _STALL_NS) / 1e9
        self._n_stalls += 1
        self._stall_phase = PHASES[phase]
        flight.instant(_F_STALL, (ns // 1000) << 8 | phase)

    # --------------------------------------------------------- lifecycle

    def _drain_commands(self, msg: str) -> None:
        """Unblock every RPC thread waiting in ``export_prefix`` with an
        error — a peer's pull degrades to its cold prefill."""
        while self._commands:
            try:
                _tokens, box, done = self._commands.popleft()
            except IndexError:
                return
            box["error"] = msg
            done.set()

    def shutdown(self, timeout_s: float = 5.0) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            pending = list(self._pending)
            self._pending.clear()
        self._wake.set()
        self._thread.join(timeout=timeout_s)
        for seq in pending:
            self._fail(seq, "scheduler shut down")
        for seq in list(self._slot_seqs):
            if seq is not None:
                self._fail(seq, "scheduler shut down")
        for seq in list(self._migrating):
            self._fail(seq, "scheduler shut down")
        self._migrating.clear()
        self._drain_commands("scheduler shut down")
        if self._mig_thread is not None:
            self._mig_requests.put(None)
        if self._radix is not None:
            # every slot ref is gone; drain the cache so the page gauge
            # returns to zero (chaos_soak asserts this after a kill)
            self._radix.clear()

    @property
    def closed(self) -> bool:
        return self._closed

    def compiled_programs(self) -> int:
        """Total compiled program count across the scheduler's jitted
        entry points — the two-compiles contract says this is exactly 2
        (one prefill shape + one decode shape) no matter how lengths,
        pages and prefix hits churn, and whether or not a chunk takes decode
        rows along (it always takes the step's arrays, none active where
        none is live); speculative decoding adds the verify program as the
        only new shape (and the plain decode step, never driven in spec
        mode, stays uncompiled — the total remains 2; the drafter's own
        programs are reported separately in stats)."""
        n = self._prefill._cache_size() + self._step._cache_size()
        if self._verify is not None:
            n += self._verify._cache_size()
        return int(n)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            q = len(self._pending)
        (tokens, first_tokens, gap_plain, gap_plain_ns, gap_prefill,
         gap_prefill_ns) = self._emitted
        out = {
            "mode": "continuous",
            "slots": self.slots,
            "prefill_chunk": self.prefill_chunk,
            "arena_len": self.arena_len,
            "decode_steps": self._n_steps,
            "prefill_chunks": self._n_prefill_chunks,
            # prompt tokens dispatched (the chunks' real tokens), and loop
            # turns that dispatched or read a program
            "prefill_tokens": self._n_prefill_tokens,
            "turns": self._n_turns,
            # chunk programs that took live decode rows along (they count in
            # prefill_chunks AND in decode_steps), and those rows;
            # fused_turns / prefill_chunks is how often a turn with a chunk
            # carried a live row
            "fused_turns": self._n_fused_turns,
            "fused_step_rows": self._n_fused_step_rows,
            "admitted": self._n_admitted,
            "retired": self._n_retired,
            "tokens_generated": tokens,
            # iteration-level proof signals: > 0 means a request was
            # admitted while others were mid-generation, which a
            # flush-and-drain batcher can never do
            "admitted_mid_flight": self._admitted_mid_flight,
            "max_active_slots": self._max_active_slots,
            "peak_queue_depth": self._peak_queue_depth,
            "queue_depth": q,
            "active_slots": sum(1 for s in self._slot_seqs if s is not None),
            "compiled_programs": self.compiled_programs(),
            # a request's life: submit -> admit (count: admitted) and
            # admit -> first token (count: first_tokens), summed
            "queue_wait_s": self._queue_wait_s,
            "first_token_wait_s": self._first_token_wait_s,
            "first_tokens": first_tokens,
            # every emitted token after its sequence's first, by what the
            # device was given between the programs that sampled it and
            # the one before it: decode work only (plain) or prompt tokens
            # too (prefill); seconds between the two reads, 0 with the
            # recorder off. plain + prefill + first_tokens ==
            # tokens_generated, exactly, in every snapshot (_emitted)
            "gap_plain_tokens": gap_plain,
            "gap_plain_s": gap_plain_ns / 1e9,
            "gap_prefill_tokens": gap_prefill,
            "gap_prefill_s": gap_prefill_ns / 1e9,
            # loop turns longer than 1 s while a slot was live: the time
            # beyond it, how many, and the phase that held the last one
            "stall_s": self._stall_s,
            "stalls": self._n_stalls,
            "stall_phase": self._stall_phase,
            # the loop runs a step ahead: decode steps dispatched while the
            # previous step's ids were unread (over decode_steps: the
            # run-ahead share), reads with no program queued behind them
            # (first step after park, every speculative round), and rows
            # computed for a sequence that had already ended
            "runahead_steps": self._n_runahead,
            "pipeline_drains": self._n_drains,
            "discarded_rows": self._n_discarded,
        }
        # the scheduler thread's own time by phase (0 with the recorder off)
        out.update(zip(_PHASE_KEYS, self._clock.seconds()))
        out["page_tokens"] = self.page_tokens
        out["pages_per_slot"] = self._pages_per_slot
        out["attn_lane"] = self.attn_lane
        out["attn_bytes_moved"] = self._n_attn_bytes
        out["attn_tokens_attended"] = self._n_attn_attended
        out["attn_tokens_fetched"] = self._n_attn_fetched
        if self._state_bytes:
            # a float32 state a slot a layer that keeps one, by kind
            # (``transformer.state_shapes``)
            out["state_slots"] = self.slots
            out["state_bytes"] = self._state_bytes
        if self._n_linear:
            # layer-calls of the chunked scan; live rows x layers of the
            # one-row update
            out["linear_chunk_calls"] = self._n_linear_chunk_calls
            out["linear_step_rows"] = self._n_linear_step_rows
        if self._n_retention:
            # the same of the 'power-retention' layers, and the real tokens
            # their chunk calls carried
            out["retention_chunk_calls"] = self._n_retention_chunk_calls
            out["retention_chunk_tokens"] = self._n_retention_chunk_tokens
            out["retention_step_rows"] = self._n_retention_step_rows
        if self._n_sparse:
            # query rows x 'minicpm4' layers (real tokens of a chunk, live
            # rows of a step), those at or under dense_len, and the tokens
            # of the blocks they attended over the tokens of their contexts
            out["sparse_rows"] = self._n_sparse_rows
            out["sparse_rows_dense"] = self._n_sparse_rows_dense
            out["sparse_tokens_attended"] = self._n_sparse_attended
            out["sparse_tokens_context"] = self._n_sparse_context
            # of which a step's rows (the rest are a chunk's queries)
            out["sparse_step_tokens_attended"] = self._n_sparse_step_attended
            out["sparse_step_tokens_context"] = self._n_sparse_step_context
        if self._moe:
            # the device's per-expert row counts, summed a layer-call
            # (one expert layer in one program run) as of the last
            # fetch; rows_routed == live_rows x top_k x layers exactly:
            # no row is dropped
            out["moe_live_rows"] = self._n_moe_live_rows
            out["moe_layer_calls"] = self._n_moe_layer_calls
            out["moe_rows_routed"] = self._n_moe_rows_routed
            out["moe_experts_hit"] = self._n_moe_experts_hit
            out["moe_max_expert_rows"] = self._n_moe_max_expert_rows
        out.update(self._arena.stats())
        if self._window:
            arena = self._window_arena.stats()
            # by pool: the full layers' pages (``kv_pages``: the keys above
            # too) and the window layers', whose pool the scheduler sized
            out["kv_pages_in_use_full"] = out["pages_in_use"]
            out["kv_peak_pages_in_use_full"] = out["peak_pages_in_use"]
            out["kv_pages_in_use_window"] = arena["pages_in_use"]
            out["kv_peak_pages_in_use_window"] = arena["peak_pages_in_use"]
            out["window_pages_released"] = self._n_window_released
            out["window_tokens_held"] = self._n_window_tokens_held
            out["window_tokens_unreleased"] = self._n_window_tokens_unreleased
            # what the mask admits, by kind and by work (``_record_window``)
            out["window_attn_step_keys"] = self._n_window_step_keys
            out["full_attn_step_keys"] = self._n_full_step_keys
            out["window_attn_chunk_pairs"] = self._n_window_chunk_pairs
            out["full_attn_chunk_pairs"] = self._n_full_chunk_pairs
        # 0 without a prefix cache: no prompt token was served from one
        out["prefix_hit_tokens"] = self._n_prefix_hit_tokens
        if self._radix is not None:
            out.update(self._radix.stats())
            out["migrations"] = self._n_migrations
            out["migrated_pages"] = self._n_migrated_pages
            out["migration_failures"] = self._n_migration_failures
            out["migrations_pending"] = len(self._migrating)
        if self._drafter is not None:
            out["spec_k"] = self.spec_k
            out["drafter"] = self._drafter.name
            out["spec_rounds"] = self._n_spec_rounds
            out["spec_drafted_tokens"] = self._n_drafted
            out["spec_accepted_tokens"] = self._n_accepted
            out["spec_accept_rate"] = (
                self._n_accepted / self._n_drafted
                if self._n_drafted else 0.0)
            out["spec_tokens_per_step"] = (
                self._n_spec_emitted / self._n_spec_rounds
                if self._n_spec_rounds else 0.0)
            out["drafter_compiled_programs"] = (
                self._drafter.compiled_programs())
        return out
