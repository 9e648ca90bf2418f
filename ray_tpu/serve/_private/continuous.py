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

THE KINDS OF THE MODEL'S LAYERS (``TransformerConfig.layer_kinds``) are not
named here. What they hold of pages is ``_pools`` (``paging.build_pools``:
none, one or several ``PagePool`` — an arena, every slot's tables, perhaps a
window behind which a slot's pages go back before a turn allocates); a state
a slot is part of ``_caches``, zero at position 0 and bitwise alone where a
row is not live, so an admission runs no device program for any model. What
that forbids where pages alone do not continue a sequence (the prefix cache,
prefix export / migration, the speculative programs) is
``paging.cannot_continue``, asked when the scheduler is built. What a call
did, by kind, is ``_work`` (``work.Work``), merged into ``stats()`` unread.

Knobs: ``RAY_TPU_SERVE_SLOTS`` (slots), ``RAY_TPU_SERVE_PREFILL_CHUNK``
(prefill chunk tokens), ``RAY_TPU_SERVE_PAGE_TOKENS``,
``RAY_TPU_SERVE_KV_PAGES`` (0 = size the pool to every slot's worst case),
``RAY_TPU_SERVE_PREFIX_CACHE``, ``RAY_TPU_SERVE_MIGRATION_BUDGET`` (pages
per cross-replica pull), ``RAY_TPU_SERVE_SPEC_K`` (draft tokens per verify
round), ``RAY_TPU_SERVE_DRAFTER`` (drafter preset; ``"self"`` shares the
target's weights); all overridable per-deployment via LLMServer init.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from queue import Empty as _QueueEmpty
from queue import Queue as _Queue
from typing import Any, Dict, List, Optional

from ray_tpu._private import flight
from ray_tpu._private.metrics import Counter, Gauge, Histogram
from ray_tpu.serve._private.paging import (OutOfPagesError, RadixCache,
                                           SlotPages, build_pools,
                                           cannot_continue, pool_stats,
                                           pool_tables)

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
                 "cached_len", "cursor", "held", "radix_node",
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
        self.held: tuple = ()  # of each pool, once seated (``SlotPages``)
        self.radix_node = None         # ref-counted prefix-cache node
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
                 "returned", "live_rows")

    def __init__(self, serial: int, prefill_mark: int, step: bool,
                 chunk: bool, ids, rows: List[_Seq], returned: tuple,
                 live_rows: int):
        self.serial = serial        # its number among the dispatched programs
        # prompt tokens dispatched so far, this program's own among them
        self.prefill_mark = prefill_mark
        self.step = step            # it advanced decode rows
        self.chunk = chunk          # it carried a prefill chunk (or both)
        self.ids = ids              # the program's [slots] ids, on the device
        # the sequences it sampled a token for: the decode rows and, behind
        # them, the prompt whose last chunk it carried
        self.rows = rows
        self.returned = returned    # beside ids and caches: ``_work``'s
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
        import jax

        from ray_tpu._private.config import global_config
        from ray_tpu.models.decode import (init_paged_caches,
                                           paged_decode_step,
                                           paged_prefill_into_slot,
                                           paged_verify_step)
        from ray_tpu.ops.paged_attention import resolve_impl
        from ray_tpu.serve._private.work import Work

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
        # the implementation resolves ONCE at build — an unknown value
        # fails the constructor, not some later decode step, and stats()
        # always names what really runs
        self.attn_lane = resolve_impl(cfg, attn)
        self._pools = build_pools(
            cfg, slots=self.slots, page_tokens=self.page_tokens,
            pages_per_slot=self._pages_per_slot, num_pages=kvp,
            prefill_chunk=self.prefill_chunk)
        if not self._pools:
            # no layer holds a page: memory is a state a slot and a request
            # is bounded by arena_len alone (the knobs above were checked as
            # given): no pool on the device, no table uploaded
            self.page_tokens, self._pages_per_slot = self.arena_len, 1
        use_prefix = (conf.serve_prefix_cache if prefix_cache is None
                      else bool(prefix_cache))
        self._refused = cannot_continue(cfg, self._pools) or {}
        if prefix_cache and "prefix_cache" in self._refused:
            raise ValueError(self._refused["prefix_cache"])
        if drafter is not None and "drafter" in self._refused:
            raise ValueError(self._refused["drafter"])
        if "prefix_cache" in self._refused:
            use_prefix = False  # the configured default cannot apply
        # the prefix cache is the first pool's: it splices and adopts that
        # pool's pages, and the pool asks it for pages before it fails
        self._radix = None
        if use_prefix:
            first = self._pools[0]
            first.radix = self._radix = RadixCache(first.arena)
        self._work = Work(
            cfg, slots=self.slots, page_tokens=self.page_tokens,
            pages_per_slot=self._pages_per_slot, lane=self.attn_lane,
            itemsize=int(jax.numpy.dtype(cache_dtype or cfg.dtype).itemsize))
        program_kw = {"attn": self.attn_lane, **self._work.program_keywords}
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
            cfg, kvp, self.page_tokens, self._pages_per_slot, cache_dtype,
            slots=self.slots,
            window_pages=next((pool.arena.num_pages for pool in self._pools
                               if pool.window is not None), None))
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
        effective = min([self.arena_len]
                        + [pool.longest for pool in self._pools])
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
        if seq.radix_node is not None:
            self._radix.release(seq.radix_node)
            seq.radix_node = None
        for pool, held in zip(self._pools, seq.held):
            pool.free(seq.slot, held)

    def _release_migration_ref(self, seq: _Seq) -> None:
        """A migrated-prefix pin must drop no matter how the sequence
        ends — including cancellation BEFORE it ever took a slot (the
        node ref is held while the sequence waits in the pending queue)."""
        if seq.migration_node is not None and self._radix is not None:
            self._radix.release(seq.migration_node)
            seq.migration_node = None

    def _end(self, seq: _Seq, event: str, payload: str) -> None:
        """The one way out of the scheduler: ``("end", reason)`` for a
        sequence that retires, ``("err", message)`` for one that fails."""
        self._release_migration_ref(seq)
        self._release_slot_resources(seq)
        if seq.slot is not None:
            self._slot_seqs[seq.slot] = None
            seq.slot = None
        flight.instant(_F_RETIRE, seq.rid)
        seq.state = _DONE
        self._n_retired += 1
        _m_retired.inc()
        self._emit(seq, event, payload)

    def _retire(self, seq: _Seq, reason: str) -> None:
        self._end(seq, "end", reason)

    def _fail(self, seq: _Seq, msg: str) -> None:
        self._end(seq, "err", msg)

    def _ensure_pages(self, seq: _Seq, upto: int) -> bool:
        """Grow the slot's view of every pool to cover [0, upto) tokens
        (``PagePool.grow``). On exhaustion the SEQUENCE fails cleanly (the
        scheduler and its other slots keep running). Returns True if the
        pages are present."""
        need = -(-upto // self.page_tokens)
        try:
            for pool, held in zip(self._pools, seq.held):
                pool.grow(seq.slot, held, need, seq.cursor)
        except OutOfPagesError as e:
            self._fail(seq, str(e))
            return False
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
        self._pools[0].splice(seq.slot, seq.held[0], pages[:keep // T])
        seq.cached_len = keep
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
            seq.radix_node = None
            seq.held = tuple(SlotPages() for _ in self._pools)
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

    def _launch(self, out, *, step: bool, chunk: bool, rows: List[_Seq],
                live_rows: int) -> None:
        """Take over what a paged program returned: the ids stay on the
        device for the next program, the pool is the next program's, and
        what the host has to read of it later (ids, and whatever else it
        returned: ``_work`` asked for it) is queued for ``_collect``: one
        record a program."""
        self._ids, self._caches = out[0], out[1]
        self._serial += 1
        if rows or out[2:]:
            self._inflight.append(_Launched(
                self._serial, self._n_prefill_tokens, step, chunk, out[0],
                rows, out[2:], live_rows))

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
            if rec.returned:
                self._work.routed(rec.returned, rec.live_rows, rec.step)
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
        step = StepRows(rows.active, self._cursors(),
                        *pool_tables(self._pools), rows.temperature,
                        rows.seeds)
        self._n_prefill_tokens += real
        self._launch(self._prefill(
            self.params, tokens, np.int32(real), np.int32(seq.cursor),
            *pool_tables(self._pools, seq.slot), self._caches, self._ids,
            np.int32(seq.slot if last else -1),
            np.float32(seq.temperature), np.uint32(seq.seed), step,
            np.int32(seq.slot)),
            step=bool(live), chunk=True,
            rows=live + [seq] if last else live, live_rows=real + len(live))
        self._work.record(self.prefill_chunk, [seq.cursor], real=real)
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
        pool = self._pools[0]
        offered = [int(x) for x in pool.read[seq.slot, :ins_len // T]]
        dups, node = self._radix.insert(seq.prompt[:ins_len], offered)
        adopted = set(offered) - set(dups)
        if adopted:
            pool.share(seq.slot, seq.held[0], adopted)
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
        T, arena = self.page_tokens, self._radix.arena
        matched = (int(res["matched_len"]) // T) * T
        n = matched // T
        if n <= 0:
            raise ValueError("empty migration payload")
        pages = self._pools[0].take(n)
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
            arena.free(pages)
            raise
        if dups:
            # spans another sequence cached while we pulled: keep theirs
            arena.free(dups)
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
        if "export" in self._refused:
            raise ValueError(self._refused["export"])
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
                seq.slot, self._caches, self._pools[0].read[seq.slot],
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
            *pool_tables(self._pools), self._caches)
        self._caches = out[1]
        va = np.asarray(out[0])
        self._n_drains += 1  # read with nothing queued behind it
        if out[2:]:
            self._work.routed(out[2:], int(used.sum()))
        switch(_P_EMIT)  # acceptance and emission
        self._work.record(K, [s.cursor for s in live],
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
        self._work.record(1, [s.cursor for s in live], self.slots - len(live))
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
        if chunk is not None or rows.live:
            self._work.sample(self._pools)
        if chunk is not None:
            self._dispatch_chunk(*chunk, rows)
        elif rows.live:
            # the tables go up as COPIES (``paging.pool_tables``)
            self._launch(self._step(
                self.params, self._ids, rows.active, self._cursors(),
                *pool_tables(self._pools), self._caches, rows.temperature,
                rows.seeds),
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
        # the kinds' counts and the pools' pages: merged unread
        out.update(self._work.stats())
        out.update(pool_stats(self._pools))
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
