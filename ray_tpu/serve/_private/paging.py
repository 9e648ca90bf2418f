"""Paged KV arena allocator, a model's page pools + prefix/radix cache.

Host-side bookkeeping for the paged slot arena in ``models.decode``:

  * ``PageArena`` — a free-list allocator over the fixed pool of
    ``page_tokens``-sized KV pages. Page 0 is RESERVED as the garbage
    page (unallocated/shared write-table entries redirect there), so a
    pool of N pages holds N-1 sequences' worth of allocatable pages.
    Allocation and release are O(1) list ops on the scheduler thread —
    no locks, no RPCs, nothing on the device.

  * ``RadixCache`` — a radix tree over PROMPT token prefixes whose nodes
    reference refcounted read-only pages. Admitting a request whose
    prompt shares a cached prefix becomes a page-table splice + cursor
    jump (the PR-9 shared-weights idiom applied to KV) instead of a
    re-prefill. Every node covers a whole number of pages, so a partial
    match SPLITS an edge cleanly at a page boundary. Eviction is LRU
    over refcount-0 LEAVES under arena pressure (an interior node is
    unreachable-from-root once evicted, so leaves go first and parents
    become evictable as their subtrees drain).

  * ``PagePool`` — one pool as the scheduler drives it: an arena, every
    slot's host page tables, the window the pool keeps. ``build_pools``
    makes a model's pools from its layers' kinds and ``cannot_continue``
    says what those kinds forbid.

All are single-thread structures: the continuous scheduler owns them and
touches them only from its own loop thread (admission validation in
``submit`` is pure arithmetic and reads no allocator state).
"""

from __future__ import annotations

import time
from collections import deque
from typing import Collection, Dict, List, Optional, Tuple

from ray_tpu._private import flight
from ray_tpu._private.metrics import Counter, Gauge
from ray_tpu.serve._private.affinity import CHAIN_SEED, chain_hashes

F_PREFIX_HIT = flight.intern("serve.prefix_hit")
F_PAGE_ALLOC = flight.intern("serve.page_alloc")
F_EVICT = flight.intern("serve.evict")
# pages a window layers' arena took back from behind a slot's window
F_WINDOW_RELEASE = flight.intern("serve.window_release")

m_prefix_hits = Counter(
    "ray_tpu_serve_prefix_hits_total",
    "Admissions that spliced a cached KV prefix instead of re-prefilling")
m_prefix_misses = Counter(
    "ray_tpu_serve_prefix_misses_total",
    "Admissions that found no cached prefix (cold prefill)")
m_pages_allocated = Counter(
    "ray_tpu_serve_kv_pages_allocated_total",
    "KV pages handed out by the paged arena")
m_pages_freed = Counter(
    "ray_tpu_serve_kv_pages_freed_total",
    "KV pages returned to the paged arena free list")
m_pages_in_use = Gauge(
    "ray_tpu_serve_kv_pages_in_use",
    "KV pages currently allocated (slot-owned + prefix-cache resident)")
m_window_pages_released = Counter(
    "ray_tpu_serve_kv_window_pages_released_total",
    "KV pages of sliding-window layers released from behind a live "
    "sequence's window")

GARBAGE_PAGE = 0


class OutOfPagesError(RuntimeError):
    """The arena has no free page and nothing evictable remains."""


class PageArena:
    """Free-list allocator over the paged KV pool. Page ids are indices
    into the device-side ``PagedKVCache`` pools; page 0 never leaves the
    allocator (it is the shared garbage page). ``pool``: the name of a
    SECOND arena of one scheduler (the window layers' pool, 'window'),
    which labels what it puts in the process's metrics; the one arena every
    scheduler has carries no label, as ever."""

    def __init__(self, num_pages: int, page_tokens: int,
                 pool: Optional[str] = None):
        if page_tokens < 1:
            # the PR-8/PR-9 falsy-zero lesson: an explicit 0 must raise
            # here, never silently become some default upstream
            raise ValueError(
                f"page_tokens must be >= 1, got {page_tokens}")
        if num_pages < 2:
            raise ValueError(
                f"kv arena needs >= 2 pages (page 0 is reserved), "
                f"got {num_pages}")
        self.num_pages = num_pages
        self.page_tokens = page_tokens
        self._labels = {"pool": pool} if pool else None
        # LIFO free list: recently-freed pages are re-used first (their
        # content is dead by construction — cursors never read past a
        # slot's own writes)
        self._free: List[int] = list(range(num_pages - 1, 0, -1))
        # outstanding page ids: a double-free or foreign id is the one
        # bookkeeping slip that would hand the same physical page to two
        # slots (silent cross-sequence KV contamination) — fail LOUDLY
        # at the free site instead
        self._outstanding: set = set()
        self._allocated_total = 0
        self._freed_total = 0
        self._peak_in_use = 0

    @property
    def usable_pages(self) -> int:
        return self.num_pages - 1

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.usable_pages - len(self._free)

    def alloc(self, n: int) -> List[int]:
        """Allocate ``n`` pages or raise ``OutOfPagesError`` allocating
        NONE (no partial grants — the caller retries after eviction)."""
        if n <= 0:
            return []
        if len(self._free) < n:
            raise OutOfPagesError(
                f"kv arena out of pages: need {n}, "
                f"{len(self._free)} free of {self.usable_pages}")
        pages = [self._free.pop() for _ in range(n)]
        self._outstanding.update(pages)
        self._allocated_total += n
        self._peak_in_use = max(self._peak_in_use, self.pages_in_use)
        m_pages_allocated.inc(n, self._labels)
        m_pages_in_use.set(float(self.pages_in_use), self._labels)
        flight.instant(F_PAGE_ALLOC, n)
        return pages

    def free(self, pages: Collection[int]) -> None:
        for p in pages:
            if p == GARBAGE_PAGE:
                raise ValueError("page 0 is reserved and never allocated")
            if p not in self._outstanding:
                raise ValueError(
                    f"page {p} freed while not allocated (double-free or "
                    f"foreign id) — would alias two sequences' KV")
            self._outstanding.discard(p)
            self._free.append(p)
        if pages:
            self._freed_total += len(pages)
            m_pages_freed.inc(len(pages), self._labels)
            m_pages_in_use.set(float(self.pages_in_use), self._labels)

    def stats(self) -> Dict[str, int]:
        return {
            "num_pages": self.num_pages,
            "usable_pages": self.usable_pages,
            "pages_in_use": self.pages_in_use,
            "pages_free": len(self._free),
            "pages_allocated_total": self._allocated_total,
            "pages_freed_total": self._freed_total,
            "peak_pages_in_use": self._peak_in_use,
        }


class SlotPages:
    """What one seated sequence holds of one pool: the pages it must free,
    oldest first, and the logical pages its view covers (``fill``). Under a
    window the pages are the logical pages ``[fill - len(pages), fill)``;
    under the prefix cache the view may begin with shared pages. Made when
    the sequence takes its slot, whose rows are zero since ``free``."""

    __slots__ = ("pages", "fill")

    def __init__(self):
        self.pages: deque = deque()
        self.fill = 0


class PagePool:
    """One pool of pages as the scheduler drives it: an arena; every slot's
    host page tables (logical page j of slot s lives at physical page
    ``read[s, j]``; 0 is the garbage page: unallocated reads are causally
    masked, redirected writes are absorbed); the ``name`` its tables go
    under in a program's arguments (``decode.pool_of``); the ``window`` it
    keeps (None: everything; else a slot holds the pages its window still
    covers and no more, whatever the context); ``radix``, the prefix cache
    over this arena, asked for pages before a sequence fails (or None)."""

    def __init__(self, name: str, num_pages: int, page_tokens: int,
                 slots: int, pages_per_slot: int,
                 window: Optional[int] = None):
        import numpy as np

        self.name = name
        self.window = window
        self.label = "full" if window is None else "window"
        self.arena = PageArena(num_pages, page_tokens,
                               pool=None if window is None else self.label)
        self.radix: Optional["RadixCache"] = None
        self.read = np.zeros((slots, pages_per_slot), np.int32)
        self.write = np.zeros_like(self.read)
        self.released = 0  # pages given back from behind a window
        # logical pages in the seated slots' views, released ones among them
        self.filled = 0
        # tokens of the longest sequence the pool can hold alone: all its
        # pages', or, releasing behind a window, its table's whole range
        self.longest = page_tokens * (
            num_pages - 1 if window is None else pages_per_slot)

    def grow(self, slot: int, held: SlotPages, need: int,
             cursor: int) -> None:
        """Grow the slot's view to ``need`` logical pages. Under a window,
        first give back the pages wholly behind the window of the next row
        the slot's programs run (``cursor``: every program dispatched so far
        took COPIES of the tables, and the device runs them first); their
        entries point at the garbage page and the kernel's walk starts
        behind them. Raises ``OutOfPagesError`` as ``take`` does."""
        if self.window is not None:
            front = held.fill - len(held.pages)
            n = min(max(cursor - self.window + 1, 0)
                    // self.arena.page_tokens - front, len(held.pages))
            if n > 0:
                self.arena.free([held.pages.popleft() for _ in range(n)])
                self.read[slot, front:front + n] = 0
                self.write[slot, front:front + n] = 0
                self.released += n
                m_window_pages_released.inc(n)
                flight.instant(F_WINDOW_RELEASE, n)
        missing = need - held.fill
        if missing <= 0:
            return
        pages = self.take(missing)
        self.read[slot, held.fill:need] = pages
        self.write[slot, held.fill:need] = pages
        held.pages.extend(pages)
        self.filled += missing
        held.fill = need

    def take(self, n: int) -> List[int]:
        """``n`` pages of the arena, the prefix cache evicting LRU
        unreferenced nodes for them under pressure; or ``OutOfPagesError``
        (its message is a failed sequence's) and nothing allocated."""
        arena = self.arena
        if arena.free_pages < n and self.radix is not None:
            self.radix.evict(n - arena.free_pages)
        if arena.free_pages < n:
            said = (f"need {n} more, {arena.free_pages} free of "
                    f"{arena.usable_pages}")
            raise OutOfPagesError(
                f"kv arena out of pages ({said}; nothing evictable)"
                if self.window is None else
                f"window kv arena out of pages ({said})")
        return arena.alloc(n)

    def splice(self, slot: int, held: SlotPages, pages: List[int]) -> None:
        """Begin a fresh view with shared ``pages`` of the prefix cache:
        read entries only (shared pages are immutable: the write entries
        stay on the garbage page), and nothing the sequence must free."""
        self.read[slot, :len(pages)] = pages
        self.filled += len(pages)
        held.fill = len(pages)

    def share(self, slot: int, held: SlotPages, adopted: set) -> None:
        """The prefix cache adopted pages of the slot: the sequence no
        longer frees them and nothing writes them again."""
        held.pages = deque(p for p in held.pages if p not in adopted)
        row = self.write[slot]
        for j in range(held.fill):
            if int(row[j]) in adopted:
                row[j] = 0

    def free(self, slot: int, held: SlotPages) -> None:
        """A sequence leaves ``slot``: its pages back to the arena, its
        rows cleared (an inactive slot's write lands on the garbage page)."""
        self.arena.free(held.pages)
        held.pages.clear()
        self.filled -= held.fill
        held.fill = 0
        self.read[slot, :] = 0
        self.write[slot, :] = 0


def build_pools(cfg, *, slots: int, page_tokens: int, pages_per_slot: int,
                num_pages: int, prefill_chunk: int) -> Tuple[PagePool, ...]:
    """A model's pools, from its layers' kinds and the scheduler's sizes:
    none where no layer holds a page, else one a distinct
    ``decode.pool_of(kind)``, first the pool that keeps everything, of
    ``num_pages`` (``kv_pages`` means this pool). A pool with a window is
    sized by what a slot can hold of it at once — the window behind a
    chunk's first row, the chunk, and a page for where the two begin inside
    one — times ``slots``, plus the garbage page: no option sets it."""
    from ray_tpu.models.decode import pool_of
    from ray_tpu.models.transformer import holds_page

    pools: Dict[str, PagePool] = {}
    for kind in cfg.kinds:
        if not holds_page(kind) or pool_of(kind) in pools:
            continue
        window, pages = cfg.window(kind), num_pages
        if window is not None:
            pages = 1 + slots * min(
                pages_per_slot,
                -(-(window + prefill_chunk) // page_tokens) + 1)
        pools[pool_of(kind)] = PagePool(pool_of(kind), pages, page_tokens,
                                        slots, pages_per_slot, window)
    return tuple(sorted(pools.values(),
                        key=lambda pool: pool.window is not None))


def pool_tables(pools: Tuple[PagePool, ...], slot: Optional[int] = None):
    """(read, write) page tables for a program, of all slots or of one, as
    COPIES (the host frees and hands out pages while a program that took
    them is in flight): (None, None) for a model that holds no page (nothing
    is uploaded), two arrays for a model of one pool, else each of the two a
    dict by the pool's name (``decode.pool_tables`` picks a layer's)."""
    if not pools:
        return None, None
    rows = slice(None) if slot is None else slot
    pairs = [(pool.read[rows].copy(), pool.write[rows].copy())
             for pool in pools]
    if len(pools) == 1:
        return pairs[0]
    return tuple({pool.name: pair[i] for pool, pair in zip(pools, pairs)}
                 for i in range(2))


def pool_stats(pools: Tuple[PagePool, ...]) -> Dict[str, int]:
    """What ``stats()`` shows of a model's pools: the first pool's arena
    under the keys every scheduler has (for a model that holds no page the
    reserved page alone and a true 0 elsewhere); where there are two, each
    one's pages by its label; what a pool with a window released."""
    if not pools:
        return dict(num_pages=1, usable_pages=0, pages_in_use=0, pages_free=0,
                    pages_allocated_total=0, pages_freed_total=0,
                    peak_pages_in_use=0)
    out = pools[0].arena.stats()
    for pool in pools:
        if len(pools) > 1:
            arena = pool.arena.stats()
            out["kv_pages_in_use_" + pool.label] = arena["pages_in_use"]
            out["kv_peak_pages_in_use_" + pool.label] = arena[
                "peak_pages_in_use"]
        if pool.window is not None:
            out["window_pages_released"] = pool.released
    return out


# why a model's sequences cannot be continued from pages alone -> what would
# need that -> the message that refuses it
_REFUSALS = {"state": {
    "prefix_cache": (
        "prefix_cache=True cannot serve a model with layers that keep a "
        "state a slot ('lightning-attn', 'power-retention'): their state at "
        "a prefix's end is not kept"),
    "drafter": (
        "speculative decoding cannot serve a model with layers that keep a "
        "state a slot ('lightning-attn', 'power-retention'): a rejected "
        "draft would have to rewind their states"),
    "export": (
        "a model with layers that keep a state a slot exports no prefix: "
        "pages alone, if it holds any, do not continue a sequence"),
}, "window": {
    "prefix_cache": (
        "prefix_cache=True cannot serve a model with 'sliding_attention' "
        "layers: a spliced prefix would need their last window of it kept "
        "too, and they release it"),
    "drafter": (
        "speculative decoding cannot serve a model with 'sliding_attention' "
        "layers: a rejected draft would need the pages released behind it "
        "back"),
    "export": (
        "a model with 'sliding_attention' layers exports no prefix: the full "
        "layers' pages alone do not continue a sequence, and the window "
        "layers' are released behind the window"),
}, "index": {
    "drafter": (
        "speculative decoding cannot serve a model with 'indexed_attention' "
        "layers: the drafter's slot arena holds keys and values alone, and "
        "a verify window's rows have not been held against the reference "
        "for the kind"),
    "export": (
        "a model with 'indexed_attention' layers exports no prefix: an "
        "exported page carries its keys and values and not its index keys"),
}, "latent": {
    "drafter": (
        "speculative decoding cannot serve a model with 'latent_attention' "
        "layers: the drafter's slot arena holds keys and values alone, and "
        "a verify window's rows have not been held against the reference "
        "for the kind"),
    "export": (
        "a model with 'latent_attention' layers exports no prefix: its "
        "pages hold latents and rotated keys, and what is exported is keys "
        "and values"),
}, "indexed_latent": {
    "drafter": (
        "speculative decoding cannot serve a model with "
        "'indexed_latent_attention' layers: the drafter's slot arena holds "
        "keys and values alone, and a verify window's rows have not been "
        "held against the reference for the kind"),
    "export": (
        "a model with 'indexed_latent_attention' layers exports no prefix: "
        "its pages hold latents, rotated keys and index keys, and what is "
        "exported is keys and values"),
}, "loop": {
    "drafter": (
        "speculative decoding cannot serve a model with loop_passes > 1: "
        "the drafter's slot arena holds one (K, V) a layer and token, and "
        "a looped layer leaves one a PASS"),
}}


def cannot_continue(cfg, pools: Tuple[PagePool, ...]
                    ) -> Optional[Dict[str, str]]:
    """Why a sequence of this model cannot be continued from pages alone, as
    the message for each thing that would need it and cannot have it (of
    'prefix_cache', 'drafter', 'export'): a layer keeps a state a slot (what
    a token leaves in it cannot be cut at a page boundary or rewound, and no
    snapshot is kept), or a pool releases pages behind a window. An
    'indexed_attention' layer's index keys lie in its pages, under the same
    table: a spliced radix prefix brings them along, so the prefix cache
    serves it (tests/test_keye.py); what leaves the pool as keys and values
    alone does not. A 'latent_attention' layer's pages hold a latent and a
    rotated key a token and nothing else: the prefix cache splices them as
    it splices keys and values (tests/test_glm_moe_lite.py), and the two
    that count on keys and values are refused. An
    'indexed_latent_attention' layer's pages hold that row AND the token's
    index key under the one table: a spliced prefix brings both
    (tests/test_deepseek_v32.py), the other two are refused as for both
    parents. Plain 'attention' and
    'minicpm4' forbid nothing: None. A LOOPED model (``loop_passes``
    > 1) keeps keys and values alone, once a pass, all under the one table:
    a page that is spliced or exported carries every pass's
    (``decode.LoopPagedKVCache``), so only the drafter is refused."""
    if cfg.recurrent:
        return _REFUSALS["state"]
    if any(pool.window is not None for pool in pools):
        return _REFUSALS["window"]
    if "indexed_attention" in cfg.kinds:
        return _REFUSALS["index"]
    if "latent_attention" in cfg.kinds:
        return _REFUSALS["latent"]
    if "indexed_latent_attention" in cfg.kinds:
        return _REFUSALS["indexed_latent"]
    if cfg.looped:
        return _REFUSALS["loop"]
    return None


class _RadixNode:
    __slots__ = ("tokens", "pages", "children", "parent", "refs",
                 "last_used", "hashes")

    def __init__(self, tokens: Tuple[int, ...], pages: List[int],
                 parent: Optional["_RadixNode"],
                 hashes: Optional[List[int]] = None):
        self.tokens = tokens          # this EDGE's token span
        self.pages = pages            # pages backing exactly that span
        self.children: Dict[int, "_RadixNode"] = {}  # first-token -> child
        self.parent = parent
        self.refs = 0                 # live slots holding this node
        self.last_used = 0.0
        # per-page CHAIN hashes (affinity digest): hashes[i] commits to
        # the whole root path through this node's page i. Parallel to
        # ``pages``; splits slice it, never recompute it
        self.hashes: List[int] = hashes if hashes is not None else []

    def chain_end(self) -> int:
        """The chain value new children extend from."""
        return self.hashes[-1] if self.hashes else CHAIN_SEED


class RadixCache:
    """Radix tree over prompt prefixes; nodes own read-only pages.

    Every edge span is a whole number of pages (``page_tokens`` each), so
    matching, splitting and eviction all happen at page boundaries and a
    node's ``pages`` list is exactly parallel to its token span.

    Refcounting: ``match``/``insert`` return the deepest node on the path
    with ``refs`` already incremented; the caller MUST ``release`` it when
    the sequence retires. A node is evictable iff it is a leaf with
    refs == 0 (an ancestor of a referenced node has children, hence is
    not a leaf, hence is safe).
    """

    def __init__(self, arena: PageArena, clock=time.monotonic):
        self.arena = arena
        self.page_tokens = arena.page_tokens
        self._root = _RadixNode((), [], None)
        self._clock = clock
        self._hits = 0
        self._misses = 0
        self._evicted_pages = 0
        # affinity digest: count per chain hash (counts, not a set — two
        # sibling subtrees can't share a chain value, but a hash that
        # reappears after an evict/re-insert race must not flicker) and a
        # version stamp the long-poll channel keys on
        self._digest: Dict[int, int] = {}
        self._digest_version = 0

    # ------------------------------------------------------------ match

    def match(self, tokens: List[int]) -> Tuple[List[int], int,
                                                Optional[_RadixNode]]:
        """Longest cached page-aligned prefix of ``tokens``.

        Returns (pages, matched_len, node): the shared pages covering
        ``tokens[:matched_len]`` and the deepest node on the path
        (ref-counted — caller releases it at retire). A partial edge
        match splits the edge at the page boundary so the matched part
        becomes its own node. (None, for a zero-length match.)

        Match is metrics-free: the CALLER decides whether the match is
        actually spliced (it may clamp it away entirely) and records the
        hit/miss via ``note_hit``/``note_miss`` — so ``prefix_hits``
        counts avoided prefills, never discarded matches.
        """
        now = self._clock()
        node = self._root
        pages: List[int] = []
        matched = 0
        rest = tokens
        while rest:
            child, n = self._advance(node, rest, now)
            if n == 0:
                break
            pages.extend(child.pages)
            matched += n
            rest = rest[n:]
            node = child
        if node is self._root:
            return [], 0, None
        node.refs += 1
        return pages, matched, node

    def note_hit(self, matched_tokens: int) -> None:
        """Record an admission that spliced a cached prefix (call AFTER
        any clamping — only an avoided prefill counts)."""
        self._hits += 1
        m_prefix_hits.inc()
        flight.instant(F_PREFIX_HIT, matched_tokens)

    def note_miss(self) -> None:
        self._misses += 1
        m_prefix_misses.inc()

    def _advance(self, node: _RadixNode, rest: List[int], now: float
                 ) -> Tuple[Optional[_RadixNode], int]:
        """One descend step shared by ``match`` and ``insert``: find the
        child edge for ``rest``, page-align the shared length, split the
        edge at that boundary and stamp its LRU time. Returns (child, n):
        n == 0 means no child or a collision with no full shared page —
        in the latter case the node's LRU stamp is deliberately NOT
        refreshed (a stream of near-miss probes must not keep a never-hit
        node resident while genuinely reused nodes get evicted)."""
        child = node.children.get(rest[0])
        if child is None:
            return None, 0
        span = child.tokens
        n = 0
        limit = min(len(span), len(rest))
        while n < limit and span[n] == rest[n]:
            n += 1
        n = (n // self.page_tokens) * self.page_tokens
        if n == 0:
            return child, 0
        child.last_used = now
        if n < len(span):
            child = self._split(child, n)
            child.last_used = now
        return child, n

    def _split(self, node: _RadixNode, at: int) -> _RadixNode:
        """Split ``node``'s edge after ``at`` tokens (a page multiple);
        returns the new upper node. The lower half keeps the children and
        the refs (live slots reference the FULL path content)."""
        T = self.page_tokens
        upper = _RadixNode(tuple(node.tokens[:at]),
                           node.pages[: at // T], node.parent,
                           hashes=node.hashes[: at // T])
        upper.last_used = node.last_used
        node.parent.children[upper.tokens[0]] = upper
        lower_tokens = tuple(node.tokens[at:])
        node.tokens = lower_tokens
        node.pages = node.pages[at // T:]
        # chain hashes commit to the whole root path, so redistributing
        # them across the split needs no recompute — the digest set is
        # unchanged by a split
        node.hashes = node.hashes[at // T:]
        node.parent = upper
        upper.children[lower_tokens[0]] = node
        return upper

    # ----------------------------------------------------------- insert

    def insert(self, tokens: List[int], pages: List[int]
               ) -> Tuple[List[int], _RadixNode]:
        """Offer the pages backing ``tokens`` (page-aligned length) to the
        cache. Spans already cached keep their EXISTING pages; the novel
        suffix's pages are adopted by new nodes.

        Returns (duplicate_pages, node): the caller-owned pages NOT
        adopted (already covered — caller frees or keeps them) and the
        deepest node of the inserted path, ref-counted for the caller.
        """
        T = self.page_tokens
        if len(tokens) % T != 0 or len(tokens) // T != len(pages):
            raise ValueError(
                f"insert span must be page-aligned: {len(tokens)} tokens, "
                f"{len(pages)} pages, page_tokens={T}")
        now = self._clock()
        node = self._root
        rest = list(tokens)
        rest_pages = list(pages)
        duplicates: List[int] = []
        while rest:
            child, n = self._advance(node, rest, now)
            if child is None:
                new = _RadixNode(
                    tuple(rest), rest_pages, node,
                    hashes=chain_hashes(rest, T, seed=node.chain_end()))
                new.last_used = now
                node.children[rest[0]] = new
                self._digest_add(new.hashes)
                node = new
                rest, rest_pages = [], []
                break
            if n == 0:
                # same first token but no full shared page — token-level
                # divergence inside page 1 of the edge. The cache keeps
                # the incumbent; the new span is not representable at
                # page granularity alongside it
                duplicates.extend(rest_pages)
                rest, rest_pages = [], []
                break
            duplicates.extend(rest_pages[: n // T])
            rest = rest[n:]
            rest_pages = rest_pages[n // T:]
            node = child
        duplicates.extend(rest_pages)
        if node is self._root:
            return duplicates, None
        node.refs += 1
        return duplicates, node

    def release(self, node: Optional[_RadixNode]) -> None:
        if node is not None:
            if node.refs <= 0:
                raise RuntimeError("radix node released more times than "
                                   "matched")
            node.refs -= 1

    # ---------------------------------------------------------- evict

    def evict(self, need_pages: int) -> int:
        """Free LRU refcount-0 leaves until ``need_pages`` pages have been
        returned to the arena (or nothing evictable remains). Returns the
        number of pages actually freed.

        One tree scan collects ALL evictable leaves for the round (LRU
        order); only a cascade — a parent becoming a leaf as its subtree
        drains — triggers another scan, so the cost is O(nodes x depth)
        worst case instead of O(nodes x victims)."""
        freed = 0
        while freed < need_pages:
            candidates = []
            stack = [self._root]
            while stack:
                n = stack.pop()
                for c in n.children.values():
                    if not c.children and c.refs == 0:
                        candidates.append(c)
                    else:
                        stack.append(c)
            if not candidates:
                break
            candidates.sort(key=lambda c: c.last_used)
            for victim in candidates:
                if freed >= need_pages:
                    break
                victim.parent.children.pop(victim.tokens[0])
                self._digest_remove(victim.hashes)
                self.arena.free(victim.pages)
                freed += len(victim.pages)
                self._evicted_pages += len(victim.pages)
                flight.instant(F_EVICT, len(victim.pages))
        return freed

    def clear(self) -> int:
        """Drop every unreferenced node (shutdown / tests); still-referenced
        nodes survive. Returns pages freed."""
        return self.evict(1 << 30)

    # --------------------------------------------------------- digest

    def _digest_add(self, hashes: List[int]) -> None:
        for h in hashes:
            self._digest[h] = self._digest.get(h, 0) + 1
        if hashes:
            self._digest_version += 1

    def _digest_remove(self, hashes: List[int]) -> None:
        for h in hashes:
            n = self._digest.get(h, 0) - 1
            if n <= 0:
                self._digest.pop(h, None)
            else:
                self._digest[h] = n
        if hashes:
            self._digest_version += 1

    def digest(self) -> Dict:
        """Affinity digest snapshot (ISSUE 18): every page-boundary chain
        hash currently resident, plus a version stamp. Maintained
        incrementally by insert/evict/split — this is a dict-keys copy,
        safe to call from the stats path at poll rates. Callers that ship
        it off-process add tokenizer metadata (vocab_size / tok) so the
        router can hash prompts the same way."""
        return {
            "page_tokens": self.page_tokens,
            "hashes": list(self._digest.keys()),
            "version": self._digest_version,
        }

    # ---------------------------------------------------------- stats

    def _walk_totals(self) -> Tuple[int, int, int]:
        """(nodes, resident_pages, active_refs) in ONE tree traversal —
        stats() is polled in tight loops by chaos baselines and benches."""
        nodes, pages, refs = -1, 0, 0  # -1: exclude the root sentinel
        stack = [self._root]
        while stack:
            n = stack.pop()
            nodes += 1
            pages += len(n.pages)
            refs += n.refs
            stack.extend(n.children.values())
        return nodes, pages, refs

    def resident_pages(self) -> int:
        return self._walk_totals()[1]

    def active_refs(self) -> int:
        return self._walk_totals()[2]

    def node_count(self) -> int:
        return self._walk_totals()[0]

    def stats(self) -> Dict[str, int]:
        hits, misses = self._hits, self._misses
        nodes, pages, refs = self._walk_totals()
        return {
            "prefix_hits": hits,
            "prefix_misses": misses,
            "prefix_hit_rate": round(hits / max(hits + misses, 1), 4),
            "radix_nodes": nodes,
            "radix_resident_pages": pages,
            "radix_active_refs": refs,
            "evicted_pages_total": self._evicted_pages,
        }
