"""Paged KV arena + prefix/radix caching for the continuous-batching serve
path (ISSUE 13; ROADMAP item 3).

Covers: the page allocator and radix tree units (insert/match/refcount/
evict, partial-prefix splice at page boundaries), temperature-0 parity of
the paged scheduler against the sequential single-request reference
under mixed lengths + slot/page reuse, the
~10x-concurrency admission contract at fixed arena bytes, the two-compiles
guard (compile counter unchanged across mixed paged workloads — shape
churn would show up here), loud rejection of falsy-zero knobs and
over-budget prompts (before any page is allocated), LRU eviction under
arena pressure, and cancel-mid-stream leaving the prefix cache clean for
a later admit of the same prefix.
"""

import asyncio
import time

import pytest

from ray_tpu.serve._private.paging import (OutOfPagesError, PageArena,
                                           PagePool, RadixCache, SlotPages,
                                           build_pools, cannot_continue,
                                           pool_stats, pool_tables)
from tests.model_harness import sequential_text

SLOTS = 4
CHUNK = 8
PAGE = 8
NEW = 6

PROMPTS = ["hi", "hello 123", "a much longer prompt than the others!"]


# ------------------------------------------------------------- allocator


class TestPageArena:
    def test_alloc_free_roundtrip_and_reserved_garbage_page(self):
        a = PageArena(num_pages=5, page_tokens=8)
        assert a.usable_pages == 4
        pages = a.alloc(3)
        assert len(pages) == 3 and 0 not in pages
        assert a.pages_in_use == 3
        a.free(pages)
        assert a.pages_in_use == 0
        with pytest.raises(ValueError, match="reserved"):
            a.free([0])

    def test_exhaustion_grants_nothing_partially(self):
        a = PageArena(num_pages=4, page_tokens=8)
        a.alloc(2)
        with pytest.raises(OutOfPagesError):
            a.alloc(2)  # only 1 free
        assert a.free_pages == 1, "failed alloc must not leak a partial grant"

    def test_zero_page_tokens_rejected(self):
        with pytest.raises(ValueError, match="page_tokens"):
            PageArena(num_pages=8, page_tokens=0)

    def test_degenerate_pool_rejected(self):
        with pytest.raises(ValueError, match="pages"):
            PageArena(num_pages=1, page_tokens=8)

    def test_stats_counters(self):
        a = PageArena(num_pages=6, page_tokens=4)
        p = a.alloc(4)
        a.free(p[:2])
        st = a.stats()
        assert st["pages_allocated_total"] == 4
        assert st["pages_freed_total"] == 2
        assert st["pages_in_use"] == 2
        assert st["peak_pages_in_use"] == 4


# ------------------------------------------------------------- the pools


class TestPagePool:
    """One pool as the scheduler drives it: a slot's view grown a
    chunk and then a step at a time, and freed."""

    T, C, W = 4, 16, 24  # page tokens, prefill chunk, window

    def _walk(self, pool, slot, length):
        """A sequence of ``length`` tokens through ``slot``: chunks of C,
        then steps; yields (cursor, held) behind every growth."""
        held = SlotPages()
        cursor = 0
        while cursor < length:
            real = min(self.C, length - cursor) if cursor < 64 else 1
            pool.grow(slot, held, -(-(cursor + real) // self.T), cursor)
            yield cursor, held
            cursor += real
        pool.free(slot, held)

    def test_a_window_bounds_what_a_slot_holds(self):
        bound = -(-(self.W + self.C) // self.T) + 1
        pool = PagePool("w", 1 + 2 * bound, self.T, slots=2,
                        pages_per_slot=64, window=self.W)
        other = SlotPages()
        pool.grow(1, other, 3, 0)
        for cursor, held in self._walk(pool, 0, 250):
            assert len(held.pages) <= bound
            view = pool.read[0]
            first = max(cursor - self.W + 1, 0) // self.T
            # released entries point at the garbage page, held ones do not,
            # and every position the window lets in is on a held page
            assert not view[:held.fill - len(held.pages)].any()
            assert list(view[first:held.fill]) == list(held.pages)[
                first - (held.fill - len(held.pages)):]
            assert (pool.write[0] == view).all()
            assert pool.arena.pages_in_use == len(held.pages) + 3
            last = len(held.pages)
        assert pool.released == -(-250 // self.T) - last > 40
        # what was released and what was freed is back: the other slot's
        # three pages are all the arena misses
        assert pool.arena.pages_in_use == 3 and pool.filled == 3
        assert not pool.read[0].any() and not pool.write[0].any()
        assert pool.longest == 64 * self.T

    def test_without_a_window_nothing_is_released(self):
        pool = PagePool("full", 1 + 64, self.T, slots=2, pages_per_slot=64)
        for cursor, held in self._walk(pool, 1, 250):
            assert held.fill == len(held.pages) == pool.arena.pages_in_use
            assert list(pool.read[1, :held.fill]) == list(held.pages)
        assert pool.released == 0 and pool.arena.pages_in_use == 0
        assert pool.longest == 64 * self.T
        read, write = pool_tables((pool,), 1)
        read[:] = 7  # copies: a program in flight keeps what it took
        assert not pool.read.any() and read.shape == (64,)
        assert pool_tables((pool,))[0].shape == (2, 64)

    @pytest.mark.parametrize("window,said", [
        (None, r"^kv arena out of pages \(need 2 more, 1 free of 4; "
               r"nothing evictable\)$"),
        (8, r"^window kv arena out of pages \(need 2 more, 1 free of 4\)$"),
    ])
    def test_out_of_pages_is_the_sequences_message(self, window, said):
        pool = PagePool("p", 5, self.T, slots=2, pages_per_slot=8,
                        window=window)
        a, b = SlotPages(), SlotPages()
        pool.grow(0, a, 3, 0)
        with pytest.raises(OutOfPagesError, match=said):
            pool.grow(1, b, 2, 0)
        assert b.fill == 0 and pool.arena.free_pages == 1  # nothing granted
        pool.free(0, a)
        pool.grow(1, b, 2, 0)

    def test_the_prefix_cache_is_asked_before_a_sequence_fails(self):
        pool = PagePool("full", 5, self.T, slots=2, pages_per_slot=8)
        pool.radix = RadixCache(pool.arena)
        a = SlotPages()
        pool.grow(0, a, 3, 0)
        offered = [int(x) for x in pool.read[0, :2]]
        dups, node = pool.radix.insert(list(range(8)), offered)
        pool.share(0, a, set(offered) - set(dups))
        assert len(a.pages) == 1 and not pool.write[0, :2].any()
        pool.radix.release(node)
        pool.free(0, a)
        assert pool.arena.pages_in_use == 2  # the cache's
        b = SlotPages()
        pool.splice(1, b, offered)
        assert b.fill == 2 and not b.pages and not pool.write[1].any()
        pool.free(1, b)
        pool.grow(0, SlotPages(), 4, 0)  # evicts the cached span
        assert pool.arena.pages_in_use == 4

    def test_a_models_pools_follow_its_layers(self):
        from ray_tpu.models import brumby_debug, llama_debug, mellum_debug

        sizes = dict(slots=3, page_tokens=4, pages_per_slot=32, num_pages=50,
                     prefill_chunk=16)
        none = build_pools(brumby_debug(), **sizes)
        assert none == () and pool_tables(none) == (None, None)
        assert pool_stats(none)["num_pages"] == 1
        assert not any(v for k, v in pool_stats(none).items()
                       if k != "num_pages")
        assert cannot_continue(brumby_debug(), none)["export"].startswith(
            "a model with layers that keep a state a slot exports no prefix")
        (one,) = build_pools(llama_debug(), **sizes)
        assert one.window is None and one.arena.num_pages == 50
        assert [t.shape for t in pool_tables((one,), 2)] == [(32,), (32,)]
        assert cannot_continue(llama_debug(), (one,)) is None
        assert "window_pages_released" not in pool_stats((one,))
        # the window layers come first in the model; their pool second
        full, window = pools = build_pools(mellum_debug(), **sizes)
        assert (full.window, window.window) == (None, 24)
        assert window.arena.num_pages == 1 + 3 * 11
        read, write = pool_tables(pools)
        assert sorted(read) == sorted(write) == [
            "attention", "sliding_attention"]
        assert {"kv_pages_in_use_full", "kv_peak_pages_in_use_window",
                "window_pages_released"} <= pool_stats(pools).keys()
        assert "'sliding_attention'" in cannot_continue(
            mellum_debug(), pools)["drafter"]


def test_the_scheduler_names_no_layer_kind():
    """The seam: what a model's layers hold, forbid and do is behind
    ``paging`` and ``work``. ``continuous.py`` imports no kind and nothing
    that counts one, reads no kind off a config, and keeps nothing under a
    kind's name (the code as parsed: its docstrings may say what they like)."""
    import ast
    import inspect

    from ray_tpu.serve._private import continuous

    tree = ast.parse(inspect.getsource(continuous))
    imported = {alias.name for node in ast.walk(tree)
                if isinstance(node, (ast.Import, ast.ImportFrom))
                for alias in node.names}
    assert not imported & {
        "ATTENTION", "SPARSE", "LINEAR", "RETENTION", "SLIDING",
        "STATE_KINDS", "state_shapes", "pool_of", "tile_sizes",
        "streamed_tokens"}
    names = {node.attr if isinstance(node, ast.Attribute) else
             node.id if isinstance(node, ast.Name) else node.arg
             for node in ast.walk(tree)
             if isinstance(node, (ast.Attribute, ast.Name, ast.arg))}
    assert not names & {"kinds", "recurrent", "holds_pages"}
    assert not [name for name in names if any(
        part in name for part in ("_window", "sliding", "sparse", "linear",
                                  "retention", "_n_moe"))]


# ------------------------------------------------------------ radix tree


def _mk(page_tokens=4, num_pages=64):
    arena = PageArena(num_pages, page_tokens)
    return arena, RadixCache(arena)


class TestRadixCache:
    def test_insert_then_match_full_and_partial(self):
        arena, rc = _mk(page_tokens=4)
        toks = list(range(100, 112))  # 12 tokens = 3 pages
        pages = arena.alloc(3)
        dups, node = rc.insert(toks, pages)
        assert dups == [] and node is not None
        rc.release(node)

        got, matched, n2 = rc.match(toks)
        assert matched == 12 and got == pages
        rc.release(n2)
        # partial: only the first 5 tokens shared -> one full page
        got, matched, n3 = rc.match(toks[:5] + [999] * 7)
        assert matched == 4 and got == pages[:1]
        rc.release(n3)

    def test_partial_match_splits_edge_at_page_boundary(self):
        arena, rc = _mk(page_tokens=4)
        toks = list(range(100, 112))
        pages = arena.alloc(3)
        _, node = rc.insert(toks, pages)
        rc.release(node)
        # a 8-token match forces a split: [0:8) upper node + [8:12) lower
        got, matched, n = rc.match(toks[:8] + [7, 7, 7, 7])
        assert matched == 8 and got == pages[:2]
        assert rc.node_count() == 2
        # the lower node kept its pages; the full path still matches
        rc.release(n)
        got, matched, n2 = rc.match(toks)
        assert matched == 12 and got == pages
        rc.release(n2)

    def test_divergence_inside_first_page_is_a_miss(self):
        arena, rc = _mk(page_tokens=4)
        pages = arena.alloc(1)
        _, node = rc.insert([1, 2, 3, 4], pages)
        rc.release(node)
        got, matched, n = rc.match([1, 2, 9, 9, 9])
        assert matched == 0 and got == [] and n is None

    def test_overlapping_insert_returns_duplicates(self):
        arena, rc = _mk(page_tokens=4)
        toks = list(range(50, 58))  # 2 pages
        first = arena.alloc(2)
        _, n1 = rc.insert(toks, first)
        # second sequence prefilled the same span into ITS OWN pages plus
        # a novel page; the cache keeps the incumbent and adopts the tail
        mine = arena.alloc(3)
        dups, n2 = rc.insert(toks + [60, 61, 62, 63], mine)
        assert dups == mine[:2], "overlapping span pages must come back"
        assert rc.resident_pages() == 3  # incumbent 2 + adopted 1
        rc.release(n1)
        rc.release(n2)

    def test_refcount_blocks_eviction_until_release(self):
        arena, rc = _mk(page_tokens=4, num_pages=8)
        pages = arena.alloc(2)
        _, node = rc.insert([1, 2, 3, 4, 5, 6, 7, 8], pages)
        assert rc.evict(10) == 0, "a referenced leaf must never be evicted"
        rc.release(node)
        assert rc.evict(10) == 2
        assert arena.pages_in_use == 0

    def test_eviction_is_lru_leaf_first(self):
        clock = {"t": 0.0}
        arena = PageArena(64, 4)
        rc = RadixCache(arena, clock=lambda: clock["t"])
        spans = {}
        for i, base in enumerate((100, 200, 300)):
            clock["t"] = float(i)
            toks = [base + j for j in range(4)]
            pages = arena.alloc(1)
            _, node = rc.insert(toks, pages)
            rc.release(node)
            spans[base] = (toks, pages)
        clock["t"] = 10.0
        _, _, n = rc.match(spans[100][0])  # 100 becomes most recent
        rc.release(n)
        assert rc.evict(1) == 1
        # 200 was least recently used -> gone; 100 and 300 still cached
        assert rc.match(spans[200][0])[1] == 0
        got, matched, n = rc.match(spans[100][0])
        assert matched == 4
        rc.release(n)

    def test_parent_becomes_evictable_after_children_drain(self):
        arena, rc = _mk(page_tokens=4)
        shared = list(range(10, 14))
        p0 = arena.alloc(1)
        _, n0 = rc.insert(shared, p0)
        rc.release(n0)
        p1 = arena.alloc(1)
        _, n1 = rc.insert(shared + [1, 1, 1, 1], p0 + p1)
        rc.release(n1)
        p2 = arena.alloc(1)
        _, n2 = rc.insert(shared + [2, 2, 2, 2], p0 + p2)
        rc.release(n2)
        assert rc.node_count() == 3
        assert rc.evict(1 << 30) == 3
        assert rc.node_count() == 0 and arena.pages_in_use == 0

    def test_release_underflow_raises(self):
        arena, rc = _mk()
        pages = arena.alloc(1)
        _, node = rc.insert([1, 2, 3, 4], pages)
        rc.release(node)
        with pytest.raises(RuntimeError, match="released"):
            rc.release(node)


# --------------------------------------------------------------- parity


@pytest.fixture(scope="module")
def server():
    from ray_tpu.serve.llm import LLMServerImpl

    srv = LLMServerImpl(max_new_tokens=NEW, slots=SLOTS, prefill_chunk=CHUNK,
                        page_tokens=PAGE, share_weights=False)
    yield srv
    srv.shutdown()


def _sequential_reference(srv, prompt: str, new_tokens: int = NEW):
    return sequential_text(srv, prompt, new_tokens)


class TestPagedParity:
    def test_mixed_lengths_prefix_reuse_matches_sequential(self, server):
        """The acceptance bar: a prefix-cache hit must be bit-identical to
        a cold prefill of the same tokens, under mixed lengths, chunked
        prefill, slot reuse AND page reuse. Repeats of each prompt force
        hits (stats-asserted); every output must equal the sequential
        single-request reference exactly. The scheduler issues zero
        control-plane RPCs throughout (counter-asserted)."""
        from ray_tpu._private.rpc import _m_client_calls

        refs = {p: _sequential_reference(server, p) for p in PROMPTS}
        rpc0 = _m_client_calls.total()

        async def drive():
            reqs = [{"prompt": p} for p in PROMPTS * 4]  # > SLOTS: queues
            return await asyncio.gather(*[server(r) for r in reqs])

        outs = asyncio.run(drive())
        assert _m_client_calls.total() == rpc0, \
            "the paged scheduler issued control-plane RPCs"
        for o in outs:
            assert o["text"] == refs[o["prompt"]], \
                f"paged output diverged for {o['prompt']!r}"
        st = server.scheduler_stats()
        assert st["prefix_hits"] > 0, "repeats never hit the radix cache"
        assert st["admitted_mid_flight"] > 0
        assert st["max_active_slots"] >= 2

    def test_two_compiles_contract_across_mixed_paged_workloads(
            self, server):
        """The house invariant PR 9 established, preserved under paging:
        after mixed prompt lengths, prefix hits, misses, evictions and
        page churn, the scheduler has compiled exactly TWO programs (one
        [1, chunk] prefill + one [slots] decode)."""
        st = server.scheduler_stats()
        assert st["prefill_chunks"] > 0 and st["decode_steps"] > 0
        assert st["compiled_programs"] == 2, st["compiled_programs"]


# ------------------------------------------------------------- capacity


class TestPagedCapacity:
    def test_concurrency_multiplier_at_fixed_arena_bytes(self):
        """The memory lever: at the SAME pool bytes the contiguous layout
        reserves worst-case `arena_len` per slot — this pool holds exactly
        2 such slots — while the paged scheduler DECODES >= 10 short
        sequences on it simultaneously (>= 5x, the acceptance bar), each
        using only the pages its actual length needs."""
        from ray_tpu.serve.llm import LLMServerImpl

        arena_len = 128
        page = 4
        contiguous_equivalent_slots = 2
        pool_pages = contiguous_equivalent_slots * (arena_len // page) + 1
        new_tokens = 13  # decode window must outlast one-prefill-per-iter
        srv = LLMServerImpl(max_new_tokens=new_tokens, slots=12,
                            prefill_chunk=4, page_tokens=page,
                            arena_len=arena_len, kv_pages=pool_pages,
                            prefix_cache=False, share_weights=False)
        try:
            ref = _sequential_reference(srv, "hi", new_tokens)

            async def drive():
                return await asyncio.gather(*[
                    srv({"prompt": "hi"}) for _ in range(12)])

            outs = asyncio.run(drive())
            assert all(o["text"] == ref for o in outs)
            st = srv.scheduler_stats()
            assert st["max_active_slots"] >= \
                5 * contiguous_equivalent_slots, st
            # each sequence held 4 pages (16 tokens), not a 128-token slot
            assert st["peak_pages_in_use"] <= 12 * 4, st
            assert st["pages_in_use"] == 0  # everything retired clean
        finally:
            srv.shutdown()


# ----------------------------------------------------------------- knobs


class TestKnobValidation:
    def _cfg(self):
        class _Cfg:  # never reaches jit — validation fires first
            max_seq_len = 128
        return _Cfg()

    def test_explicit_zero_page_tokens_rejected(self):
        from ray_tpu.serve._private.continuous import ContinuousScheduler

        with pytest.raises(ValueError, match="page_tokens"):
            ContinuousScheduler(self._cfg(), None, page_tokens=0)

    def test_env_zero_page_tokens_rejected(self, monkeypatch):
        """RAY_TPU_SERVE_PAGE_TOKENS=0 must raise at build — the config
        default must not resurrect through a falsy-zero `or` chain."""
        import ray_tpu._private.config as config_mod
        from ray_tpu._private.config import Config
        from ray_tpu.serve._private.continuous import ContinuousScheduler

        monkeypatch.setenv("RAY_TPU_SERVE_PAGE_TOKENS", "0")
        monkeypatch.setattr(config_mod, "_global_config",
                            Config.from_env(), raising=False)
        try:
            with pytest.raises(ValueError, match="page_tokens"):
                ContinuousScheduler(self._cfg(), None)
        finally:
            monkeypatch.setattr(config_mod, "_global_config", None,
                                raising=False)

    def test_misaligned_arena_rejected(self):
        from ray_tpu.serve._private.continuous import ContinuousScheduler

        with pytest.raises(ValueError, match="multiple"):
            ContinuousScheduler(self._cfg(), None, arena_len=100,
                                page_tokens=16)

    def test_negative_kv_pages_rejected(self):
        from ray_tpu.serve._private.continuous import ContinuousScheduler

        with pytest.raises(ValueError, match="kv_pages"):
            ContinuousScheduler(self._cfg(), None, kv_pages=-1)

    def test_over_budget_prompt_rejected_before_any_page_allocated(self):
        """Admission is page-aware: a prompt whose prompt+budget can never
        fit the pool fails at submit() — with the allocation counter
        proving no page was ever handed out for it."""
        from ray_tpu.serve.llm import LLMServerImpl

        srv = LLMServerImpl(max_new_tokens=4, slots=4, prefill_chunk=CHUNK,
                            page_tokens=PAGE, arena_len=64,
                            kv_pages=5,  # 4 usable pages = 32 tokens
                            prefix_cache=False, share_weights=False)
        try:
            with pytest.raises(Exception, match="arena"):
                asyncio.run(srv({"prompt": "x" * 40}))
            st = srv.scheduler_stats()
            assert st["pages_allocated_total"] == 0, st
            # and a fitting prompt still works
            out = asyncio.run(srv({"prompt": "hello 123", "max_new_tokens": 2}))
            assert out["num_tokens"] == 2
        finally:
            srv.shutdown()


# -------------------------------------------------------------- eviction


class TestEvictionAndCancel:
    def test_arena_pressure_evicts_lru_and_stays_correct(self):
        """A pool too small to cache every distinct prompt forces LRU
        eviction of refcount-0 nodes; evicted prefixes simply re-prefill
        (miss), and outputs stay exact throughout."""
        from ray_tpu.serve.llm import LLMServerImpl

        srv = LLMServerImpl(max_new_tokens=4, slots=2, prefill_chunk=CHUNK,
                            page_tokens=PAGE, arena_len=64,
                            kv_pages=2 * (64 // PAGE) + 1,
                            share_weights=False)
        try:
            # distinct from byte 0 so no page is shared between prompts —
            # each caches its own full pages and the pool must churn
            prompts = [f"{i} unique preamble body tail xx" for i in range(6)]
            refs = {p: _sequential_reference(srv, p, 4) for p in prompts}

            async def drive():
                outs = []
                for p in prompts:       # sequentially: maximal cache churn
                    outs.append(await srv({"prompt": p}))
                outs += await asyncio.gather(*[
                    srv({"prompt": p}) for p in prompts])
                return outs

            outs = asyncio.run(drive())
            for o in outs:
                assert o["text"] == refs[o["prompt"]], \
                    f"eviction corrupted {o['prompt']!r}"
            st = srv.scheduler_stats()
            assert st["evicted_pages_total"] > 0, \
                f"pool never came under pressure: {st}"
            assert st["pages_in_use"] == st["radix_resident_pages"]
            assert st["radix_active_refs"] == 0
        finally:
            srv.shutdown()

    def test_cancel_mid_stream_keeps_prefix_cache_clean(self):
        """A cancelled stream retires its pages; a later admit that hits
        the SAME cached prefix must decode exactly the sequential
        reference (no contamination through shared pages)."""
        from ray_tpu.serve.llm import LLMServerImpl

        srv = LLMServerImpl(max_new_tokens=NEW, slots=2, prefill_chunk=CHUNK,
                            page_tokens=PAGE, share_weights=False)
        try:
            prompt = "a much longer prompt than the others!"
            ref = _sequential_reference(srv, prompt)

            async def drive():
                gen = await srv({"prompt": prompt, "stream": True,
                                 "max_new_tokens": 64})
                it = gen.__aiter__()
                await it.__anext__()
                await it.__anext__()
                await gen.aclose()  # walk away mid-decode
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline:
                    if srv.scheduler_stats()["active_slots"] == 0:
                        break
                    await asyncio.sleep(0.05)
                st = srv.scheduler_stats()
                assert st["active_slots"] == 0, st
                assert st["radix_active_refs"] == 0, st
                hits0 = st["prefix_hits"]
                out = await srv({"prompt": prompt})
                return out, hits0

            out, hits0 = asyncio.run(drive())
            assert out["text"] == ref
            st = srv.scheduler_stats()
            assert st["prefix_hits"] > hits0, \
                "re-admit after cancel never hit the cached prefix"
        finally:
            srv.shutdown()
