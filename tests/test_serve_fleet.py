"""Fleet serving phase 2 (ISSUE 18): prefix-affinity routing, cross-
replica page migration, and speculative decoding.

Three independent mechanisms share this suite because they share one
contract: none of them may change WHAT a request decodes, only WHERE and
HOW FAST. Affinity picks the replica, migration moves KV pages between
radix caches, speculation reorders the arithmetic — temperature-0 output
must stay bit-identical to the sequential reference through all of them,
and a failed migration must degrade to a cold prefill with the same
tokens.

The end-to-end fleet path (4 replicas through the real control plane)
is exercised by `chaos_soak --fleet`; this
suite covers the in-process contracts: chain-hash/digest construction,
router steering + skew/fail fallback + hint injection, the migration
splice's refcount/eviction hygiene, speculative parity and acceptance
statistics, the two-compiles guard, knob validation, and the zero-RPC
re-proof with every fleet feature on.
"""

import asyncio
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.serve._private.affinity import (CHAIN_SEED, AffinityIndex,
                                             chain_hashes, extend_chain,
                                             prompt_chain)
from ray_tpu.serve._private.paging import PageArena, RadixCache
from ray_tpu.serve._private.speculative import (_softmax, accept_greedy,
                                                accept_sample)
from ray_tpu.serve.llm import LLMServerImpl
from tests.model_harness import sequential_text as _sequential_reference

SLOTS = 4
CHUNK = 8
NEW = 6

PROMPTS = ["hi", "hello 123", "a much longer prompt than the others!"]


# ------------------------------------------------------------ chain hash


class TestChainHash:
    def test_chain_commits_to_whole_prefix(self):
        """h_i must change when ANY earlier page changes — membership of
        h_i alone is a full prefix comparison, the property steering
        relies on."""
        a = chain_hashes([1, 2, 3, 4, 5, 6, 7, 8], 4)
        b = chain_hashes([9, 2, 3, 4, 5, 6, 7, 8], 4)  # page 0 differs
        assert len(a) == len(b) == 2
        assert a[0] != b[0]
        assert a[1] != b[1]  # later hash diverges through the chain

    def test_partial_page_dropped(self):
        assert chain_hashes([1, 2, 3], 4) == []
        assert chain_hashes([1, 2, 3, 4, 5], 4) == chain_hashes(
            [1, 2, 3, 4], 4)

    def test_incremental_equals_batch(self):
        toks = list(range(12))
        h = CHAIN_SEED
        inc = []
        for i in range(0, 12, 4):
            h = extend_chain(h, toks[i:i + 4])
            inc.append(h)
        assert inc == chain_hashes(toks, 4)

    def test_prompt_chain_clips_last_token(self):
        """Admission caches prompt[:-1] (the last token's KV is written by
        sampling) — the router must hash the same clipped span or it
        steers on pages no replica can hold."""
        toks = list(range(9))
        assert prompt_chain(toks, 4) == chain_hashes(toks[:-1], 4)

    def test_page_tokens_validated(self):
        with pytest.raises(ValueError):
            chain_hashes([1, 2], 0)


class TestAffinityIndex:
    def _payload(self, key, toks, pt=4, version=1):
        return {"version": version,
                "digests": {key: {"page_tokens": pt, "vocab_size": 256,
                                  "tok": "byte",
                                  "hashes": chain_hashes(toks, pt)}}}

    def test_steer_picks_deepest_match(self):
        idx = AffinityIndex()
        toks = list(range(16))
        shallow = self._payload("a", toks[:8])["digests"]["a"]
        deep = self._payload("b", toks)["digests"]["b"]
        idx.update({"version": 2, "digests": {"a": shallow, "b": deep}})
        chain = chain_hashes(toks, 4)
        key, depth = idx.steer(chain, ["a", "b"])
        assert (key, depth) == ("b", 4)
        # replica set restriction: an absent holder can't be steered to
        key, depth = idx.steer(chain, ["a"])
        assert (key, depth) == ("a", 2)

    def test_no_match_returns_none(self):
        idx = AffinityIndex()
        idx.update(self._payload("a", list(range(8))))
        assert idx.steer(chain_hashes([99] * 8, 4), ["a"]) == (None, 0)

    def test_byte_tokenizer_reproduced(self):
        idx = AffinityIndex()
        idx.update(self._payload("a", [1, 2, 3, 4]))
        ids = idx.tokenize("hello")
        assert ids == [b % 256 for b in b"hello"]
        # opaque tokenizer: unroutable without explicit prompt_ids
        p = self._payload("a", [1, 2, 3, 4])
        p["digests"]["a"]["tok"] = "opaque"
        idx2 = AffinityIndex()
        idx2.update(p)
        assert idx2.tokenize("hello") is None
        assert idx2.chain_for("hello") == []
        assert idx2.chain_for(prompt_ids=list(range(9))) != []

    def test_not_ready_before_any_digest(self):
        idx = AffinityIndex()
        assert not idx.ready()
        assert idx.chain_for("anything") == []


# ---------------------------------------------------- radix cache digest


class TestRadixDigest:
    def _tree_hashes(self, radix):
        """Recompute the digest from a full tree walk (the thing the
        incremental bookkeeping must always equal)."""
        out = []
        stack = [radix._root]
        while stack:
            n = stack.pop()
            out.extend(n.hashes)
            stack.extend(n.children.values())
        return sorted(out)

    def test_digest_tracks_insert_split_evict(self):
        arena = PageArena(num_pages=32, page_tokens=4)
        radix = RadixCache(arena)
        assert radix.digest()["hashes"] == []

        t1 = list(range(16))
        dup, n1 = radix.insert(t1, arena.alloc(4))
        assert dup == []
        v1 = radix.digest()["version"]
        assert sorted(radix.digest()["hashes"]) == self._tree_hashes(radix)
        assert len(radix.digest()["hashes"]) == 4

        # divergent suffix after 8 shared tokens -> edge split; the split
        # must preserve the digest set (hashes commit to the root path)
        t2 = t1[:8] + [90, 91, 92, 93]
        dup2, n2 = radix.insert(t2, arena.alloc(3))
        assert len(dup2) == 2  # the shared 2 pages were already cached
        arena.free(dup2)
        d = radix.digest()
        assert sorted(d["hashes"]) == self._tree_hashes(radix)
        assert len(d["hashes"]) == 5  # 4 original + 1 divergent page
        assert d["version"] > v1

        # eviction unregisters exactly the evicted spans
        radix.release(n1)
        radix.release(n2)
        radix.evict(1 << 30)
        d2 = radix.digest()
        assert d2["hashes"] == []
        assert d2["version"] > d["version"]
        assert arena.pages_in_use == 0

    def test_match_probe_does_not_change_digest(self):
        arena = PageArena(num_pages=16, page_tokens=4)
        radix = RadixCache(arena)
        _, node = radix.insert(list(range(8)), arena.alloc(2))
        v = radix.digest()["version"]
        pages, matched, m = radix.match(list(range(8)) + [7, 7, 7, 7])
        assert matched == 8
        assert radix.digest()["version"] == v
        radix.release(node)
        radix.release(m)


# ------------------------------------------------------- router steering


class _Aid:
    def __init__(self, h):
        self._h = h

    def hex(self):
        return self._h


class _Rep:
    def __init__(self, h):
        self._actor_id = _Aid(h)


def _router(keys=("a", "b", "c")):
    """A Router with its replica set installed directly — steering and
    fallback logic are pure functions of this state; no control plane."""
    from ray_tpu.serve._private.router import Router

    r = Router(controller=None, app_name="t", deployment_name="t")
    # no control plane in these units: pin the poll-thread slots so
    # _affinity_chain never spawns a loop against the None controller
    r._digest_thread = threading.current_thread()
    r._poll_thread = threading.current_thread()
    r._replicas = [_Rep(k) for k in keys]
    r._key_to_idx = {k: i for i, k in enumerate(keys)}
    r._inflight = {i: 0 for i in range(len(keys))}
    r._version = 1
    return r


def _install_digest(r, key, toks, pt=4):
    r._affinity.update({
        "version": 1,
        "digests": {key: {"page_tokens": pt, "vocab_size": 256,
                          "tok": "byte", "hashes": chain_hashes(toks, pt)}}})


class TestRouterSteering:
    def test_steers_to_holder(self):
        r = _router()
        toks = list(range(16))
        _install_digest(r, "b", toks)
        chain = chain_hashes(toks, 4)
        for _ in range(8):
            idx, rep, hint = r._pick(chain=chain)
            assert idx == 1 and hint is None
            r._inflight[idx] -= 1  # request completes before the next pick
        r._inflight = {0: 0, 1: 0, 2: 0}
        # without completions, steering saturates at the skew bound and
        # hotspot protection kicks in — that's the next test's subject,
        # but the first `skew` picks must still steer
        for i in range(r._affinity_skew + 1):
            idx, rep, hint = r._pick(chain=chain)
            assert idx == 1 and hint is None
        assert r._inflight[1] == r._affinity_skew + 1

    def test_skew_bound_falls_back_with_hint(self):
        r = _router()
        r._affinity_skew = 2
        toks = list(range(16))
        _install_digest(r, "b", toks)
        chain = chain_hashes(toks, 4)
        r._inflight = {0: 0, 1: 3, 2: 0}  # holder 3 over min 0 > skew 2
        idx, rep, hint = r._pick(chain=chain)
        assert idx != 1
        assert hint is not None
        assert hint["handle"] is r._replicas[1]
        assert hint["tokens"] == 4 * 4  # depth pages x page_tokens
        # at exactly the bound the holder still wins
        r._inflight = {0: 0, 1: 2, 2: 0}
        idx, rep, hint = r._pick(chain=chain)
        assert idx == 1 and hint is None

    def test_fail_marked_holder_falls_back_with_hint(self):
        r = _router()
        toks = list(range(16))
        _install_digest(r, "b", toks)
        chain = chain_hashes(toks, 4)
        r._note_result("b", ok=False)
        for _ in range(8):
            idx, rep, hint = r._pick(chain=chain)
            assert idx != 1
            assert hint is not None and hint["handle"] is r._replicas[1]
        r._note_result("b", ok=True)
        idx, rep, hint = r._pick(chain=chain)
        assert idx == 1 and hint is None

    def test_no_digest_match_is_plain_pow2(self):
        from ray_tpu.serve._private.affinity import m_affinity_misses

        r = _router()
        _install_digest(r, "b", list(range(16)))
        m0 = m_affinity_misses.total()
        idx, rep, hint = r._pick(chain=chain_hashes([99] * 16, 4))
        assert hint is None
        assert m_affinity_misses.total() == m0 + 1

    def test_attach_hint_copies_request(self):
        from ray_tpu.serve._private.router import Router

        req = {"prompt": "p", "max_new_tokens": 3}
        args = Router._attach_hint((req,), {"handle": "h", "tokens": 8})
        assert args[0] is not req  # caller's dict untouched
        assert "_fleet_hint" not in req
        assert args[0]["_fleet_hint"] == {"handle": "h", "tokens": 8}
        assert args[0]["prompt"] == "p"
        # bare-string requests are wrapped, not crashed on
        args = Router._attach_hint(("p",), {"handle": "h", "tokens": 8})
        assert args[0]["prompt"] == "p"

    def test_affinity_chain_ignores_non_llm_payloads(self):
        r = _router()
        _install_digest(r, "a", list(range(16)))
        assert r._affinity_chain((123,)) is None
        assert r._affinity_chain(()) is None
        assert r._affinity_chain(({"op": "sum"},)) is None
        # explicit prompt_ids beat router-side tokenization
        chain = r._affinity_chain(({"prompt_ids": list(range(9))},))
        assert chain == prompt_chain(list(range(9)), 4)


class TestMuxStaleEntryFix:
    def test_failure_clears_optimistic_location(self):
        """The satellite-e bug: assign_request optimistically marks the
        chosen replica as holding the mux model; if that request FAILS the
        entry used to linger for MUX_MARK_TTL_S, steering siblings at a
        cold/dead replica. A failed completion must clear it."""
        r = _router()
        now = time.monotonic()
        r._mux_locations = {"m": {"a", "b"}}
        r._mux_marks = {("m", "a"): now, ("m", "b"): now}
        r._note_result("a", ok=False, mux_id="m")
        assert ("m", "a") not in r._mux_marks
        assert r._mux_locations["m"] == {"b"}
        assert "a" in r._fail_marks
        # last holder failing removes the model entry entirely
        r._note_result("b", ok=False, mux_id="m")
        assert "m" not in r._mux_locations
        # success never touches mux state
        r._mux_locations = {"m": {"a"}}
        r._mux_marks = {("m", "a"): now}
        r._note_result("a", ok=True, mux_id="m")
        assert r._mux_locations == {"m": {"a"}}
        assert "a" not in r._fail_marks


# ------------------------------------------------- migration splice


class _FakeRef:
    def __init__(self, value=None, exc=None):
        self._value, self._exc = value, exc

    def get(self):
        if self._exc is not None:
            raise self._exc
        return self._value


class _FakeMethod:
    def __init__(self, fn):
        self._fn = fn

    def remote(self, *a, **k):
        try:
            return _FakeRef(value=self._fn(*a, **k))
        except Exception as e:  # noqa: BLE001 — crosses the fake RPC
            return _FakeRef(exc=e)


class _FakeHandle:
    """Stands in for the holder replica's actor handle: export_prefix
    runs the real scheduler export (command queue + scheduler thread)."""

    def __init__(self, target_llm):
        self.export_prefix = _FakeMethod(
            lambda toks, **k: target_llm.export_prefix(list(toks)))


@pytest.fixture
def fake_get(monkeypatch):
    real_get = ray_tpu.get

    def get(ref, timeout=None):
        if isinstance(ref, _FakeRef):
            return ref.get()
        return real_get(ref, timeout=timeout)

    monkeypatch.setattr(ray_tpu, "get", get)


def _mk_server(**kw):
    kw.setdefault("max_new_tokens", NEW)
    kw.setdefault("slots", SLOTS)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("share_weights", False)
    return LLMServerImpl(**kw)


def _run(server, request):
    return asyncio.run(server(dict(request)))


class TestMigrationSplice:
    PREFIX = "shared preamble long enough to span multiple kv pages ok. "

    def test_pull_splices_and_releases_refs(self, fake_get):
        holder = _mk_server()
        puller = _mk_server()
        try:
            p = self.PREFIX + "q0"
            ref = _run(holder, {"prompt": p})
            pt = holder._sched.page_tokens
            hint = {"handle": _FakeHandle(holder),
                    "tokens": (len(holder._tokenize(p)) // pt) * pt}
            out = _run(puller, {"prompt": p, "_fleet_hint": hint})
            assert out["text"] == ref["text"]  # bit-identical to holder
            st = puller.scheduler_stats()
            assert st["migrations"] == 1
            assert st["migration_failures"] == 0
            assert st["migrated_pages"] >= 1
            assert st["prefix_hits"] == 1  # the splice avoided a prefill
            assert st["prefix_hit_tokens"] >= pt
            # refcount hygiene: nothing pinned after retire, and the
            # arena agrees with the radix tree page for page
            assert st["radix_active_refs"] == 0
            assert st["pages_in_use"] == st["radix_resident_pages"]
            assert st["migrations_pending"] == 0
        finally:
            holder.shutdown()
            puller.shutdown()

    def test_migrated_pages_evict_under_pressure(self, fake_get):
        """Migrated spans obey the same LRU/refcount eviction as locally
        prefilled ones — pulling pages must not wedge the arena."""
        holder = _mk_server()
        puller = _mk_server(kv_pages=10)  # small pool: force eviction
        try:
            p = self.PREFIX + "q0"
            _run(holder, {"prompt": p})
            pt = puller._sched.page_tokens
            hint = {"handle": _FakeHandle(holder),
                    "tokens": (len(holder._tokenize(p)) // pt) * pt}
            _run(puller, {"prompt": p, "_fleet_hint": hint})
            assert puller.scheduler_stats()["migrations"] == 1
            # now churn distinct prompts through the small pool — each
            # diverges at char 0 (a shared first page would collapse
            # them into one radix node and build no pressure); the
            # migrated node must be evictable once unreferenced
            for i in range(6):
                _run(puller, {"prompt": f"{i:02d} unique filler stream "
                                        f"padding out two pages {i:02d}"})
            st = puller.scheduler_stats()
            assert st["evicted_pages_total"] > 0
            assert st["radix_active_refs"] == 0
            assert st["pages_in_use"] == st["radix_resident_pages"]
        finally:
            holder.shutdown()
            puller.shutdown()

    def test_failed_pull_degrades_to_cold_prefill(self, fake_get):
        holder = _mk_server()
        puller = _mk_server()
        try:
            p = self.PREFIX + "q1"
            ref = _run(holder, {"prompt": p})

            class _DeadHandle:
                export_prefix = _FakeMethod(lambda *a, **k: (_ for _ in ())
                                            .throw(RuntimeError("dead")))

            hint = {"handle": _DeadHandle(), "tokens": 64}
            out = _run(puller, {"prompt": p, "_fleet_hint": hint})
            assert out["text"] == ref["text"]  # cold prefill, same bits
            st = puller.scheduler_stats()
            assert st["migrations"] == 0
            assert st["migration_failures"] == 1
            assert st["radix_active_refs"] == 0
            assert st["pages_in_use"] == st["radix_resident_pages"]
        finally:
            holder.shutdown()
            puller.shutdown()

    def test_local_hit_skips_pull(self, fake_get):
        """A hint for a prefix the puller ALREADY holds must not trigger
        an RPC — the local radix match wins."""
        holder = _mk_server()
        puller = _mk_server()
        try:
            p = self.PREFIX + "q2"
            _run(holder, {"prompt": p})
            _run(puller, {"prompt": p})  # warms the puller locally
            calls = []

            class _CountingHandle:
                export_prefix = _FakeMethod(
                    lambda *a, **k: calls.append(1) or {"matched_len": 0})

            hint = {"handle": _CountingHandle(), "tokens": 64}
            _run(puller, {"prompt": p, "_fleet_hint": hint})
            assert calls == []  # never pulled
            assert puller.scheduler_stats()["migrations"] == 0
        finally:
            holder.shutdown()
            puller.shutdown()


# --------------------------------------------------- speculative decoding


def _assert_the_pool_keeps_no_cursor(srv):
    """``_Seq.cursor`` is the only cursor: the device holds pages, so a
    speculative round has nothing to read back or upload but its logits."""
    import dataclasses

    assert [f.name for f in dataclasses.fields(srv._sched._caches[0])] == [
        "k", "v"]


@pytest.fixture(scope="module")
def spec_server():
    srv = _mk_server(drafter="self", spec_k=4)
    yield srv
    srv.shutdown()


class TestSpeculativeParity:
    def test_temp0_bit_identical_mixed_lengths(self, spec_server):
        """The core spec-decode contract: k-token drafting + one-shot
        verification emits EXACTLY the sequential greedy tokens — mixed
        prompt lengths, chunked prefill, concurrent slots and all."""
        srv = spec_server
        refs = {p: _sequential_reference(srv, p, NEW) for p in PROMPTS}

        async def drive():
            reqs = [{"prompt": p} for p in PROMPTS * 3]
            return await asyncio.gather(*[srv(r) for r in reqs])

        outs = asyncio.run(drive())
        for o in outs:
            assert o["text"] == refs[o["prompt"]], (
                f"speculative output diverged for {o['prompt']!r}")
            assert o["num_tokens"] == NEW
        st = srv.scheduler_stats()
        assert st["spec_rounds"] > 0
        assert st["spec_drafted_tokens"] > 0
        # self-drafter at temperature 0: every draft must be accepted
        assert st["spec_accept_rate"] == 1.0
        assert st["spec_tokens_per_step"] > 1.0
        _assert_the_pool_keeps_no_cursor(srv)

    def test_rejected_drafts_move_only_the_hosts_cursor(self):
        """A drafter that is wrong every other step: each rejection leaves
        ``seq.cursor`` short of the window the verify call wrote, there is
        no device cursor to rewind or read back, and the stream is still
        the sequential greedy one — slots reused mid-speculation."""
        srv = _mk_server(drafter="self", spec_k=3)
        try:
            drafter = srv._sched._drafter
            step, calls = drafter.step, [0]

            def wrong_every_other_step(tokens, active):
                calls[0] += 1
                logits = step(tokens, active)
                return -logits if calls[0] % 2 else logits

            drafter.step = wrong_every_other_step
            refs = {p: _sequential_reference(srv, p, NEW) for p in PROMPTS}

            async def drive():
                reqs = [{"prompt": p} for p in PROMPTS * 3]
                return await asyncio.gather(*[srv(r) for r in reqs])

            for o in asyncio.run(drive()):
                assert o["text"] == refs[o["prompt"]], o["prompt"]
            st = srv.scheduler_stats()
            assert 0.0 < st["spec_accept_rate"] < 1.0
            assert st["compiled_programs"] == 2
            _assert_the_pool_keeps_no_cursor(srv)
        finally:
            srv.shutdown()

    def test_slot_reuse_stays_exact(self, spec_server):
        """> slots requests force retire/reuse mid-speculation; rewound
        cursors and drafter sync must not leak between occupants."""
        srv = spec_server
        ref = _sequential_reference(srv, "hello 123", NEW)

        async def drive():
            reqs = [{"prompt": "hello 123"} for _ in range(SLOTS * 3)]
            return await asyncio.gather(*[srv(r) for r in reqs])

        for o in asyncio.run(drive()):
            assert o["text"] == ref

    def test_k1_degenerate_matches(self):
        """spec_k=1 is the smallest speculation: one draft + bonus. Still
        bit-exact, still > 1 token per verify step at full acceptance."""
        srv = _mk_server(drafter="self", spec_k=1)
        try:
            ref = _sequential_reference(srv, "hello 123", NEW)
            out = _run(srv, {"prompt": "hello 123"})
            assert out["text"] == ref
            st = srv.scheduler_stats()
            assert st["spec_k"] == 1
            assert st["spec_tokens_per_step"] > 1.0
        finally:
            srv.shutdown()

    def test_temp_gt0_runs_and_counts(self):
        srv = _mk_server(drafter="self", spec_k=3, temperature=0.8)
        try:
            out = _run(srv, {"prompt": "hello 123"})
            assert out["num_tokens"] == NEW
            st = srv.scheduler_stats()
            assert st["spec_drafted_tokens"] > 0
            assert 0.0 < st["spec_accept_rate"] <= 1.0
        finally:
            srv.shutdown()

    def test_compiles_contract(self, spec_server):
        """Fixed-shape guarantee with speculation ON: chunked prefill +
        paged_verify_step are the ONLY target-model programs (the plain
        decode step never runs in spec mode), and the drafter's own
        programs are accounted separately."""
        st = spec_server.scheduler_stats()
        assert st["compiled_programs"] == 2, st
        assert st["drafter_compiled_programs"] >= 1


class TestAcceptanceSampling:
    def test_greedy_acceptance_prefix_rule(self):
        logits = np.zeros((4, 8), np.float32)
        logits[0, 3] = 9  # target argmax after position: 3
        logits[1, 5] = 9
        logits[2, 2] = 9
        logits[3, 7] = 9
        acc, emitted = accept_greedy([3, 5, 2], logits)
        assert acc == 3
        assert emitted == [3, 5, 2, 7]  # all accepted + bonus
        acc, emitted = accept_greedy([3, 9, 2], logits)
        assert acc == 1
        assert emitted == [3, 5]  # replacement from the verify row

    def test_sample_acceptance_matches_target_distribution(self):
        """The arXiv:2211.17192 guarantee: tokens emitted by speculative
        sampling are distributed EXACTLY per the target distribution,
        whatever the draft distribution. Empirical check on a small
        vocab with a deliberately skewed drafter."""
        rng = np.random.default_rng(0)
        vocab = 4
        p_target = np.asarray([0.5, 0.3, 0.15, 0.05])
        p_draft = np.asarray([0.05, 0.15, 0.3, 0.5])  # reversed: adversarial
        counts = np.zeros(vocab)
        n_trials = 20000
        accepted_total = 0
        for _ in range(n_trials):
            d = int(rng.choice(vocab, p=p_draft))
            acc, emitted = accept_sample(
                [d], [p_draft], [p_target, p_target], rng)
            accepted_total += acc
            counts[emitted[0]] += 1
        emp = counts / counts.sum()
        assert np.abs(emp - p_target).max() < 0.02, emp
        # acceptance rate = sum_t min(p, q) for these distributions
        expect = float(np.minimum(p_target, p_draft).sum())
        assert abs(accepted_total / n_trials - expect) < 0.02

    def test_identical_distributions_always_accept(self):
        rng = np.random.default_rng(1)
        p = np.asarray([0.25, 0.25, 0.25, 0.25])
        for _ in range(200):
            d = int(rng.integers(4))
            acc, emitted = accept_sample([d], [p], [p, p], rng)
            assert acc == 1
            assert emitted[0] == d

    def test_softmax_temperature(self):
        row = np.asarray([1.0, 2.0, 3.0], np.float32)
        p = _softmax(row, 1.0)
        assert abs(p.sum() - 1.0) < 1e-9
        sharp = _softmax(row, 0.25)
        assert sharp[2] > p[2]  # lower temperature sharpens


# --------------------------------------------------------- knob hygiene


class TestKnobValidation:
    def test_explicit_zero_spec_k_rejected(self):
        with pytest.raises(ValueError, match="spec_k"):
            _mk_server(drafter="self", spec_k=0)

    def test_explicit_zero_migration_budget_rejected(self):
        with pytest.raises(ValueError, match="migration_budget"):
            _mk_server(migration_budget=0)

    def test_unknown_drafter_preset_rejected(self):
        with pytest.raises(ValueError, match="drafter"):
            _mk_server(drafter="no_such_preset")

    def test_env_knobs_parse(self, monkeypatch):
        from ray_tpu._private.config import Config

        monkeypatch.setenv("RAY_TPU_SERVE_AFFINITY", "0")
        monkeypatch.setenv("RAY_TPU_SERVE_SPEC_K", "7")
        monkeypatch.setenv("RAY_TPU_SERVE_MIGRATION_BUDGET", "9")
        monkeypatch.setenv("RAY_TPU_SERVE_DRAFTER", "self")
        monkeypatch.setenv("RAY_TPU_SERVE_AFFINITY_SKEW", "3")
        c = Config.from_env()
        assert c.serve_affinity is False
        assert c.serve_spec_k == 7
        assert c.serve_migration_budget == 9
        assert c.serve_drafter == "self"
        assert c.serve_affinity_skew == 3


# ------------------------------------------------------------- zero RPC


class TestZeroRPCAllFeaturesOn:
    def test_steady_state_decode_makes_no_control_rpcs(self, fake_get):
        """The ISSUE-18 counter-assert, re-proven with EVERY fleet
        feature on: paged arena + radix cache + speculative decoding +
        migration machinery armed. Steady-state admission, drafting,
        verification, splicing of a LOCAL prefix hit and retirement must
        execute zero control-plane RPCs (migration pulls are data-plane,
        replica-to-replica, and happen only on a fleet hint)."""
        from ray_tpu._private.rpc import _m_client_calls

        srv = _mk_server(drafter="self", spec_k=3)
        try:
            _run(srv, {"prompt": "warm the programs"})  # compile off-meter
            rpc0 = _m_client_calls.total()
            for i in range(3):
                out = _run(srv, {"prompt": "warm the programs"})
                assert out["num_tokens"] == NEW
            st = srv.scheduler_stats()
            assert st["prefix_hits"] >= 1
            assert st["spec_rounds"] > 0
            assert _m_client_calls.total() == rpc0
        finally:
            srv.shutdown()
