"""Continuous (iteration-level) batching for the LLM serve path + one-copy-
per-node shared weights (ISSUE 9; ROADMAP item 4).

Covers the scheduler's correctness contracts: temperature-0 parity of
continuous-batching outputs against the sequential single-request decode
reference (exact token match, mixed prompt lengths, chunked prefill),
slot retire/reuse under mid-stream cancellation, admission under full
slots (queues, no drops), the `_BatchQueue` hardening (flush-race, per-
item errors, deploy-time overrides), and the shared-weights pin
accounting (second replica adds no arena bytes; replica death releases
its pins).

The scheduler's KV pool is PAGED with the radix prefix cache on — this
suite runs the defaults end to end; the paged/radix-specific contracts
(capacity at fixed pool bytes, eviction, two-compiles guard) live in
tests/test_paged_kv.py.
"""

import asyncio
import gc
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve
from ray_tpu.serve.batching import _BatchQueue  # noqa: F401  (unit tests)
from ray_tpu.serve.llm import LLMServerImpl, build_app
from tests.model_harness import sequential_text as _sequential_reference

SLOTS = 4
CHUNK = 8
NEW = 6

PROMPTS = ["hi", "hello 123", "a much longer prompt than the others!"]


@pytest.fixture(scope="module")
def server():
    """One directly-instantiated replica callable (no control plane): the
    scheduler-level contracts don't need actors, and sharing the instance
    keeps jit compiles to one per program shape."""
    srv = LLMServerImpl(max_new_tokens=NEW, slots=SLOTS,
                        prefill_chunk=CHUNK, share_weights=False)
    yield srv
    srv.shutdown()


def _scheduler_on_a_stub(**kwargs):
    from ray_tpu.serve._private.continuous import ContinuousScheduler

    class _Cfg:  # never reaches jit — validation fires first
        max_seq_len = 128

    return ContinuousScheduler(_Cfg(), None, **kwargs)


class TestContinuousParity:
    def test_concurrent_mixed_lengths_match_sequential(self, server):
        """Mixed-length prompts decoded concurrently through the slot
        arena must equal the sequential single-request reference token for
        token at temperature 0 — admission interleaving, chunked prefill
        (one prompt is longer than the chunk), and batch width must not
        perturb any sequence's tokens."""
        refs = {p: _sequential_reference(server, p, NEW) for p in PROMPTS}

        async def drive():
            reqs = [{"prompt": p} for p in PROMPTS * 3]  # > SLOTS: queues
            return await asyncio.gather(*[server(r) for r in reqs])

        outs = asyncio.run(drive())
        for o in outs:
            assert o["text"] == refs[o["prompt"]], \
                f"continuous output diverged for {o['prompt']!r}"
            assert o["num_tokens"] == NEW
        st = server.scheduler_stats()
        assert st["mode"] == "continuous"
        # iteration-level proof: requests were admitted while others were
        # mid-generation, and the decode step actually ran multi-slot
        assert st["admitted_mid_flight"] > 0
        assert st["max_active_slots"] >= 2

    def test_streaming_rides_the_shared_scheduler(self, server):
        """Streaming is a consumer of the scheduler's per-slot queue: the
        streamed text equals the non-streamed (batched) result and no
        per-stream decode loop exists (decode_steps advances globally)."""
        ref = _sequential_reference(server, "hello 123", NEW)

        async def drive():
            gen = await server({"prompt": "hello 123", "stream": True})
            return [c async for c in gen]

        chunks = asyncio.run(drive())
        assert len(chunks) == NEW
        assert "".join(chunks) == ref

    def test_request_level_max_new_tokens(self, server):
        ref = _sequential_reference(server, "hello 123", NEW)

        async def drive():
            return await server({"prompt": "hello 123",
                                 "max_new_tokens": 3})

        out = asyncio.run(drive())
        assert out["num_tokens"] == 3
        assert ref.startswith(out["text"])

    def test_prompt_over_capacity_rejected(self, server):
        """A prompt that cannot fit its slot (padded prefill + generation
        budget vs arena length) fails loudly at admission, not by silent
        cache-clamp corruption."""
        with pytest.raises(Exception, match="arena"):
            asyncio.run(server({"prompt": "x" * 500}))


class TestSlotLifecycle:
    def test_cancel_mid_stream_retires_and_reuses_slot(self, server):
        """Abandoning a stream mid-generation must retire its slot on the
        next iteration; the freed slot is reusable and later requests on
        it are uncontaminated."""
        ref = _sequential_reference(server, "hello 123", NEW)

        async def drive():
            retired0 = server.scheduler_stats()["retired"]
            gen = await server({"prompt": "a much longer prompt than the "
                                          "others!", "stream": True})
            it = gen.__aiter__()
            await it.__anext__()
            await it.__anext__()
            await gen.aclose()  # consumer walks away after 2 tokens
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                st = server.scheduler_stats()
                if st["active_slots"] == 0 and st["retired"] > retired0:
                    break
                await asyncio.sleep(0.05)
            st = server.scheduler_stats()
            assert st["active_slots"] == 0, st
            # the arena still decodes correctly after the retire
            outs = await asyncio.gather(*[
                server({"prompt": "hello 123"}) for _ in range(SLOTS)])
            return outs

        outs = asyncio.run(drive())
        for o in outs:
            assert o["text"] == ref

    def test_admission_under_full_slots_queues_no_drop(self, server):
        """2x-oversubscribed load: every request queues for a free slot and
        completes — nothing is dropped or errored."""
        n = SLOTS * 2 + 1
        ref = _sequential_reference(server, "hi", NEW)

        async def drive():
            return await asyncio.gather(*[
                server({"prompt": "hi"}) for _ in range(n)])

        outs = asyncio.run(drive())
        assert len(outs) == n
        assert all(o["text"] == ref for o in outs)
        st = server.scheduler_stats()
        assert st["peak_queue_depth"] >= 1, \
            "oversubscription never reached the queue"
        assert st["queue_depth"] == 0 and st["active_slots"] == 0

    def test_eos_retires_early(self):
        """A sampled EOS token retires the slot before the max_new budget
        is spent."""
        srv = LLMServerImpl(max_new_tokens=NEW, slots=2, prefill_chunk=CHUNK,
                            share_weights=False, eos_id=0)
        try:
            async def drive():
                return await asyncio.gather(*[
                    srv({"prompt": p, "max_new_tokens": 64})
                    for p in ("hello 123", "hi")])

            outs = asyncio.run(drive())
            for o in outs:
                # either EOS fired early (retired short) or the budget ran
                assert 1 <= o["num_tokens"] <= 64
            assert srv.scheduler_stats()["active_slots"] == 0
        finally:
            srv.shutdown()

    def test_explicit_zero_knobs_rejected(self):
        """slots=0 / prefill_chunk=0 must raise, not silently take the
        config default (the PR-8 falsy-zero lesson)."""
        with pytest.raises(ValueError, match="slots"):
            _scheduler_on_a_stub(slots=0)
        with pytest.raises(ValueError, match="prefill_chunk"):
            _scheduler_on_a_stub(prefill_chunk=0)

    @pytest.mark.parametrize("build,error", [
        (lambda: LLMServerImpl(scheduler="batch", share_weights=False),
         TypeError),
        (lambda: LLMServerImpl(kv_layout="contiguous", share_weights=False),
         TypeError),
        (lambda: _scheduler_on_a_stub(attn="gather"), ValueError),
    ], ids=["scheduler=batch", "kv_layout=contiguous", "attn=gather"])
    def test_deleted_options_are_refused(self, build, error):
        """There is one scheduler, one KV layout and no gathered-view lane:
        asking for another raises, it is not ignored."""
        with pytest.raises(error, match="scheduler|kv_layout|gather"):
            build()

    def test_shutdown_fails_inflight_cleanly(self):
        srv = LLMServerImpl(max_new_tokens=NEW, slots=2, prefill_chunk=CHUNK,
                            share_weights=False)

        async def drive():
            task = asyncio.ensure_future(
                srv({"prompt": "hello 123", "max_new_tokens": 64}))
            await asyncio.sleep(0.2)
            srv.shutdown()
            with pytest.raises(RuntimeError):
                await task

        asyncio.run(drive())
        from ray_tpu.serve._private.continuous import SchedulerClosedError

        with pytest.raises(SchedulerClosedError):
            srv._sched.submit([1, 2], max_new_tokens=2)


class TestBatchQueueHardening:
    """serve/batching.py stays the generic request-level batcher; these are
    the ISSUE-9 satellite hardening contracts."""

    def test_deploy_time_size_and_timeout_overrides(self):
        sizes = []

        class Dep:
            def __init__(self):
                # deploy-time overrides (the LLMServer idiom)
                setattr(self, "__serve_batch_size_fn", 3)
                setattr(self, "__serve_batch_timeout_fn", 5.0)

            @serve.batch(max_batch_size=64, batch_wait_timeout_s=0.001)
            async def fn(self, items):
                sizes.append(len(items))
                return [i * 2 for i in items]

        async def drive():
            d = Dep()
            # 3 concurrent submits == the OVERRIDDEN size: must flush full
            # immediately (the 5s override timeout would otherwise stall)
            t0 = time.monotonic()
            out = await asyncio.wait_for(
                asyncio.gather(d.fn(1), d.fn(2), d.fn(3)), timeout=2.0)
            assert time.monotonic() - t0 < 2.0
            return out

        assert asyncio.run(drive()) == [2, 4, 6]
        assert sizes == [3], f"override ignored: {sizes}"

    def test_len_mismatch_fails_every_waiter(self):
        class Dep:
            @serve.batch(max_batch_size=2, batch_wait_timeout_s=0.01)
            async def fn(self, items):
                return [1]  # wrong length

        async def drive():
            d = Dep()
            r = await asyncio.gather(d.fn("a"), d.fn("b"),
                                     return_exceptions=True)
            assert all(isinstance(x, ValueError) for x in r), r
            assert all("results for" in str(x) for x in r)

        asyncio.run(drive())

    def test_per_item_error_isolation(self):
        """An Exception INSTANCE in the batch fn's output fails only its
        own waiter; batchmates resolve normally."""
        class Dep:
            @serve.batch(max_batch_size=3, batch_wait_timeout_s=0.01)
            async def fn(self, items):
                return [ValueError(f"bad {i}") if i == 2 else i * 10
                        for i in items]

        async def drive():
            d = Dep()
            r = await asyncio.gather(d.fn(1), d.fn(2), d.fn(3),
                                     return_exceptions=True)
            assert r[0] == 10 and r[2] == 30
            assert isinstance(r[1], ValueError) and "bad 2" in str(r[1])

        asyncio.run(drive())

    def test_full_flush_timer_race_no_double_flush(self):
        """Stress the full-batch path against the expiring timer: with a
        zero timeout every submit races the timer task's wakeup. Every
        waiter must resolve exactly once and no batch may be flushed
        empty/twice (total outputs == total submits)."""
        flushed = []

        class Dep:
            @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.0)
            async def fn(self, items):
                flushed.append(len(items))
                await asyncio.sleep(0)  # yield so flushes interleave
                return list(items)

        async def drive():
            d = Dep()
            out = []
            for _round in range(20):
                out += await asyncio.gather(*[d.fn(i) for i in range(7)])
            return out

        out = asyncio.run(drive())
        assert len(out) == 20 * 7
        assert sorted(out) == sorted(list(range(7)) * 20)
        assert sum(flushed) == 20 * 7, f"lost/duplicated items: {flushed}"

    def test_function_batch_still_works(self):
        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.01)
        async def fn(items):
            return [i + 1 for i in items]

        async def drive():
            return await asyncio.gather(*[fn(i) for i in range(4)])

        assert asyncio.run(drive()) == [1, 2, 3, 4]


# --------------------------------------------------------------- weights


def _small_loader():
    rng = np.random.default_rng(0)
    return {"w": rng.standard_normal((128, 128)), "b": np.arange(32.0)}


@ray_tpu.remote
class _WeightHolder:
    def attach(self, key):
        from ray_tpu.serve._private import weights

        self.params, self.info = weights.get_or_publish(key, _small_loader)
        return self.info

    def is_readonly(self):
        try:
            self.params["w"][0, 0] = 1.0
            return False
        except ValueError:
            return True

    def checksum(self):
        return float(self.params["w"].sum())


def _store_stats():
    from ray_tpu._private import api

    core = api._core
    return core._run(
        core.clients.get(core.supervisor_addr).call("store_stats"))


class TestSharedWeights:
    def test_one_copy_per_node_and_death_releases_pins(self, ray_init):
        """First replica publishes (one arena copy); the second attaches
        read-only views over the SAME range (arena delta == 0, well under
        the <= 10% acceptance bound); killing the attached replica returns
        the pin gauge to baseline via the dead-client sweep."""
        gc.collect()
        a = _WeightHolder.remote()
        info_a = ray_tpu.get(a.attach.remote("t1"), timeout=60)
        assert info_a["mode"] == "published" and info_a["shared"]
        st1 = _store_stats()
        used1 = st1["capacity"] - st1["free_bytes"]

        b = _WeightHolder.remote()
        info_b = ray_tpu.get(b.attach.remote("t1"), timeout=60)
        assert info_b["mode"] == "attached"
        assert info_b["ref"] == info_a["ref"]
        st2 = _store_stats()
        used2 = st2["capacity"] - st2["free_bytes"]
        assert used2 - used1 <= 0.1 * info_a["nbytes"], (
            f"second replica added {used2 - used1} arena bytes "
            f"(> 10% of one {info_a['nbytes']}-byte copy)")
        assert ray_tpu.get(b.is_readonly.remote(), timeout=30)
        assert ray_tpu.get(a.checksum.remote(), timeout=30) == \
            ray_tpu.get(b.checksum.remote(), timeout=30)
        assert st2["pins_total"] > st1["pins_total"], \
            "attached replica holds no pin — nothing protects the views"

        ray_tpu.kill(b)
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if _store_stats()["pins_total"] <= st1["pins_total"]:
                break
            time.sleep(0.3)
        assert _store_stats()["pins_total"] <= st1["pins_total"], \
            "replica death did not release its shared-weight pins"
        ray_tpu.kill(a)

    def test_broadcast_delivery_new_node_path(self, ray_init):
        """`collective.broadcast` weight delivery: the receiver gets the
        identical tree without touching the loader/checkpoint path."""
        @ray_tpu.remote
        def root():
            from ray_tpu.serve._private import weights

            tree = _small_loader()
            out = weights.broadcast_params(tree, "wbll", 2, 0)
            return float(out["w"].sum())

        @ray_tpu.remote
        def recv():
            from ray_tpu.serve._private import weights

            out = weights.broadcast_params(None, "wbll", 2, 1)
            assert out["b"].tolist() == list(np.arange(32.0))
            return float(out["w"].sum())

        rs, vs = ray_tpu.get([root.remote(), recv.remote()], timeout=120)
        assert rs == vs


# ------------------------------------------------------------ deployment


@pytest.fixture
def serve_shutdown(ray_init):
    yield
    serve.shutdown()


class TestLLMDeploymentContinuous:
    def test_replicas_share_weights_and_scheduler_engages(
            self, serve_shutdown):
        """Through the real control plane: 2 replicas of the default app
        share one node arena copy (one publisher + one attacher), and
        concurrent load drives the iteration-level scheduler."""
        import threading

        h = serve.run(build_app(max_new_tokens=4, num_replicas=2,
                                slots=4, prefill_chunk=8),
                      name="llmc", route_prefix="/llmc")
        solo = h.remote({"prompt": "hello 123"}).result(timeout=180)

        outs = [None] * 8
        def call(i):
            outs[i] = h.remote({"prompt": "hello 123"}).result(timeout=180)
        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert all(o is not None and o["text"] == solo["text"]
                   for o in outs)

        modes = set()
        infos = []
        for _ in range(16):
            info = h.weights_info.remote().result(timeout=60)
            modes.add(info["mode"])
            infos.append(info)
            if modes == {"published", "attached"}:
                break
        assert modes == {"published", "attached"}, (
            f"replicas did not share one arena copy: {infos[-1]}")

        st = h.scheduler_stats.remote().result(timeout=60)
        assert st["mode"] == "continuous"
        assert st["retired"] >= 1
