"""ray_tpu.serve: deployments, handles, composition, batching, scaling,
replica recovery, HTTP proxy. Mirrors the reference's
`python/ray/serve/tests/` coverage shape."""

import asyncio
import time

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_shutdown(ray_init):
    yield
    serve.shutdown()


@serve.deployment
class Doubler:
    def __call__(self, x):
        return x * 2


@serve.deployment
def plus_one(x):
    return x + 1


class TestDeployments:
    def test_basic_class_deployment(self, serve_shutdown):
        h = serve.run(Doubler.bind(), name="d1", route_prefix="/d1")
        assert h.remote(21).result(timeout=10) == 42

    def test_function_deployment(self, serve_shutdown):
        h = serve.run(plus_one.bind(), name="d2", route_prefix="/d2")
        assert h.remote(41).result(timeout=10) == 42

    def test_init_args(self, serve_shutdown):
        @serve.deployment
        class WithArgs:
            def __init__(self, base, scale=1):
                self.base = base
                self.scale = scale

            def __call__(self, x):
                return self.base + x * self.scale

        h = serve.run(WithArgs.bind(100, scale=3), name="d3",
                      route_prefix="/d3")
        assert h.remote(5).result(timeout=10) == 115

    def test_a_constructor_that_raises_fails_the_run_at_once(
            self, serve_shutdown):
        """A replica whose constructor raises would raise again: the
        deployment stops and ``serve.run`` raises the cause within
        seconds, not ``TimeoutError`` after ``timeout_s``."""
        @serve.deployment
        class Broken:
            def __init__(self):
                raise ValueError("no such preset field: 'qk_norm'")

            def __call__(self, x):
                return x

        t0 = time.monotonic()
        with pytest.raises(RuntimeError) as ei:
            serve.run(Broken.bind(), name="d_broken", route_prefix="/dbr",
                      timeout_s=300)
        assert time.monotonic() - t0 < 60
        assert "died in its constructor" in str(ei.value)
        assert "no such preset field: 'qk_norm'" in str(ei.value)
        status = serve.status()["d_broken"]["Broken"]
        assert status["status"] == "DEPLOY_FAILED" and status["error"]
        assert status["replicas"] == 0
        serve.delete("d_broken")
        # the controller still deploys what works
        h = serve.run(Doubler.bind(), name="d_after", route_prefix="/daf")
        assert h.remote(4).result(timeout=10) == 8

    def test_method_call(self, serve_shutdown):
        @serve.deployment
        class Multi:
            def __call__(self, x):
                return x

            def square(self, x):
                return x * x

        h = serve.run(Multi.bind(), name="d4", route_prefix="/d4")
        assert h.square.remote(7).result(timeout=10) == 49

    def test_num_replicas_spread(self, serve_shutdown):
        import os

        @serve.deployment(num_replicas=3)
        class PidReporter:
            def __call__(self, _):
                import os

                return os.getpid()

        h = serve.run(PidReporter.bind(), name="d5", route_prefix="/d5")
        pids = {h.remote(None).result(timeout=10) for _ in range(30)}
        assert len(pids) >= 2  # pow-2 routing spreads load

    def test_status(self, serve_shutdown):
        serve.run(Doubler.bind(), name="d6", route_prefix="/d6")
        st = serve.status()
        assert st["d6"]["Doubler"]["status"] == "RUNNING"
        assert st["d6"]["Doubler"]["replicas"] == 1

    def test_delete(self, serve_shutdown):
        serve.run(Doubler.bind(), name="d7", route_prefix="/d7")
        serve.delete("d7")
        assert "d7" not in serve.status()


class TestComposition:
    def test_model_chaining(self, serve_shutdown):
        @serve.deployment
        class Preprocess:
            def __call__(self, x):
                return x + 1

        @serve.deployment
        class Ingress:
            def __init__(self, pre):
                self.pre = pre

            async def __call__(self, x):
                y = await self.pre.remote(x)
                return y * 10

        h = serve.run(Ingress.bind(Preprocess.bind()), name="chain",
                      route_prefix="/chain")
        assert h.remote(4).result(timeout=10) == 50


class TestAsyncAndBatching:
    def test_async_concurrent_requests(self, serve_shutdown):
        @serve.deployment(max_ongoing_requests=16)
        class Slow:
            async def __call__(self, x):
                await asyncio.sleep(0.2)
                return x

        h = serve.run(Slow.bind(), name="conc", route_prefix="/conc")
        t0 = time.monotonic()
        responses = [h.remote(i) for i in range(8)]
        out = [r.result(timeout=15) for r in responses]
        elapsed = time.monotonic() - t0
        assert sorted(out) == list(range(8))
        assert elapsed < 1.2  # concurrent, not 8×0.2 serial

    def test_serve_batch(self, serve_shutdown):
        @serve.deployment(max_ongoing_requests=32)
        class Batched:
            def __init__(self):
                self.batch_sizes = []

            @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.1)
            async def handle(self, items):
                self.batch_sizes.append(len(items))
                return [i * 2 for i in items]

            async def __call__(self, x):
                if x == "sizes":
                    return self.batch_sizes
                return await self.handle(x)

        h = serve.run(Batched.bind(), name="batch", route_prefix="/batch")
        responses = [h.remote(i) for i in range(8)]
        assert [r.result(timeout=15) for r in responses] == [
            i * 2 for i in range(8)]
        sizes = h.remote("sizes").result(timeout=10)
        assert max(sizes) > 1  # requests actually coalesced


class TestQueueDepthAutoscaling:
    """ROADMAP item 4's remaining bullet: the controller scales replica
    targets on the ray_tpu_serve_queue_depth signal (admitted-but-
    unscheduled backlog, relayed through replica stats), not just
    in-flight request counts."""

    def _wait_replicas(self, app, dep, n, timeout=30):
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            st = serve.status()
            if st.get(app, {}).get(dep, {}).get("replicas") == n:
                return True
            time.sleep(0.3)
        return False

    def test_synthetic_backlog_scales_up(self, serve_shutdown):
        """A replica with zero in-flight requests but a deep scheduler
        queue must still trigger scale-up — the continuous batcher
        admits everything into its pending queue, so 'ongoing' alone
        undercounts exactly when the replica is saturated."""
        @serve.deployment(autoscaling_config={
            "min_replicas": 1, "max_replicas": 3,
            "target_ongoing_requests": 2})
        class Backlogged:
            def queue_depth(self):
                return 50  # synthetic backlog; no requests in flight

            def __call__(self, x):
                return x

        serve.run(Backlogged.bind(), name="qd", route_prefix="/qd")
        assert self._wait_replicas("qd", "Backlogged", 3), (
            "queue-depth backlog did not scale replicas to max")

    def test_idle_queue_stays_at_min(self, serve_shutdown):
        @serve.deployment(autoscaling_config={
            "min_replicas": 1, "max_replicas": 3,
            "target_ongoing_requests": 2})
        class Idle:
            def queue_depth(self):
                return 0

            def __call__(self, x):
                return x

        h = serve.run(Idle.bind(), name="qd2", route_prefix="/qd2")
        assert h.remote(1).result(timeout=10) == 1
        time.sleep(2.0)  # several autoscale passes
        assert serve.status()["qd2"]["Idle"]["replicas"] == 1


class TestRecovery:
    def test_replica_replaced_after_death(self, serve_shutdown):
        @serve.deployment
        class Fragile:
            def __call__(self, x):
                if x == "die":
                    import os

                    os._exit(1)
                return "alive"

        h = serve.run(Fragile.bind(), name="frag", route_prefix="/frag")
        assert h.remote("ok").result(timeout=10) == "alive"
        try:
            h.remote("die").result(timeout=10)
        except Exception:
            pass
        # controller health sweep replaces the replica
        deadline = time.monotonic() + 30
        ok = False
        while time.monotonic() < deadline:
            try:
                if h.remote("ok").result(timeout=5) == "alive":
                    ok = True
                    break
            except Exception:
                time.sleep(0.5)
        assert ok, "replica was not replaced"


class TestHTTP:
    def test_http_proxy(self, serve_shutdown):
        import httpx

        @serve.deployment
        class Echo:
            def __call__(self, payload):
                return {"got": payload}

        serve.run(Echo.bind(), name="http", route_prefix="/echo")
        port = serve.start(http_port=0)  # ephemeral: no collisions
        base = f"http://127.0.0.1:{port}"
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                r = httpx.get(base + "/-/healthz", timeout=2)
                if r.status_code == 200:
                    break
            except Exception:
                time.sleep(0.2)
        r = httpx.post(base + "/echo", json={"x": 1}, timeout=30)
        assert r.status_code == 200, r.text
        assert r.json() == {"got": {"x": 1}}
        r404 = httpx.get(base + "/nope", timeout=10)
        assert r404.status_code == 404


class TestStreaming:
    def test_handle_streams_generator(self, serve_shutdown):
        @serve.deployment
        class Streamer:
            def __call__(self, n):
                def gen():
                    for i in range(n):
                        yield f"tok{i} "
                return gen()

        h = serve.run(Streamer.bind(), name="stream", route_prefix="/stream")
        chunks = list(h.remote(5))
        assert chunks == [f"tok{i} " for i in range(5)]

    def test_handle_streams_async_generator(self, serve_shutdown):
        @serve.deployment
        class AStreamer:
            async def __call__(self, n):
                async def gen():
                    for i in range(n):
                        await asyncio.sleep(0.001)
                        yield i * 10
                return gen()

        h = serve.run(AStreamer.bind(), name="astream",
                      route_prefix="/astream")
        assert list(h.remote(4)) == [0, 10, 20, 30]

    def test_stream_error_propagates(self, serve_shutdown):
        @serve.deployment
        class Bad:
            def __call__(self, _):
                def gen():
                    yield "ok"
                    raise ValueError("boom")
                return gen()

        h = serve.run(Bad.bind(), name="badstream", route_prefix="/bad")
        it = iter(h.remote(None))
        assert next(it) == "ok"
        with pytest.raises(RuntimeError, match="boom"):
            list(it)

    def test_native_generator_transport(self, serve_shutdown):
        """handle.options(stream=True): chunks ride the streaming-
        generator task transport (ObjectRefGenerator), not the
        chunk-pull stream_next path."""
        @serve.deployment
        class Streamer:
            def __call__(self, n):
                def gen():
                    for i in range(n):
                        yield f"n{i}"
                return gen()

        h = serve.run(Streamer.bind(), name="ngen", route_prefix="/ngen")
        sh = h.options(stream=True)
        resp = sh.remote(4)
        assert isinstance(resp.ref, ray_tpu.ObjectRefGenerator)
        assert list(resp) == ["n0", "n1", "n2", "n3"]
        # async generators too
        @serve.deployment
        class AStreamer:
            async def __call__(self, n):
                async def gen():
                    for i in range(n):
                        await asyncio.sleep(0.001)
                        yield i
                return gen()

        h2 = serve.run(AStreamer.bind(), name="ngen2",
                       route_prefix="/ngen2")
        assert list(h2.options(stream=True).remote(3)) == [0, 1, 2]

    def test_streamed_response_settles_its_replica_when_it_ends(
            self, serve_shutdown):
        """A streamed handle response read to its end (or to its error)
        gives its replica's in-flight count back THEN, and feeds the
        router's failure accounting, while the response is still
        referenced: nothing is left for ``__del__`` to settle from inside
        a collection (which can run under the router's lock)."""
        @serve.deployment
        class Streamer:
            def __call__(self, n):
                def gen():
                    for i in range(abs(n)):
                        yield i
                    if n < 0:
                        raise ValueError("stream boom")
                return gen()

        h = serve.run(Streamer.bind(), name="settle", route_prefix="/settle")
        sh = h.options(stream=True)
        router = sh._get_router()
        kept = []
        for n in (3, 1):
            resp = sh.remote(n)
            kept.append(resp)
            assert sum(router._inflight.values()) == 1
            assert list(resp) == list(range(n))
            assert resp.ref._settled
            assert sum(router._inflight.values()) == 0
        resp = sh.remote(-2)
        kept.append(resp)
        with pytest.raises(Exception, match="stream boom"):
            list(resp)
        assert resp.ref._settled
        assert sum(router._inflight.values()) == 0
        assert resp.ref._replica_key in router._fail_marks

    def test_busy_replica_survives_missed_health_probes(self,
                                                        serve_shutdown):
        """A replica that blocks its loop longer than one probe timeout
        (e.g. jit-compiling a new batch shape) must NOT be replaced —
        replacement needs HEALTH_FAIL_THRESHOLD consecutive misses.
        Regression: one missed 5s probe used to kill the replica and
        fail every in-flight request with ActorDiedError."""
        import time as _time

        @serve.deployment
        class Slow:
            def __call__(self, seconds):
                import os as _os
                import time as _t

                # synchronous sleep BLOCKS the replica loop: health
                # probes time out while this runs
                _t.sleep(seconds)
                return _os.getpid()

        h = serve.run(Slow.bind(), name="slowhp", route_prefix="/slowhp")
        pid_before = h.remote(0).result(timeout=30)
        # block for ~1.5 probe timeouts; the sweep (0.5s period, 5s
        # probe timeout) misses at least once during this window
        pid_during = h.remote(7).result(timeout=60)
        assert pid_during == pid_before, \
            "replica was replaced during a single blocked probe window"
        assert h.remote(0).result(timeout=30) == pid_before

    def test_router_failure_mark_skews_pick(self):
        """A replica with a recent request failure (unary or stream
        terminal error — advisor r4) loses every pow-2 draw until the
        penalty window lapses."""
        from ray_tpu.serve._private.router import Router

        r = Router(None, "app", "dep")
        rep_a, rep_b = object(), object()
        r._replicas = [rep_a, rep_b]
        r._inflight = {0: 0, 1: 0}
        r._key_to_idx = {r._replica_key(rep_a): 0,
                         r._replica_key(rep_b): 1}
        r._note_result(r._replica_key(rep_a), ok=False)
        picks = {r._pick()[0] for _ in range(20)}
        assert picks == {1}, f"failing replica still drawn: {picks}"
        # success clears the mark; both replicas are drawable again
        r._note_result(r._replica_key(rep_a), ok=True)
        r._inflight = {0: 0, 1: 0}
        picks = {r._pick()[0] for _ in range(50)}
        assert picks == {0, 1}

    def test_native_stream_error_propagates(self, serve_shutdown):
        @serve.deployment
        class Bad:
            def __call__(self, _):
                def gen():
                    yield "ok"
                    raise ValueError("native boom")
                return gen()

        h = serve.run(Bad.bind(), name="nbad", route_prefix="/nbad")
        it = iter(h.options(stream=True).remote(None))
        assert next(it) == "ok"
        with pytest.raises(Exception, match="native boom"):
            list(it)


class TestLLMDecode:
    """The BASELINE.md serve flagship: batched llama-shaped decode replica
    with prefill + KV-cache decode, continuous batching, HTTP streaming."""

    def test_batched_decode_and_http_streaming(self, serve_shutdown):
        import threading

        import httpx

        from ray_tpu.serve.llm import build_app

        h = serve.run(build_app(max_new_tokens=6, slots=4,
                                prefill_chunk=8), name="llm",
                      route_prefix="/llm")

        # continuous batching: concurrent same-shape requests coalesce into
        # one decode program and all complete
        outs = [None] * 4
        def call(i):
            outs[i] = h.remote({"prompt": "hello 123"}).result(timeout=120)
        threads = [threading.Thread(target=call, args=(i,)) for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for o in outs:
            assert o is not None and o["num_tokens"] == 6
            assert isinstance(o["text"], str)
        # same prompt + greedy sampling => identical outputs across the batch
        assert len({o["text"] for o in outs}) == 1

        # HTTP: non-streaming JSON, then chunked token streaming
        port = serve.start(http_port=0)  # ephemeral: no collisions
        base = f"http://127.0.0.1:{port}"
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                if httpx.get(base + "/-/healthz", timeout=2).status_code == 200:
                    break
            except Exception:
                time.sleep(0.2)
        r = httpx.post(base + "/llm", json={"prompt": "hi"}, timeout=120)
        assert r.status_code == 200, r.text
        assert r.json()["num_tokens"] == 6

        with httpx.stream("POST", base + "/llm",
                          json={"prompt": "hi", "stream": True},
                          timeout=120) as r:
            assert r.status_code == 200
            assert r.headers.get("x-serve-stream") == "1"
            pieces = list(r.iter_text())
        assert len("".join(pieces)) > 0

    def test_mixed_length_prompts_batch_correctly(self, serve_shutdown):
        """Different-length prompts coalescing into one flush must not
        contaminate each other (length-grouped decode programs): each
        result equals the prompt decoded alone."""
        import threading

        from ray_tpu.serve.llm import build_app

        h = serve.run(build_app(max_new_tokens=4, slots=4,
                                prefill_chunk=8), name="llmmix",
                      route_prefix="/llmmix")
        solo_a = h.remote({"prompt": "abcd"}).result(timeout=120)
        solo_b = h.remote({"prompt": "a much longer prompt!"}).result(
            timeout=120)

        outs = {}
        def call(key, prompt):
            outs[key] = h.remote({"prompt": prompt}).result(timeout=120)
        threads = [
            threading.Thread(target=call, args=("a", "abcd")),
            threading.Thread(target=call, args=("b", "a much longer prompt!")),
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert outs["a"]["text"] == solo_a["text"]
        assert outs["b"]["text"] == solo_b["text"]
