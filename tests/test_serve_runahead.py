"""The decode loop runs one step ahead (ISSUE 29): both paged programs
sample, the ids stay on the device, and step n+1 is dispatched before step
n is read. What must hold: temperature-0 streams are the sequential
cache's token for token, whatever strikes while a step is in flight (EOS, a
cancellation, an exhausted pool); nothing is emitted past an end; the
mechanism engages (the counters) and costs what it says (discarded rows);
above temperature 0 the device sampler draws from ``softmax(logits / T)``
with a key that follows from the request alone. CPU, ``llama_debug`` and
``moe_debug``: counts and tokens, never a time."""

import asyncio
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import presets
from ray_tpu.models.decode import sample_token
from ray_tpu.models.transformer import init_params
from ray_tpu.serve._private.continuous import ContinuousScheduler
from tests import model_harness as harness

CHUNK, PAGE = 8, 4
MODELS = ("llama_debug", "moe_debug")


@pytest.fixture(scope="module", params=MODELS)
def model(request):
    cfg = getattr(presets, request.param)()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def dense():
    cfg = presets.llama_debug()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _sched(model, **kw):
    cfg, params = model
    kw.setdefault("slots", 2)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("page_tokens", PAGE)
    return ContinuousScheduler(cfg, params, attn="reference", **kw)


def _oracle(model, prompt, new):
    """Greedy tokens of the sequential cache (``decode_step`` on one
    sequence): no page, table, slot or step in flight."""
    return harness.oracle(*model, prompt, new)


def _serve(sched, requests, on_submit=None):
    """Submit ``requests`` (dicts: prompt, new, and optionally temperature,
    seed) together; returns [(tokens, how it ended)] in their order."""
    async def drive():
        return await asyncio.gather(*(harness.stream(
            sched, r["prompt"], r["new"],
            temperature=r.get("temperature", 0.0), seed=r.get("seed", 0),
            submitted=on_submit and partial(on_submit, i))
            for i, r in enumerate(requests)))

    return asyncio.run(drive())


def _prompt(n, start=1):
    return [(start + 7 * i) % 250 + 1 for i in range(n)]


class _AfterStep:
    """Test double of the decode step: runs the real program, then (still
    on the scheduler's thread, the step in flight) calls ``then`` after its
    ``at``-th dispatch."""

    def __init__(self, step, at, then):
        self._step, self._at, self._then, self.calls = step, at, then, 0

    def __call__(self, *args):
        out = self._step(*args)
        self.calls += 1
        if self.calls == self._at:
            self._then()
        return out

    def _cache_size(self):
        return self._step._cache_size()


# ------------------------------------------------- temperature 0: the oracle


def test_streams_equal_the_oracle_under_churn(model):
    """Mixed lengths (one of three chunks, one exactly a chunk), more
    requests than slots so every slot is reused, different budgets (one of
    a single token: its length is known before any id is read), and a
    prefix hit."""
    sched = _sched(model, slots=3)
    shared = _prompt(2 * PAGE + 3, 40)
    requests = [{"prompt": _prompt(n, 3 * n), "new": new}
                for n, new in ((3, 9), (CHUNK, 5), (2 * CHUNK + 5, 12),
                               (5, 1), (11, 7), (2, 10), (CHUNK + 1, 3))]
    requests.append({"prompt": shared, "new": 6})
    try:
        served = _serve(sched, requests)
        hit = {"prompt": shared[:2 * PAGE] + [9, 8, 7], "new": 6}
        served += _serve(sched, [hit])
        stats = sched.stats()
    finally:
        sched.shutdown()
    for r, (out, end) in zip(requests + [hit], served):
        assert end == ("end", "length")
        assert out == _oracle(model, r["prompt"], r["new"]), r
    assert stats["prefix_hit_tokens"] == 2 * PAGE
    assert stats["compiled_programs"] == 2
    assert stats["discarded_rows"] == 0  # no EOS, nothing cancelled
    assert stats["tokens_generated"] == sum(r["new"] for r in requests) + 6
    assert stats["runahead_steps"] > 0
    if model[0].mlp == "moe":
        cfg = model[0]
        assert stats["moe_rows_routed"] == (
            stats["moe_live_rows"] * cfg.moe_top_k * cfg.num_layers)


def test_eos_arrives_a_step_late_and_nothing_is_emitted_past_it(model):
    """The host learns of an EOS when it reads the id, by which time the
    row rides in the next step: that row is computed, discarded and never
    emitted, and the sequence seated next in the slot is untouched."""
    prompt, follower = _prompt(6, 11), _prompt(9, 90)
    free = _oracle(model, prompt, 12)
    # the first token of the free-running stream that did not occur before
    at = next(i for i in range(2, 12) if free[i] not in free[:i])
    sched = _sched(model, slots=1, eos_id=free[at])
    try:
        (out, end), (after, end2) = _serve(
            sched, [{"prompt": prompt, "new": 12},
                    {"prompt": follower, "new": 8}])
        stats = sched.stats()
    finally:
        sched.shutdown()
    assert (out, end) == (free[:at + 1], ("end", "eos"))
    want = _oracle(model, follower, 8)
    if free[at] in want:  # the follower may meet the same EOS
        want = want[:want.index(free[at]) + 1]
    assert after == want and end2[0] == "end"
    assert stats["discarded_rows"] >= 1
    assert stats["tokens_generated"] == len(out) + len(after)


def test_a_cancellation_while_a_step_is_in_flight(model):
    """Cancelled right after its third step was dispatched: the ids of the
    second and third step are still unread, both rows are discarded, the
    consumer has the first token and the first step's and then the end."""
    sched = _sched(model, slots=1)
    target = []
    sched._step = _AfterStep(sched._step, 3,
                             lambda: sched.cancel(target[0]))
    follower = _prompt(7, 60)
    try:
        (out, end), (after, end2) = _serve(
            sched, [{"prompt": _prompt(5, 21), "new": 10},
                    {"prompt": follower, "new": 6}],
            on_submit=lambda i, seq: target.append(seq) if i == 0 else None)
        stats = sched.stats()
    finally:
        sched.shutdown()
    assert end == ("end", "cancelled")
    assert out == _oracle(model, _prompt(5, 21), 10)[:2]
    assert (after, end2) == (_oracle(model, follower, 6), ("end", "length"))
    assert stats["discarded_rows"] == 2
    assert stats["tokens_generated"] == 2 + 6


def test_an_exhausted_pool_while_a_step_is_in_flight(model):
    """Two sequences outgrow a pool of 8 pages: the one that asks for the
    ninth fails cleanly with its newest row still in flight (discarded),
    the other runs to its end on the oracle's tokens, and so does the
    sequence seated next on the freed pages."""
    a, b, c = _prompt(8, 5), _prompt(6, 130), _prompt(10, 77)
    sched = _sched(model, slots=2, kv_pages=9, prefix_cache=False,
                   arena_len=32)
    try:
        first = _serve(sched, [{"prompt": a, "new": 12},
                               {"prompt": b, "new": 12}])
        (after, end3), = _serve(sched, [{"prompt": c, "new": 9}])
        stats = sched.stats()
    finally:
        sched.shutdown()
    failed = [i for i, (_, end) in enumerate(first) if end[0] == "err"]
    assert len(failed) == 1, first
    for i, (prompt, (out, end)) in enumerate(zip((a, b), first)):
        want = _oracle(model, prompt, 12)
        if i in failed:
            assert "out of pages" in end[1]
            assert 0 < len(out) < 12 and out == want[:len(out)]
        else:
            assert (out, end) == (want, ("end", "length"))
    assert (after, end3) == (_oracle(model, c, 9), ("end", "length"))
    assert stats["discarded_rows"] >= 1
    assert stats["pages_in_use"] == 0


# --------------------------------------------------------- the mechanism


class _ReadLog:
    """Stands where the scheduler keeps ``jax``: logs every blocking read
    by the array it waits for."""

    def __init__(self, real, log):
        self._real, self._log = real, log

    def block_until_ready(self, x):
        self._log.append(("read", id(x)))
        return self._real.block_until_ready(x)

    def __getattr__(self, name):
        return getattr(self._real, name)


def test_a_full_arena_runs_ahead_and_reads_no_step_before_the_next_is_out(
        dense):
    """32 slots, 32 requests: every decode step but the first is
    dispatched while its predecessor's ids are unread, the thread's only
    blocking reads between two dispatches are of OLDER programs, and the
    pipeline runs empty only at the end."""
    sched = _sched(dense, slots=32, kv_pages=32 * 12 + 1)
    log, keep = [], []
    step = sched._step

    def logged(*args):
        out = step(*args)
        keep.append(out[0])  # keep the ids alive: id() must stay unique
        log.append(("step", id(out[0])))
        return out

    logged._cache_size = step._cache_size
    sched._step = logged
    sched._jax = _ReadLog(sched._jax, log)
    requests = [{"prompt": _prompt(3 + i % 5, i), "new": 40}
                for i in range(32)]
    try:
        served = _serve(sched, requests)
        stats = sched.stats()
    finally:
        sched.shutdown()
    for r, (out, end) in zip(requests, served):
        assert (out, end) == (_oracle(dense, r["prompt"], 40),
                              ("end", "length"))
    steps = stats["decode_steps"]
    assert steps >= 40 and stats["max_active_slots"] == 32
    assert stats["runahead_steps"] == steps - 1
    assert stats["runahead_steps"] / steps > 0.9
    assert 1 <= stats["pipeline_drains"] <= 2
    assert stats["discarded_rows"] == 0
    order = [i for kind, i in log if kind == "step"]
    at = {entry: n for n, entry in enumerate(log)}
    for this, following in zip(order, order[1:]):
        assert at[("step", following)] < at[("read", this)]


def test_a_programs_tokens_leave_with_one_wake_up_of_the_loop(dense):
    """The hand-off to the consumers' event loop is one call a program read,
    however many rows it carried (a call is a point at which the loop's
    thread takes the interpreter lock): four streams of ten tokens cross in
    about as many calls as there were steps, in order, ends included."""
    import threading

    class Loop:  # runs the call where it is made, and counts
        calls = 0

        def call_soon_threadsafe(self, fn, *args):
            Loop.calls += 1
            fn(*args)

    class Queue:
        def __init__(self):
            self.items, self.ended = [], threading.Event()

        def put_nowait(self, item):
            self.items.append(item[:2])
            if item[0] != "tok":
                self.ended.set()

    sched = _sched(dense, slots=4)
    prompts = [_prompt(3 + i, 17 * i) for i in range(4)]
    queues = [Queue() for _ in prompts]
    try:
        for prompt, queue in zip(prompts, queues):
            sched.submit(prompt, max_new_tokens=10, loop=Loop(), queue=queue)
        assert all(q.ended.wait(120) for q in queues)
        stats = sched.stats()
    finally:
        sched.shutdown()
    for prompt, queue in zip(prompts, queues):
        assert queue.items == [("tok", t) for t in _oracle(dense, prompt, 10)
                               ] + [("end", "length")]
    # every request has its own Loop object here: at most one call a
    # request a program, and far fewer than one a token when they share
    assert stats["tokens_generated"] == 40
    assert Loop.calls <= 4 * (stats["decode_steps"] + 1)
    shared = _sched(dense, slots=4)
    Loop.calls, loop = 0, Loop()
    queues = [Queue() for _ in prompts]
    try:
        for prompt, queue in zip(prompts, queues):
            shared.submit(prompt, max_new_tokens=10, loop=loop, queue=queue)
        assert all(q.ended.wait(120) for q in queues)
        stats = shared.stats()
    finally:
        shared.shutdown()
    assert Loop.calls <= stats["decode_steps"] + 4 < 40


def test_a_lone_short_request_drains_and_parks(dense):
    """One request of one token: nothing to run ahead of. The chunk's id is
    read with nothing queued behind it (a drain), no step ever runs."""
    sched = _sched(dense)
    try:
        (out, end), = _serve(sched, [{"prompt": _prompt(4), "new": 1}])
        stats = sched.stats()
    finally:
        sched.shutdown()
    assert (out, end) == (_oracle(dense, _prompt(4), 1), ("end", "length"))
    assert (stats["decode_steps"], stats["runahead_steps"],
            stats["pipeline_drains"]) == (0, 0, 1)


# ------------------------------------------------------ above temperature 0


def test_the_device_sampler_draws_from_the_softmax():
    """4,000 draws off one row of logits at T = 0.7, keys fixed (one seed,
    4,000 positions), against ``softmax(logits / T)`` by chi-square (15
    degrees of freedom: 37.7 is the 0.1% point); greedy rows of the same
    call take the argmax."""
    n, T = 4000, 0.7
    row = np.asarray([2.0, 1.5, 1.0, 0.5, 0.0, -0.5, 2.2, 0.3, 1.1, -1.0,
                      0.9, 1.9, 0.1, -0.2, 1.3, 0.7], np.float32)
    logits = jnp.asarray(np.tile(row, (n + 2, 1)))
    temperature = jnp.asarray([T] * n + [0.0, 0.0], jnp.float32)
    ids = np.asarray(jax.jit(sample_token)(
        logits, temperature, jnp.full(n + 2, 77, jnp.uint32),
        jnp.arange(n + 2, dtype=jnp.int32)))
    assert ids.dtype == np.int32
    assert list(ids[n:]) == [int(row.argmax())] * 2
    p = np.exp(row / T - (row / T).max())
    p /= p.sum()
    seen = np.bincount(ids[:n], minlength=len(row))
    chi2 = float(((seen - n * p) ** 2 / (n * p)).sum())
    assert chi2 < 37.7, (chi2, seen)


def test_a_key_is_the_seed_and_the_position_and_greedy_calls_draw_nothing():
    logits = jnp.asarray(np.random.default_rng(0).normal(size=(6, 64)),
                         jnp.float32)
    hot = jnp.full(6, 1.0, jnp.float32)
    seeds = jnp.asarray([1, 1, 2, 2, 1, 1], jnp.uint32)
    positions = jnp.asarray([5, 6, 5, 6, 5, 6], jnp.int32)
    same = np.asarray(sample_token(jnp.tile(logits[:1], (6, 1)), hot, seeds,
                                   positions))
    # rows 0 and 4, 1 and 5: same seed, same position, other row
    assert same[0] == same[4] and same[1] == same[5]
    assert len({(int(s), int(p), int(t))
                for s, p, t in zip(seeds, positions, same)}) == 4
    # a call all of whose rows are greedy takes the branch without a key
    text = str(jax.make_jaxpr(sample_token)(logits, hot, seeds, positions))
    assert "cond" in text and "random_bits" in text
    greedy = np.asarray(sample_token(logits, jnp.zeros(6), seeds, positions))
    assert list(greedy) == list(np.asarray(logits).argmax(-1))


def test_a_sampled_stream_is_the_same_alone_and_in_a_full_batch(dense):
    """Temperature 0.8, one seed: alone in slot 0 of an idle arena, and
    again admitted last among four (another slot, every row live)."""
    hot = {"prompt": _prompt(9, 33), "new": 10, "temperature": 0.8,
           "seed": 2_147_483_659}  # more than 31 bits, as a driver's seed
    others = [{"prompt": _prompt(4 + i, 50 * i), "new": 14,
               "temperature": 0.5 * (i % 2), "seed": i} for i in range(3)]
    sched = _sched(dense, slots=4)
    try:
        (alone, end), = _serve(sched, [hot])
        crowd = _serve(sched, others + [hot])
        (again, _), = _serve(sched, [hot])
        stats = sched.stats()
    finally:
        sched.shutdown()
    assert end == ("end", "length") and len(alone) == 10
    assert crowd[-1][0] == alone == again
    assert alone != _oracle(dense, hot["prompt"], 10)  # it did sample
    # greedy neighbours of a sampling row are the oracle's all the same
    for r, (out, _) in zip(others, crowd):
        if r["temperature"] == 0:
            assert out == _oracle(dense, r["prompt"], 14)
    assert stats["compiled_programs"] == 2
