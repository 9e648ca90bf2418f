"""The serving layers on the profiler's clock (ISSUE 24): the scheduler
thread's time cut into leaf phases by one clock with three sinks (flight
ring, profiler host events, ``stats()`` seconds), a request's life as
instants that carry its id, the stall counter, the hand-off to the event
loop, and stable names for the scheduler's programs. CPU, toy model: what
is asserted is counts and structure; a time is only ever compared with a
time the test itself made pass (a sleep), never reported."""

import asyncio
import subprocess
import sys
import time

import pytest

from ray_tpu._private import flight
from ray_tpu.serve._private import continuous
from ray_tpu.serve.llm import LLMServerImpl

PHASE_KEYS = continuous._PHASE_KEYS


@pytest.fixture(autouse=True)
def recorder_on():
    was = flight.is_enabled()
    flight.configure(enabled=True)
    yield
    flight.configure(enabled=was)


def _server(**kw):
    kw.setdefault("slots", 2)
    kw.setdefault("prefill_chunk", 8)
    return LLMServerImpl(preset="llama_debug", max_new_tokens=6,
                         share_weights=False, **kw)


def _drive(srv, prompts, new_tokens=6, stream=False):
    async def one(p):
        req = {"prompt": p, "max_new_tokens": new_tokens, "stream": stream}
        out = await srv(req)
        if stream:
            return "".join([chunk async for chunk in out])
        return out["text"]

    async def go():
        return await asyncio.gather(*[one(p) for p in prompts])

    return asyncio.run(go())


def _delta(after, before, keys):
    return {k: after[k] - before[k] for k in keys}


class _SlowStep:
    """Test double of the decode step: sleeps before chosen calls, then
    runs the real program (same tokens)."""

    def __init__(self, step, sleeps):
        self._step, self._sleeps, self.calls = step, dict(sleeps), 0

    def __call__(self, *args):
        self.calls += 1
        time.sleep(self._sleeps.get(self.calls, self._sleeps.get("each", 0)))
        return self._step(*args)

    def _cache_size(self):
        return self._step._cache_size()


class _CancelMidPrefill:
    """Test double of the prefill chunk: once ``target`` has a chunk
    resident, cancels it (on the scheduler's thread, so before its next
    chunk), then runs the real program."""

    def __init__(self, sched):
        self._sched, self._prefill, self.target = sched, sched._prefill, None

    def __call__(self, *args):
        seq = self.target
        if seq is not None and seq.cursor > 0:
            self._sched.cancel(seq)
        return self._prefill(*args)

    def _cache_size(self):
        return self._prefill._cache_size()


# ------------------------------------------------------------- the clock


class TestPhaseClock:
    def test_phases_partition_the_time_between_start_and_stop(self):
        clock = flight.PhaseClock(["t.a", "t.b", "t.c"])
        t0 = time.perf_counter()
        clock.switch(0)
        time.sleep(0.02)
        clock.switch(1)
        clock.switch(1)  # the open phase again: no transition
        time.sleep(0.01)
        clock.switch(2)
        clock.stop()
        wall = time.perf_counter() - t0
        a, b, c = clock.seconds()
        assert a >= 0.02 and b >= 0.01
        assert a + b + c <= wall
        assert a + b + c >= 0.98 * wall - 1e-3
        spans = [e for e in flight.local_timeline()
                 if e.get("ph") == "X" and e["name"].startswith("t.")]
        assert [e["name"] for e in spans[-3:]] == ["t.a", "t.b", "t.c"]

    def test_the_open_phase_counts_up_to_now_and_lap_names_the_longest(self):
        clock = flight.PhaseClock(["t.x", "t.y"])
        clock.switch(0)
        time.sleep(0.02)
        assert clock.seconds()[0] >= 0.02  # still open
        clock.switch(1)
        time.sleep(0.002)
        clock.switch(0)
        t_ns, longest, ns = clock.lap()
        assert longest == 0 and ns >= 20_000_000 and t_ns > 0
        assert clock.lap()[1:] == (-1, 0)  # nothing closed since
        clock.stop()

    def test_recorder_off_stamps_nothing(self):
        flight.configure(enabled=False)
        clock = flight.PhaseClock(["t.off"])
        clock.switch(0)
        time.sleep(0.005)
        clock.stop()
        assert clock.seconds() == [0.0]
        assert clock.lap() == (0, -1, 0)

    def test_the_clock_imports_no_jax_into_a_process_without_it(self):
        code = ("import sys\n"
                "from ray_tpu._private import flight\n"
                "c = flight.PhaseClock(['a', 'b'])\n"
                "c.switch(0); c.switch(1); c.stop()\n"
                "assert c._annotate is None\n"
                "assert 'jax' not in sys.modules, 'jax was imported'\n")
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]


# ---------------------------------------------------- the scheduler's loop


@pytest.fixture(scope="module")
def warm():
    """One replica with both programs compiled (compiles are loop turns of
    seconds: every test below reads deltas after them)."""
    srv = _server()
    _drive(srv, ["warm a", "warm b"])
    yield srv
    srv.shutdown()


def test_phase_seconds_sum_to_the_scheduler_threads_wall_time(warm):
    t0 = time.perf_counter()
    before = warm.scheduler_stats()
    _drive(warm, [f"partition {i}" for i in range(5)])
    time.sleep(0.3)  # an idle stretch: serve.park is part of the partition
    after = warm.scheduler_stats()
    wall = time.perf_counter() - t0
    phases = _delta(after, before, PHASE_KEYS)
    assert abs(sum(phases.values()) - wall) <= 0.02 * wall
    for key in ("phase_admit_s", "phase_prefill_s", "phase_prefill_wait_s",
                "phase_decode_prepare_s", "phase_decode_wait_s",
                "phase_decode_fetch_s", "phase_sample_s", "phase_emit_s",
                "phase_park_s"):
        assert phases[key] > 0, key
    assert phases["phase_verify_s"] == phases["phase_migrate_s"] == 0
    assert after["compiled_programs"] == 2


def test_transitions_of_a_turn_do_not_grow_with_the_slots():
    """Sampling and emitting are two phases a step however many sequences
    are live: the spans of a run count the steps, not the tokens."""
    srv = _server(slots=4)
    try:
        _drive(srv, ["w"])
        n0 = len([e for e in flight.local_timeline()
                  if e.get("ph") == "X" and e["name"] == "serve.sample"])
        before = srv.scheduler_stats()
        _drive(srv, [f"many slots {i}" for i in range(4)])
        after = srv.scheduler_stats()
        n1 = len([e for e in flight.local_timeline()
                  if e.get("ph") == "X" and e["name"] == "serve.sample"])
        d = _delta(after, before, ("decode_steps", "prefill_chunks",
                                   "tokens_generated", "first_tokens",
                                   "fused_turns"))
        assert d["tokens_generated"] == 24 > d["decode_steps"]
        # one per program read that sampled for a row: a decode step, or a
        # prompt's last chunk, which are ONE program where the chunk took
        # the live rows along (ISSUE 40) and two reads where it went alone
        assert d["first_tokens"] == 4 and 0 < d["fused_turns"]
        assert (d["decode_steps"] < n1 - n0
                <= d["decode_steps"] + d["first_tokens"])
    finally:
        srv.shutdown()


def test_phases_and_programs_are_named_in_a_profiler_trace(warm, tmp_path):
    from perfbench.lib import trace

    before = warm.scheduler_stats()
    trace.start(str(tmp_path))
    _drive(warm, [f"traced {i}" for i in range(4)])
    time.sleep(0.2)  # parked: the next request ends the phase in session
    _drive(warm, ["traced 4"])
    path = trace.stop(str(tmp_path))
    ran = _delta(warm.scheduler_stats(), before,
                 ("decode_steps", "prefill_chunks", "fused_turns"))
    loaded = trace.load(path)
    host_names = {name for name, _, _ in loaded["host"]}
    for phase in continuous.PHASES[:9]:  # all but verify and migrate
        assert phase in host_names, phase
    summary = trace.summarize(loaded)
    programs = summary["programs"]
    assert not [p for p in programs if "unknown" in p], programs
    # a chunk's program that took decode rows along is a decode step too,
    # under the chunk's name: the plain step ran every other step
    assert (programs["jit_paged_decode_step"]["count"]
            == ran["decode_steps"] - ran["fused_turns"])
    assert ran["fused_turns"] > 0
    assert (programs["jit_paged_prefill_chunk"]["count"]
            == ran["prefill_chunks"] == 5)
    labels = [label for label, _ in summary["breakdown"]["idle_gaps"]]
    assert not [l for l in labels if "jit__unknown" in l], labels
    assert [l for l in labels if "| host: serve." in l], labels


def test_an_admission_runs_no_device_program(tmp_path):
    """The cursors are the scheduler's (ISSUE 27): whatever an admission
    finds in its slot — a longer sequence's pages and position, a cached
    prefix to start behind, a prompt cancelled between two chunks — the
    device runs the two named programs and nothing else, and serves what a
    fresh scheduler serves."""
    import dataclasses

    from perfbench.lib import trace

    long_a = "a long first occupant: forty-eight tokens, three pages"[:48]
    hit = long_a[:32] + " and another tail"
    gone = "cancelled between its chunks " * 3
    srv = _server()
    try:
        sched = srv._sched
        _drive(srv, ["warm a", "warm b"])
        cancel = sched._prefill = _CancelMidPrefill(sched)
        before = srv.scheduler_stats()
        trace.start(str(tmp_path))
        served = dict(zip((long_a, "b"), _drive(srv, [long_a, "b"])))
        # both slots held longer sequences: the cursors start over
        served.update(zip(("cd", "e"), _drive(srv, ["cd", "e"])))
        served[hit] = _drive(srv, [hit])[0]  # starts at cached_len = 32

        async def cancelled_mid_prefill():
            seq, q = srv._submit(srv._tokenize(gone), 6, 0.0)
            cancel.target = seq
            live = asyncio.ensure_future(srv({"prompt": "beside it"}))
            assert (await srv._next_item(q)) == ("end", "cancelled")
            assert 0 < seq.cursor < len(seq.prompt)
            return (await live)["text"]

        served["beside it"] = asyncio.run(cancelled_mid_prefill())
        cancel.target = None
        served["after"] = _drive(srv, ["after"])[0]  # the cancelled slot
        path = trace.stop(str(tmp_path))
        ran = _delta(srv.scheduler_stats(), before,
                     ("admitted", "retired", "prefix_hit_tokens"))
        assert ran == {"admitted": 8, "retired": 8, "prefix_hit_tokens": 32}
        programs = trace.summarize(trace.load(path))["programs"]
        assert sorted(programs) == ["jit_paged_decode_step",
                                    "jit_paged_prefill_chunk"], programs
        assert sched.compiled_programs() == 2
        assert [f.name for f in dataclasses.fields(sched._caches[0])] == [
            "k", "v"]
    finally:
        srv.shutdown()
    fresh = _server(prefix_cache=False)
    try:
        for prompt, text in served.items():
            assert _drive(fresh, [prompt]) == [text], prompt
    finally:
        fresh.shutdown()


def test_a_run_ahead_window_holds_the_two_programs_and_nothing_else(
        tmp_path):
    """The loop one step ahead (ISSUE 29), traced in its steady state with
    everything that strikes a step in flight: slots reused, a sampling row
    beside greedy ones (the draw is a branch INSIDE the step), a stream
    abandoned after its first token (its rows in flight are discarded).
    The device runs the two named programs and nothing else."""
    from perfbench.lib import trace

    srv = _server(slots=4)
    try:
        _drive(srv, ["warm a", "warm b"])
        before = srv.scheduler_stats()
        trace.start(str(tmp_path))

        async def go():
            async def abandoned():
                out = await srv({"prompt": "nobody reads this to its end",
                                 "max_new_tokens": 40, "stream": True})
                async for _ in out:
                    break
                await out.aclose()

            async def one(i):
                return (await srv({"prompt": f"ahead {i}" * (1 + i % 3),
                                   "max_new_tokens": 5 + i,
                                   "temperature": 0.9 * (i % 2)}))["text"]

            await asyncio.gather(abandoned(), *[one(i) for i in range(9)])

        asyncio.run(go())
        path = trace.stop(str(tmp_path))
        ran = _delta(srv.scheduler_stats(), before,
                     ("decode_steps", "prefill_chunks", "runahead_steps",
                      "pipeline_drains", "discarded_rows", "retired",
                      "fused_turns"))
        programs = trace.summarize(trace.load(path))["programs"]
        assert sorted(programs) == ["jit_paged_decode_step",
                                    "jit_paged_prefill_chunk"], programs
        assert programs["jit_paged_decode_step"]["count"] == ran[
            "decode_steps"] - ran["fused_turns"]
        assert programs["jit_paged_prefill_chunk"]["count"] == ran[
            "prefill_chunks"] >= ran["fused_turns"] > 0
        assert srv._sched.compiled_programs() == 2
        assert ran["retired"] == 10 and ran["discarded_rows"] >= 1
        # a step is ahead of the read before it unless it follows a drain
        # or a chunk that went alone (no row was live to take along)
        alone = ran["prefill_chunks"] - ran["fused_turns"]
        assert ran["runahead_steps"] >= ran["decode_steps"] - ran[
            "pipeline_drains"] - alone > 0
    finally:
        srv.shutdown()


def test_a_drain_is_an_instant_and_a_count(warm):
    """The thread reads a result with nothing queued behind it when a burst
    ends (and only then, without a drafter): each such read is a
    ``serve.drain`` instant and one of ``pipeline_drains``."""
    def drains():
        return len([e for e in flight.local_timeline()
                    if e.get("ph") == "i" and e["name"] == "serve.drain"])

    n0, before = drains(), warm.scheduler_stats()
    _drive(warm, ["burst a", "burst b", "burst c"])
    time.sleep(0.1)
    _drive(warm, ["alone"])
    d = _delta(warm.scheduler_stats(), before,
               ("pipeline_drains", "decode_steps", "runahead_steps",
                "discarded_rows", "prefill_chunks", "fused_turns"))
    assert drains() - n0 == d["pipeline_drains"]
    assert 2 <= d["pipeline_drains"] <= 4  # one an emptied arena, about
    # every step ahead but the one behind a drain or a chunk that went alone
    assert d["runahead_steps"] >= (
        d["decode_steps"] - d["pipeline_drains"]
        - (d["prefill_chunks"] - d["fused_turns"]))
    assert d["discarded_rows"] == 0


def test_the_scheduler_jits_named_functions():
    srv = _server()
    try:
        sched = srv._sched
        assert (sched._prefill.__name__, sched._step.__name__) == (
            "paged_prefill_chunk", "paged_decode_step")
        assert _drive(srv, ["named"])[0]
        assert sched.compiled_programs() == 2
    finally:
        srv.shutdown()


def test_the_verify_program_is_named_too():
    srv = _server(drafter="self", spec_k=2)
    try:
        assert srv._sched._verify.__name__ == "paged_verify_step"
        before = srv.scheduler_stats()
        _drive(srv, ["speculate"])
        after = srv.scheduler_stats()
        assert after["phase_verify_s"] > before["phase_verify_s"]
    finally:
        srv.shutdown()


# ------------------------------------------------------- a request's life


def test_queue_wait_is_at_least_the_time_a_request_was_held_back():
    srv = _server(slots=1)
    try:
        _drive(srv, ["w"])
        sched = srv._sched
        sched._step = _SlowStep(sched._step, {"each": 0.05})
        before = srv.scheduler_stats()
        # one slot: the second request waits for the first one's 5 decode
        # steps of at least 0.05 s each
        _drive(srv, ["first in", "held back"])
        after = srv.scheduler_stats()
        d = _delta(after, before, ("queue_wait_s", "first_token_wait_s",
                                   "admitted", "first_tokens"))
        assert d["admitted"] == d["first_tokens"] == 2
        assert d["queue_wait_s"] >= 5 * 0.05
        assert d["first_token_wait_s"] > 0
        from ray_tpu._private.metrics import default_registry

        assert ("ray_tpu_serve_queue_wait_seconds_count"
                in default_registry().render_prometheus())
    finally:
        srv.shutdown()


def test_a_stall_is_counted_with_the_phase_that_held_it():
    srv = _server()
    try:
        _drive(srv, ["w"])
        before = srv.scheduler_stats()
        _drive(srv, ["no stall here"])
        quiet = srv.scheduler_stats()
        assert _delta(quiet, before, ("stalls", "stall_s")) == {
            "stalls": 0, "stall_s": 0}
        sched = srv._sched
        sched._step = _SlowStep(sched._step, {2: 1.3})
        _drive(srv, ["the loop stands still"])
        after = srv.scheduler_stats()
        assert after["stalls"] - quiet["stalls"] == 1
        assert 0.25 <= after["stall_s"] - quiet["stall_s"] < 1.3
        assert after["stall_phase"] == "serve.decode.prepare"
        stall = [e for e in flight.local_timeline()
                 if e["name"] == "serve.stall"][-1]["args"]["arg"]
        assert continuous.PHASES[stall & 0xFF] == "serve.decode.prepare"
        assert 1.3e6 <= stall >> 8 < 3e6  # microseconds of the whole turn
    finally:
        srv.shutdown()


def test_the_instants_of_one_request_carry_one_id(warm):
    warm._seq_counter = 7000  # ids no other test's scheduler hands out
    _drive(warm, ["id a", "id b", "id c"])
    by_id = {}
    for e in flight.local_timeline():
        if e.get("ph") == "i" and e["args"]["arg"] in (7001, 7002, 7003):
            by_id.setdefault(e["args"]["arg"], []).append(e["name"])
    assert sorted(by_id) == [7001, 7002, 7003]
    for names in by_id.values():
        assert sorted(names) == ["serve.admit", "serve.first_token",
                                 "serve.req.queued", "serve.retire"]


def test_every_streamed_token_is_counted_at_the_hand_off(warm):
    before = warm.scheduler_stats()
    _drive(warm, ["streamed a", "streamed b"], stream=True)
    _drive(warm, ["whole"])
    after = warm.scheduler_stats()
    d = _delta(after, before, ("stream_tokens", "tokens_generated",
                               "stream_lag_s"))
    assert d["stream_tokens"] == d["tokens_generated"] == 18
    assert d["stream_lag_s"] > 0


def test_recorder_off_records_nothing_and_serves_the_same_tokens(warm):
    prompts = ["same tokens a", "same tokens b", "same tokens c"]
    with_recorder = _drive(warm, prompts)
    flight.configure(enabled=False)
    srv = _server()
    try:
        records = len(flight.local_timeline())
        assert _drive(srv, prompts) == with_recorder
        stats = srv.scheduler_stats()
        assert all(stats[k] == 0 for k in PHASE_KEYS)
        assert stats["stream_tokens"] == stats["stalls"] == 0
        assert stats["admitted"] == stats["first_tokens"] == 3  # counters
        assert len(flight.local_timeline()) == records
    finally:
        srv.shutdown()
