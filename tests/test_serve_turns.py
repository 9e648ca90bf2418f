"""The scheduler measures the gap it makes (ISSUE 39): every emitted token
that is not its sequence's first has a gap, taken between the two READS that
emitted it and the token before it, and put down to what the device was given
in between — decode work only (``gap_plain_*``) or prompt tokens too
(``gap_prefill_*``) — from the scheduler's own count of the prompt tokens it
dispatched, never from which program ran. One stamp a read, one
``serve.turn`` record a read that emitted, in the flight ring only. CPU, toy
model: what is asserted is counts, identities and structure; a time is only
ever compared with the stamps the scheduler itself handed out."""

import asyncio
import sys
import threading
import time

import pytest

from ray_tpu._private import flight
from ray_tpu.serve._private import continuous
from ray_tpu.serve.llm import LLMServerImpl

CHUNK = 8
GAP_KEYS = ("gap_plain_tokens", "gap_prefill_tokens", "first_tokens",
            "tokens_generated", "prefill_tokens", "prefill_chunks")
# the late request's prompt: five chunks, of which only the last is read
LONG = 4 * CHUNK + 5
# the first request decodes long enough to be live through all of them
NEW, LATE_NEW = 100, 6
TOKENS = NEW + LATE_NEW
SCRIPT = dict(first={"prompt": 5, "new": NEW},
              late={"prompt": LONG, "new": LATE_NEW})


@pytest.fixture(autouse=True)
def recorder_on():
    was = flight.is_enabled()
    flight.configure(enabled=True)
    yield
    flight.configure(enabled=was)


def _server(**kw):
    kw.setdefault("slots", 2)
    kw.setdefault("prefill_chunk", CHUNK)
    kw.setdefault("prefix_cache", False)
    return LLMServerImpl(preset="llama_debug", max_new_tokens=6,
                         share_weights=False, **kw)


def _prompt(n, start=1):
    return [(start + 7 * i) % 250 + 1 for i in range(n)]


def _delta(after, before, keys=GAP_KEYS):
    return {k: after[k] - before[k] for k in keys}


def _admission_in_mid_decode(sched, first=None, late=None):
    """``first`` decodes alone; once three of its tokens have arrived,
    ``late`` (a prompt of several chunks) is admitted beside it. Returns
    each request's raw items, ``(kind, value, stamp)``, in order."""
    first, late = first or SCRIPT["first"], late or SCRIPT["late"]

    async def one(request, start, three_arrived, waits):
        if waits:
            await three_arrived.wait()
        queue = asyncio.Queue()
        sched.submit(_prompt(request["prompt"], start),
                     max_new_tokens=request["new"],
                     loop=asyncio.get_running_loop(), queue=queue)
        items = []
        while True:
            items.append(await queue.get())
            if len(items) == 3:
                three_arrived.set()
            if items[-1][0] != "tok":
                return items

    async def drive():
        three_arrived = asyncio.Event()
        return await asyncio.gather(one(first, 3, three_arrived, False),
                                    one(late, 40, three_arrived, True))

    return asyncio.run(drive())


def _turns_of(sched):
    """The ``serve.turn`` records of one scheduler's thread: its instants'
    arguments unpacked, and its spans."""
    tid = f"({sched._thread.ident})"
    mine = [e for e in flight.local_timeline()
            if e["name"] == "serve.turn" and e.get("tid", "").endswith(tid)]
    return ([continuous.unpack_turn(e["args"]["arg"]) for e in mine
             if e["ph"] == "i"], [e for e in mine if e["ph"] == "X"])


@pytest.fixture(scope="module")
def scripted():
    """One fresh replica, warmed (both programs compiled), then the
    scripted run: its stats before and after, and every item it handed out."""
    flight.configure(enabled=True)
    srv = _server()
    sched = srv._sched
    _admission_in_mid_decode(sched, {"prompt": 3, "new": 4},
                             {"prompt": CHUNK + 1, "new": 2})
    before = sched.stats()
    metric_before = continuous._m_tokens.total()
    items = _admission_in_mid_decode(sched)
    after = sched.stats()
    yield {"sched": sched, "before": before, "after": after, "items": items,
           "metric": continuous._m_tokens.total() - metric_before}
    srv.shutdown()


# -------------------------------------------------------------- the identity


def test_every_token_is_a_first_token_or_has_a_gap_of_one_kind(scripted):
    d = _delta(scripted["after"], scripted["before"])
    assert d["tokens_generated"] == TOKENS and d["first_tokens"] == 2
    assert (d["gap_plain_tokens"] + d["gap_prefill_tokens"]
            + d["first_tokens"]) == d["tokens_generated"]
    # the live row's tokens read behind each of the late prompt's chunks
    assert d["gap_prefill_tokens"] == d["prefill_chunks"] - 1 == 5
    assert d["gap_plain_tokens"] == TOKENS - 2 - 5  # decode-only stretches


def test_a_chunk_turns_the_gap_of_the_row_it_takes_along_to_prefill(
        monkeypatch):
    """Since ISSUE 40 a dense model's chunk takes the live row along: each
    of the late prompt's chunks is ONE record that is both a chunk and a
    step, read once, and the row's token it carries counts a prefill gap —
    by what was DISPATCHED, as for the model whose chunk goes alone and is
    never read (``test_serve_fused_turn.py``, the two-program model)."""
    queued = []

    class Spy(continuous._Launched):
        __slots__ = ()

        def __init__(self, *args):
            super().__init__(*args)
            queued.append((self.chunk, self.step))

    monkeypatch.setattr(continuous, "_Launched", Spy)
    srv = _server()
    try:
        sched = srv._sched
        _admission_in_mid_decode(sched, {"prompt": 3, "new": 4},
                                 {"prompt": 2, "new": 2})
        del queued[:]
        before = sched.stats()
        _admission_in_mid_decode(sched)
        d = _delta(sched.stats(), before)
        assert d["prefill_chunks"] == 6
        # the first prompt's chunk found no row live; the late prompt's five
        # each carried the first's
        assert queued.count((True, False)) == 1
        assert queued.count((True, True)) == 5
        assert d["gap_prefill_tokens"] == 5
    finally:
        srv.shutdown()


def test_the_speculative_path_keeps_the_identity():
    """A round's accepted tokens are one read: the first of a row's tokens
    carries the gap (beside prefill if a chunk went out since its last
    round), the others of the same round a plain gap of no length."""
    srv = _server(drafter="self", spec_k=2)
    try:
        sched = srv._sched
        _admission_in_mid_decode(sched, {"prompt": 3, "new": 4},
                                 {"prompt": CHUNK + 1, "new": 2})
        before = sched.stats()
        metric_before = continuous._m_tokens.total()
        items = _admission_in_mid_decode(sched)
        after = sched.stats()
        d = _delta(after, before)
        assert after["spec_rounds"] > before["spec_rounds"]
        assert d["tokens_generated"] == TOKENS and d["first_tokens"] == 2
        assert (d["gap_plain_tokens"] + d["gap_prefill_tokens"]
                + d["first_tokens"]) == d["tokens_generated"]
        assert d["gap_prefill_tokens"] == 5
        assert d["prefill_tokens"] == 5 + LONG
        assert continuous._m_tokens.total() - metric_before == TOKENS
        _seconds_are_the_sequences_own(items, before, after)
        # tokens of one round share the round's stamp: fewer stamps than
        # tokens wherever a draft was accepted
        if after["spec_accepted_tokens"] > before["spec_accepted_tokens"]:
            stamps = {s for seq in items for k, _, s in seq if k == "tok"}
            assert len(stamps) < TOKENS
    finally:
        srv.shutdown()


def _seconds_are_the_sequences_own(items, before, after):
    stamps = [[s for kind, _, s in seq if kind == "tok"] for seq in items]
    assert all(s > 0 for seq in stamps for s in seq)
    assert all(seq == sorted(seq) for seq in stamps)
    own = sum(seq[-1] - seq[0] for seq in stamps) / 1e9
    counted = sum(after[k] - before[k]
                  for k in ("gap_plain_s", "gap_prefill_s"))
    assert own > 0 and counted == pytest.approx(own, abs=1e-6)
    assert after["gap_prefill_s"] > before["gap_prefill_s"]


def test_the_gaps_seconds_are_each_sequences_last_stamp_less_its_first(
        scripted):
    _seconds_are_the_sequences_own(scripted["items"], scripted["before"],
                                   scripted["after"])


def test_prefill_tokens_are_the_real_tokens_of_the_chunks_dispatched(
        scripted):
    d = _delta(scripted["after"], scripted["before"])
    assert d["prefill_tokens"] == 5 + LONG  # no prefix cache: the prompts
    assert d["prefill_chunks"] == 1 + 5     # of which pads count for none
    assert scripted["after"]["turns"] - scripted["before"]["turns"] >= NEW


def test_a_prefix_hit_is_no_prompt_token_dispatched():
    srv = _server(prefix_cache=True, page_tokens=4)
    try:
        sched = srv._sched
        _admission_in_mid_decode(sched, {"prompt": 3, "new": 4},
                                 {"prompt": LONG, "new": 2})
        before = sched.stats()
        _admission_in_mid_decode(sched, {"prompt": 3, "new": 4},
                                 {"prompt": LONG, "new": 2})
        after = sched.stats()
        hit = after["prefix_hit_tokens"] - before["prefix_hit_tokens"]
        assert hit >= 4 * CHUNK
        assert _delta(after, before)["prefill_tokens"] == 3 + LONG - hit
    finally:
        srv.shutdown()


def test_every_snapshot_of_the_counters_adds_up():
    """``stats()`` is read from other threads while the loop emits: three
    of them poll it through the scripted run, the interpreter switching
    threads every few bytecodes, and no snapshot shows a token counted and
    its gap not yet."""
    srv = _server()
    sched = srv._sched
    stop, torn, polls = threading.Event(), [], [0, 0, 0]

    def poll(i):
        while not stop.is_set():
            st = sched.stats()
            polls[i] += 1
            if (st["gap_plain_tokens"] + st["gap_prefill_tokens"]
                    + st["first_tokens"]) != st["tokens_generated"]:
                torn.append(st)

    threads = [threading.Thread(target=poll, args=(i,)) for i in range(3)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        _admission_in_mid_decode(sched)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
        sys.setswitchinterval(interval)
        srv.shutdown()
    assert not [t for t in threads if t.is_alive()]
    assert not torn and min(polls) > 10
    assert sched.stats()["tokens_generated"] == TOKENS


# ------------------------------------------------------------ one stamp a read


def test_the_items_of_one_read_carry_one_stamp(scripted):
    """Both rows' tokens of one step were read together: the stamps the two
    streams hold in common are the steps they shared, and every stamp is one
    ``serve.turn``."""
    a, b = ({s for kind, _, s in seq if kind == "tok"}
            for seq in scripted["items"])
    assert len(a) == NEW and len(b) == LATE_NEW
    # the late row's first token came from its last chunk, whose program
    # took the first row along (ISSUE 40): one read, one stamp for both
    assert len(a & b) == LATE_NEW
    turns, _ = _turns_of(scripted["sched"])
    assert len(a | b) == NEW <= len(turns)


def test_the_registrys_counter_still_equals_tokens_generated(scripted):
    d = _delta(scripted["after"], scripted["before"])
    assert scripted["metric"] == d["tokens_generated"] == TOKENS


# ------------------------------------------------------------------ serve.turn


def test_a_turn_is_in_the_flight_timeline_with_what_it_carried(scripted):
    sched, end = scripted["sched"], scripted["after"]
    turns, spans = _turns_of(sched)
    assert {t["kind"] for t in turns} == {"plain", "prefill"}
    assert all((t["kind"] == "prefill") == (t["prompt_tokens"] > 0)
               for t in turns)
    # the replica's whole life is in the ring: the turns carry every token
    # and every prompt token (the warm-up's too)
    assert sum(t["rows"] for t in turns) == end["tokens_generated"]
    assert sum(t["prompt_tokens"] for t in turns) == end["prefill_tokens"]
    assert [t for t in turns if t["rows"] == 2 and t["kind"] == "plain"]
    assert {"kind": "prefill", "rows": 1, "prompt_tokens": CHUNK} in turns
    # a span from the previous emitting read to this one; none over a pause
    assert 0 < len(spans) < len(turns)
    assert all(s["dur"] > 0 for s in spans)


def test_a_turn_is_no_host_event_of_a_profiler_session(tmp_path):
    """``perfbench/lib/trace.py`` labels an idle gap by the host event that
    overlaps it most: the phases stay the leaves there, and a turn, which
    encloses them, goes to the ring alone."""
    from perfbench.lib import trace

    srv = _server()
    try:
        sched = srv._sched
        _admission_in_mid_decode(sched, {"prompt": 3, "new": 4},
                                 {"prompt": CHUNK + 1, "new": 2})
        n0 = len(_turns_of(sched)[0])
        trace.start(str(tmp_path))
        _admission_in_mid_decode(sched, {"prompt": 5, "new": 12},
                                 {"prompt": CHUNK + 1, "new": 3})
        path = trace.stop(str(tmp_path))
        assert len(_turns_of(sched)[0]) > n0
        host = {name for name, _, _ in trace.load(path)["host"]}
        assert "serve.emit" in host and "serve.decode.wait" in host
        assert not [name for name in host if "serve.turn" in name]
    finally:
        srv.shutdown()


# ------------------------------------------------- by work, not by a name


def test_the_kind_is_decided_without_a_programs_name(scripted, monkeypatch):
    """The jitted functions under other names (what a fused turn, ROADMAP
    S3, would bring): the same script counts the same gaps."""
    real = continuous._program

    def renamed(fn, name, cfg, **keywords):
        return real(fn, "turn_" + name[::-1], cfg, **keywords)

    monkeypatch.setattr(continuous, "_program", renamed)
    srv = _server()
    try:
        sched = srv._sched
        assert sched._step.__name__ == "turn_pets_edoced_degap"
        _admission_in_mid_decode(sched, {"prompt": 3, "new": 4},
                                 {"prompt": CHUNK + 1, "new": 2})
        before = sched.stats()
        _admission_in_mid_decode(sched)
        assert _delta(sched.stats(), before) == _delta(scripted["after"],
                                                       scripted["before"])
    finally:
        srv.shutdown()


# ------------------------------------------------------------- recorder off


def test_recorder_off_counts_the_gaps_and_times_none():
    flight.configure(enabled=False)
    srv = _server()
    try:
        sched = srv._sched
        records = len(flight.local_timeline())
        items = _admission_in_mid_decode(sched)
        stats = sched.stats()
        assert {s for seq in items for _, _, s in seq} == {0}
        assert stats["gap_plain_s"] == stats["gap_prefill_s"] == 0
        assert stats["gap_prefill_tokens"] == 5
        assert (stats["gap_plain_tokens"] + stats["gap_prefill_tokens"]
                + stats["first_tokens"]) == stats["tokens_generated"] == TOKENS
        assert len(flight.local_timeline()) == records
    finally:
        srv.shutdown()


def test_span_between_records_the_two_stamps_it_is_given():
    name = flight.intern("t.between")
    t0 = flight.now()
    time.sleep(0.002)
    t1 = flight.now()
    flight.span_between(name, t0, t1)
    flight.span_between(name, 0, t1)  # no start yet: nothing
    spans = [e for e in flight.local_timeline() if e["name"] == "t.between"]
    assert len(spans) == 1 and spans[0]["ph"] == "X"
    assert spans[0]["dur"] == pytest.approx((t1 - t0) / 1e3)
