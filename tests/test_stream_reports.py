"""How yielded items leave a worker (ISSUE 37, layer "handle, router and
replica"): what an async actor's generators yield in one turn of its loop
goes to their owner in ONE ``stream_items`` report, and every item is still
an object of its own at the consumer, in order.

Counts and order only, never a time. The counters are the worker's
(``CoreWorker.stream_reports`` / ``stream_items_reported``), read through a
method of the actor that produces the streams.
"""

import asyncio
import time

import pytest

import ray_tpu
from ray_tpu._private import api, serialization
from ray_tpu._private.core_worker import INLINE, SHARED
from ray_tpu._private.ids import ObjectID


@pytest.fixture(scope="module")
def ray_init():
    info = ray_tpu.init(num_cpus=8, object_store_memory=128 * 1024 * 1024,
                        ignore_reinit_error=True)
    yield info
    ray_tpu.shutdown()


@ray_tpu.remote
class Fanout:
    """Streams fed the way the LLM scheduler feeds the replica's: one call
    puts an item into every stream's queue (``continuous._deliver``), so
    all the generators wake in the same turn of the actor's loop."""

    def __init__(self):
        self.queues = {}
        self.yielded = {}

    async def stream(self, sid):
        q = self.queues[sid] = asyncio.Queue()
        self.yielded[sid] = 0
        while True:
            item = await q.get()
            if item is None:
                return
            self.yielded[sid] += 1
            yield (sid, item)

    async def deliver(self, item, last=False):
        for q in self.queues.values():
            q.put_nowait(item)
            if last:
                q.put_nowait(None)  # the end in the same turn as the item
        return len(self.queues)

    async def state(self):
        core = api._core
        return {"streams": len(self.queues), "yielded": dict(self.yielded),
                "reports": core.stream_reports,
                "items": core.stream_items_reported}

    def numbers(self, n):  # a SYNC generator of an async actor
        for i in range(n):
            yield i

    async def paced(self, n):
        self.yielded["paced"] = 0
        for i in range(n):
            self.yielded["paced"] += 1
            yield i

    async def big_then_small(self, size):
        yield b"x" * size
        yield b"small"

    async def fails_after(self, n):
        for i in range(n):
            yield i
        raise ValueError("after the items")


def _until(predicate, what, tries=400):
    for _ in range(tries):
        if predicate():
            return
        time.sleep(0.025)
    raise AssertionError(f"never saw: {what}")


def _fanout(n_streams):
    actor = Fanout.options(max_concurrency=n_streams + 8).remote()
    gens = [actor.stream.options(num_returns="streaming").remote(s)
            for s in range(n_streams)]
    _until(lambda: ray_tpu.get(actor.state.remote())["streams"] == n_streams,
           "every generator parked on its queue")
    return actor, gens


def test_a_turns_items_leave_in_one_report_and_arrive_one_by_one(ray_init):
    n_streams, turns = 24, 6
    actor, gens = _fanout(n_streams)
    before = ray_tpu.get(actor.state.remote())
    for t in range(turns):
        assert ray_tpu.get(actor.deliver.remote(
            t, last=t == turns - 1)) == n_streams
        # each consumer sees that turn's item as a ref of its own
        for sid, g in enumerate(gens):
            assert ray_tpu.get(next(g)) == (sid, t)
    # the completion came behind the last item of every stream
    for g in gens:
        with pytest.raises(StopIteration):
            next(g)
    after = ray_tpu.get(actor.state.remote())
    assert after["items"] - before["items"] == n_streams * turns
    assert 1 <= after["reports"] - before["reports"] <= turns
    ray_tpu.kill(actor)


def test_done_never_overtakes_items_nobody_has_read_yet(ray_init):
    """Nothing is consumed until every stream has ended: each still hands
    out all its items, in order, then stops."""
    n_streams, turns = 8, 5
    actor, gens = _fanout(n_streams)
    for t in range(turns):
        ray_tpu.get(actor.deliver.remote(t, last=t == turns - 1))
    _until(lambda: all(api._core._streams[g.task_id()].finished
                       for g in gens),
           "every stream's completion at the owner")
    for sid, g in enumerate(gens):
        assert [ray_tpu.get(r) for r in g] == [(sid, t) for t in range(turns)]
    ray_tpu.kill(actor)


def test_a_released_consumer_stops_its_producer_and_no_other(ray_init):
    actor, gens = _fanout(3)
    ray_tpu.get(actor.deliver.remote(0))
    for sid, g in enumerate(gens):
        assert ray_tpu.get(next(g)) == (sid, 0)
    kept = [gens[0], gens[2]]
    del gens, g  # stream 1's consumer is gone
    # the released stream's answer is `stop`, in a report it shares with
    # the two that go on; its generator is left after at most the items
    # already on their way
    for t in range(1, 12):
        ray_tpu.get(actor.deliver.remote(t))
        for sid, g in zip((0, 2), kept):
            assert ray_tpu.get(next(g)) == (sid, t)
    yielded = ray_tpu.get(actor.state.remote())["yielded"]
    assert yielded[0] == yielded[2] == 12
    assert yielded[1] <= 4, yielded
    ray_tpu.kill(actor)


def test_a_replayed_report_is_idempotent_and_a_gap_is_the_streams_error(
        ray_init):
    actor, gens = _fanout(2)
    for t in range(3):
        ray_tpu.get(actor.deliver.remote(t, last=t == 2))
    core = api._core
    streams = [core._streams[g.task_id()] for g in gens]
    _until(lambda: all(s.finished for s in streams), "both completions")
    tid = [g.task_id().binary() for g in gens]
    # the same indices again (a retried frame, a re-execution's replay),
    # two streams in one report: nothing is added, nothing changes
    replay = [(tid[0], 0, "inline", serialization.pack((0, 0))),
              (tid[1], 0, "inline", serialization.pack((1, 0))),
              (tid[0], 1, "inline", serialization.pack((0, 1)))]
    for _ in range(2):
        answer = core._run(core.rpc_stream_items({"items": replay}))
        assert answer["streams"] == {
            tid[0]: {"consumed": 0, "stop": False},
            tid[1]: {"consumed": 0, "stop": False}}
    assert [len(s.items) for s in streams] == [3, 3]
    # a gap in ONE stream fails that stream and stops its producer; the
    # other stream of the same report is served
    answer = core._run(core.rpc_stream_items({"items": [
        (tid[0], 7, "inline", serialization.pack("lost")),
        (tid[1], 2, "inline", serialization.pack((1, 2)))]}))
    assert answer["streams"][tid[0]]["stop"] is True
    assert answer["streams"][tid[1]] == {"consumed": 0, "stop": False}
    assert [ray_tpu.get(r) for r in gens[1]] == [(1, t) for t in range(3)]
    got = []
    with pytest.raises(RuntimeError, match="stream item gap"):
        for r in gens[0]:
            got.append(ray_tpu.get(r))
    assert got == [(0, t) for t in range(3)]
    # a stream nobody holds any more answers `stop` and stores nothing
    gone = gens[1].task_id()
    core.stream_released(gone)  # what the consumer's __del__ calls
    _until(lambda: gone not in core._streams, "the release")
    answer = core._run(core.rpc_stream_items({"items": [
        (gone.binary(), 3, "inline", serialization.pack("late"))]}))
    assert answer["streams"] == {gone.binary(): {"consumed": 0, "stop": True}}
    assert ObjectID.for_task_return(gone, 3) not in core.objects
    ray_tpu.kill(actor)


def test_backpressure_holds_an_async_producer_at_its_window(ray_init):
    actor = Fanout.options(max_concurrency=8).remote()
    g = actor.paced.options(num_returns="streaming",
                            generator_backpressure=2).remote(9)

    def produced():
        return ray_tpu.get(actor.state.remote())["yielded"].get("paced", 0)

    _until(lambda: produced() == 2, "the producer at its window")
    for consumed in range(1, 10):
        # nothing read since: the producer has not moved, however often
        # it is asked
        lead = [produced() - (consumed - 1) for _ in range(5)]
        assert max(lead) <= 2, (consumed, lead)
        assert ray_tpu.get(next(g)) == consumed - 1
        _until(lambda: produced() == min(consumed + 2, 9),
               "the window moving with the consumer")
    with pytest.raises(StopIteration):
        next(g)
    ray_tpu.kill(actor)


def test_a_large_item_of_an_async_generator_goes_by_the_shared_store(
        ray_init):
    actor = Fanout.remote()
    size = api._core.config.max_direct_call_object_size + 50_000
    g = actor.big_then_small.options(num_returns="streaming").remote(size)
    big, small = next(g), next(g)
    core = api._core
    assert core.objects[big._object_id].state == SHARED
    assert core.objects[small._object_id].state == INLINE
    assert ray_tpu.get(big) == b"x" * size
    assert ray_tpu.get(small) == b"small"
    with pytest.raises(StopIteration):
        next(g)
    ray_tpu.kill(actor)


def test_an_async_generators_error_comes_after_its_items(ray_init):
    actor = Fanout.remote()
    g = actor.fails_after.options(num_returns="streaming").remote(4)
    got = []
    with pytest.raises(Exception, match="after the items"):
        for r in g:
            got.append(ray_tpu.get(r))
    assert got == [0, 1, 2, 3]
    ray_tpu.kill(actor)


@pytest.mark.parametrize("method", ["numbers", "paced"])
def test_a_lone_stream_reports_every_item(ray_init, method):
    """A sync generator reports a list of one an item and waits for the
    answer, as before; a lone async stream needs no more reports than
    items."""
    actor = Fanout.remote()
    before = ray_tpu.get(actor.state.remote())
    g = getattr(actor, method).options(num_returns="streaming").remote(7)
    assert [ray_tpu.get(r) for r in g] == list(range(7))
    after = ray_tpu.get(actor.state.remote())
    assert after["items"] - before["items"] == 7
    reports = after["reports"] - before["reports"]
    assert reports == 7 if method == "numbers" else 1 <= reports <= 7
    ray_tpu.kill(actor)


def test_values_reads_and_releases_every_item_in_order(ray_init):
    """``ObjectRefGenerator.values()`` (what a streamed handle response
    iterates): the values in order, a large item by the shared store, and
    nothing left in the owner's store behind it."""
    actor = Fanout.remote()
    core = api._core
    size = core.config.max_direct_call_object_size + 50_000
    g = actor.big_then_small.options(num_returns="streaming").remote(size)
    tid = g.task_id()
    assert list(g.values()) == [b"x" * size, b"small"]
    with pytest.raises(StopIteration):
        next(g)
    _until(lambda: not any(ObjectID.for_task_return(tid, i) in core.objects
                           for i in range(2)), "both items released")
    g = actor.fails_after.options(num_returns="streaming").remote(3)
    got = []
    with pytest.raises(Exception, match="after the items"):
        for value in g.values():
            got.append(value)
    assert got == [0, 1, 2]
    ray_tpu.kill(actor)
