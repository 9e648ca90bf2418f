"""Chaos soak: sweep fault-schedule seeds against the multinode harness.

Each seed drives one deterministic fault schedule (message drop/duplicate/
delay on the control RPCs, plus supervisor + worker kills) under a real
task + actor + training workload, and asserts end-state correctness — the
same workload ``tests/test_chaos.py`` runs on its fixed seeds. The sweep
prints the first failing seed so it can be handed straight back to the test
suite (or this script) for bisection and replay:

    python -m ray_tpu.scripts.chaos_soak --seeds 20          # sweep 0..19
    python -m ray_tpu.scripts.chaos_soak --one 13            # replay seed 13

Seeds run in subprocesses so one seed's daemons/env can never bleed into the
next schedule.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

# the control RPCs worth attacking; health probes (ping) are excluded so a
# node is only declared dead when a kill really happened
CHAOS_METHODS = ",".join([
    "request_lease", "push_task", "push_task_batch",
    "task_done", "task_done_batch", "get_object",
    "actor_register", "actor_ready", "worker_register", "worker_died",
    "kv_put", "job_new", "node_sync",
    "store_create", "store_seal", "store_locate",
    # zero-copy data plane: batched pinned locates, coalesced unpins, and
    # the pipelined cross-node chunk stream (chunk reads are idempotent;
    # pin-taking RPCs ride the replay cache, so drop/dup must converge)
    "store_locate_batch", "store_unpin", "store_unpin_batch",
    "store_read_chunk", "pull_object",
    # compiled-graph channels: creation is replay-cached (mints an arena
    # range + a pin), the per-step push/commit carry absolute versions so
    # dropped/duplicated frames must converge, and close is idempotent
    "channel_create", "channel_push", "channel_write_chunk",
    "channel_commit", "channel_close",
    # non-RPC seqlock perturbation points inside the shm channel protocol
    # (chaos.maybe_delay): the method filter applies to these names too,
    # so they must be listed or the in-process write/read/ack timing is
    # never perturbed
    "channel.write", "channel.read", "channel.ack",
    # p2p collectives: ring segments stream as idempotent offset-keyed
    # chunk frames (drop/dup/retry must converge to exact sums), and the
    # controller rendezvous rides the kv_wait long-poll
    "collective_chunk", "kv_wait",
])


# seed of the workload currently running in THIS process (--one mode);
# _maybe_flight_dump names its artifact after it
_CURRENT_SEED: int | None = None


def _maybe_flight_dump() -> None:
    """Dump a merged flight timeline while the seed's cluster is still
    up — unconditionally when ``--flight-dump <dir>`` was given, and
    AUTOMATICALLY when unwinding an exception (so a red seed leaves a
    debuggable Perfetto trace instead of just an exit code). Runs inside
    each workload's ``finally`` before teardown; falls back to this
    driver's own rings if the cluster is already unreachable."""
    dump_dir = os.environ.get("RAY_TPU_CHAOS_FLIGHT_DUMP", "")
    failing = sys.exc_info()[0] is not None
    if not dump_dir and not failing:
        return
    import tempfile

    if not dump_dir:
        dump_dir = os.path.join(tempfile.gettempdir(), "chaos_flight")
    tag = "fail" if failing else "ok"
    seed = "x" if _CURRENT_SEED is None else _CURRENT_SEED
    path = os.path.join(dump_dir, f"flight_seed{seed}_{tag}.json")
    try:
        os.makedirs(dump_dir, exist_ok=True)
        import ray_tpu
        from ray_tpu._private import flight
        from ray_tpu.util import state

        if ray_tpu.is_initialized():
            try:
                events = state.flight_timeline(path)
            except Exception:
                events = flight.local_timeline(path)
        else:
            events = flight.local_timeline(path)
        print(f"flight timeline ({len(events)} events) -> {path}")
    except Exception as e:  # noqa: BLE001 — the dump must never mask
        print(f"flight dump failed: {e!r}")  # the workload's own error


def run_chaos_workload(
    seed: int,
    *,
    drop_prob: float = 0.02,
    dup_prob: float = 0.05,
    delay_prob: float = 0.05,
    delay_max_ms: int = 20,
    kills: bool = True,
    train: bool = True,
    controller_restart: bool = False,
) -> None:
    """One seeded chaos run. Raises AssertionError / propagates any failure.

    Builds a 2-node cluster whose daemons (and this driver process) all run
    the seed's fault schedule, then drives:
      * a fan of tasks spread across both nodes,
      * an actor with calls in flight,
      * a worker kill (task that hard-exits its process once) and a
        supervisor kill (the 'doomed' node dies mid-run, a replacement
        joins),
      * a 2-worker data-parallel training run with checkpoint restore,
    and asserts every result is correct and no pending RPC futures leaked.
    """
    import tempfile

    import ray_tpu
    from ray_tpu._private import chaos
    from ray_tpu._private.chaos import FaultController
    from ray_tpu._private.config import Config
    from ray_tpu.cluster_utils import Cluster

    cfg = Config.from_env()
    cfg.chaos_seed = seed
    cfg.chaos_drop_prob = drop_prob
    cfg.chaos_dup_prob = dup_prob
    cfg.chaos_delay_prob = delay_prob
    cfg.chaos_delay_max_ms = delay_max_ms
    cfg.chaos_methods = CHAOS_METHODS
    # small chunks so the ~3 MB cross-node object below streams as many
    # chunk RPCs — the pipelined-transfer path the schedule attacks
    cfg.object_transfer_chunk_bytes = 256 * 1024

    cluster = Cluster(config=cfg)
    workdir = tempfile.mkdtemp(prefix=f"chaos_seed{seed}_")
    try:
        cluster.add_node(num_cpus=4, resources={"stable": 100})
        doomed = cluster.add_node(num_cpus=2, resources={"doomed": 100})
        cluster.wait_for_nodes(2)
        ray_tpu.init(address=cluster.address)
        # the driver speaks the same fault schedule as the daemons
        chaos.set_fault_controller(FaultController(
            seed=seed, drop_prob=drop_prob, dup_prob=dup_prob,
            delay_prob=delay_prob, delay_max_ms=delay_max_ms,
            methods=CHAOS_METHODS))

        @ray_tpu.remote
        def square(x):
            time.sleep(0.05)
            return x * x

        @ray_tpu.remote
        class Counter:
            def __init__(self):
                self.n = 0

            def incr(self):
                self.n += 1
                return self.n

            def total(self):
                return self.n

        @ray_tpu.remote
        def crash_once(marker):
            # first execution kills the worker process mid-task; the retry
            # (a fresh worker) succeeds — a deterministic worker kill
            if not os.path.exists(marker):
                open(marker, "w").write("x")
                os._exit(1)
            return "survived"

        @ray_tpu.remote
        def on_doomed():
            time.sleep(2.0)
            return "done"

        @ray_tpu.remote
        def make_big():
            import numpy as np
            return np.arange(400_000, dtype=np.float64)  # ~3 MB, chunked

        refs = [square.remote(i) for i in range(16)]
        # lands on the doomed node's arena: the cross-node pull races the
        # node kill, and the post-kill get exercises lineage
        # reconstruction + a second chunked transfer
        big_ref = make_big.options(resources={"doomed": 1}).remote()
        counter = Counter.options(resources={"stable": 1},
                                  max_restarts=3).remote()
        incs = [counter.incr.remote() for _ in range(10)]
        crash_ref = crash_once.options(max_retries=2).remote(
            os.path.join(workdir, "crash_marker"))
        doomed_refs = [on_doomed.options(resources={"doomed": 1}).remote()
                       for _ in range(2)]

        if kills:
            time.sleep(0.5)  # let doomed-node tasks start
            cluster.remove_node(doomed)  # supervisor kill mid-run
            cluster.add_node(num_cpus=2, resources={"doomed": 100})
            cluster.wait_for_nodes(2)

        if controller_restart:
            # controller SIGKILL + restart with tasks/actor calls in
            # flight (the default sweep's controller-HA coverage; the
            # dedicated --controller mode attacks the tentpole
            # workloads): recovery from WAL+snapshot, supervisors
            # re-register, every in-flight result below must stay exact
            cluster.restart_controller()
            cluster.wait_for_nodes(2, timeout=60)

        # compiled-graph channels under the same schedule: a 2-stage
        # cross-node pipeline (stable -> replacement node) whose per-step
        # pushes stream ~4 chunk frames each through the attacked
        # channel_write_chunk/commit path; results must stay exact
        import numpy as np

        @ray_tpu.remote
        class ChanStage:
            def mul2(self, x):
                return x * 2.0

        cs_a = ChanStage.options(resources={"stable": 1}).remote()
        cs_b = ChanStage.options(resources={"doomed": 1}).remote()
        ray_tpu.get([cs_a.mul2.remote(1.0), cs_b.mul2.remote(1.0)],
                    timeout=120)
        from ray_tpu.dag import InputNode

        with InputNode() as inp:
            chan_dag = cs_b.mul2.bind(cs_a.mul2.bind(inp))
        compiled = chan_dag.experimental_compile()
        # a chaos-induced compile failure falls back to dynamic execution,
        # which would pass the exactness asserts while attacking none of
        # the channel RPCs — the soak must fail loudly instead
        assert compiled.is_channel_backed, (
            "compiled-channel section fell back to dynamic execution")
        try:
            for i in range(4):
                arr = np.full(120_000, float(i))  # ~1 MB -> chunked push
                out = ray_tpu.get(compiled.execute(arr), timeout=120)
                assert np.array_equal(out, arr * 4.0), (
                    "compiled-channel pipeline corrupted under chaos")
        finally:
            compiled.teardown()

        # training runs FIRST so the tasks/actor calls above settle (with
        # their retries) concurrently under it — the asserts below are then
        # cheap resolutions instead of serial waits
        if train:
            from ray_tpu.air.config import (FailureConfig, RunConfig,
                                            ScalingConfig)
            from ray_tpu.train import DataParallelTrainer
            from ray_tpu.train._checkpoint import Checkpoint
            from ray_tpu.train._internal.session import get_session

            def loop():
                sess = get_session()
                start = 0
                ckpt = sess.get_checkpoint()
                if ckpt is not None:
                    start = int(ckpt.get_metadata().get("step", 0))
                for step in range(start, 3):
                    time.sleep(0.1)
                    d = tempfile.mkdtemp(dir=workdir)
                    c = Checkpoint(d)
                    c.set_metadata({"step": step + 1})
                    sess.report({"step": step}, checkpoint=c)

            trainer = DataParallelTrainer(
                loop,
                scaling_config=ScalingConfig(num_workers=2),
                run_config=RunConfig(
                    name=f"chaos-seed{seed}",
                    storage_path=os.path.join(workdir, "train"),
                    failure_config=FailureConfig(max_failures=3),
                ),
            )
            result = trainer.fit()
            assert result.error is None, f"training failed: {result.error}"
            assert result.metrics["step"] == 2, result.metrics

        assert ray_tpu.get(refs, timeout=120) == [i * i for i in range(16)]
        import numpy as np
        big = ray_tpu.get(big_ref, timeout=120)
        assert np.array_equal(big, np.arange(400_000, dtype=np.float64)), \
            "chunked cross-node object corrupted under chaos"
        del big
        assert sorted(ray_tpu.get(incs, timeout=120)) == list(range(1, 11))
        assert ray_tpu.get(counter.total.remote(), timeout=60) == 10
        assert ray_tpu.get(crash_ref, timeout=120) == "survived"
        if kills:
            # tasks lost with the doomed supervisor retried onto its
            # replacement — no lost tasks
            assert ray_tpu.get(doomed_refs, timeout=120) == ["done", "done"]

        # no leaked pending futures: every retried/severed call either
        # completed or popped its entry on the way out
        from ray_tpu._private import api as _api

        deadline = time.monotonic() + 5
        while time.monotonic() < deadline:
            leaked = {addr: len(c._pending)
                      for addr, c in _api._core.clients._clients.items()
                      if c._pending}
            if not leaked:
                break
            time.sleep(0.1)
        assert not leaked, f"pending RPC futures leaked: {leaked}"
    finally:
        chaos.set_fault_controller(None)  # calm teardown
        _maybe_flight_dump()  # before shutdown, while dumps exist
        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
        cluster.shutdown()
        chaos.reset()


def run_collective_chaos(
    seed: int,
    *,
    drop_prob: float = 0.02,
    dup_prob: float = 0.05,
    delay_prob: float = 0.05,
    delay_max_ms: int = 20,
    kills: bool = True,
) -> None:
    """One seeded chaos run against the p2p collective data plane.

    Builds a 2-node cluster, rings 4 ranks across both nodes with a small
    chunk size (every segment streams as many attacked ``collective_chunk``
    frames), and drives repeated allreduces whose sums must stay EXACT
    under drop/dup/delay — a dropped frame may cost a retry, never a wrong
    reduction. With ``kills``, a participant is then hard-killed mid-group:
    the survivors' next collective must surface a clean TimeoutError /
    peer-dead / channel-closed error (and the shm variant's channel pins
    reclaim through the supervisor's dead-client path), never a hang or a
    silently wrong sum.
    """
    import numpy as np

    import ray_tpu
    from ray_tpu._private import chaos
    from ray_tpu._private.chaos import FaultController
    from ray_tpu._private.config import Config
    from ray_tpu.cluster_utils import Cluster

    cfg = Config.from_env()
    cfg.chaos_seed = seed
    cfg.chaos_drop_prob = drop_prob
    cfg.chaos_dup_prob = dup_prob
    cfg.chaos_delay_prob = delay_prob
    cfg.chaos_delay_max_ms = delay_max_ms
    cfg.chaos_methods = CHAOS_METHODS
    # ~12 frames per ring segment at this size: plenty of attack surface
    cfg.collective_chunk_bytes = 128 * 1024

    cluster = Cluster(config=cfg)
    try:
        cluster.add_node(num_cpus=4, resources={"left": 100})
        cluster.add_node(num_cpus=4, resources={"right": 100})
        cluster.wait_for_nodes(2)
        ray_tpu.init(address=cluster.address)
        chaos.set_fault_controller(FaultController(
            seed=seed, drop_prob=drop_prob, dup_prob=dup_prob,
            delay_prob=delay_prob, delay_max_ms=delay_max_ms,
            methods=CHAOS_METHODS))

        @ray_tpu.remote
        class Rank:
            def init_group(self, world, rank, name, algo=None):
                from ray_tpu.util import collective as col

                col.init_collective_group(world, rank, backend="host",
                                          group_name=name, algo=algo)
                return rank

            def algo(self, name):
                from ray_tpu.util.collective.collective import _manager

                return _manager.get(name).algo

            def allreduce_checked(self, n, fill, name, timeout_ms=60000):
                from ray_tpu.util import collective as col

                out = col.allreduce(np.full(n, float(fill), np.float64),
                                    group_name=name, timeout_ms=timeout_ms)
                return float(out[0]), float(out[-1])

        ranks = [
            Rank.options(
                resources={("left" if i % 2 == 0 else "right"): 1}).remote()
            for i in range(4)
        ]
        ray_tpu.get([r.init_group.remote(4, i, "soak")
                     for i, r in enumerate(ranks)], timeout=120)
        ray_tpu.get([r.allreduce_checked.remote(10, 1.0, "soak")
                     for r in ranks], timeout=120)  # rendezvous + warm
        # auto must have picked the ring (a silent shm/kv fallback would
        # attack none of the p2p RPCs and pass vacuously)
        assert ray_tpu.get(ranks[0].algo.remote("soak"),
                           timeout=60) == "ring", \
            "cross-node group did not resolve to the ring data plane"
        for step in range(4):
            # ~1.2 MB/rank -> chunked ring segments under the schedule
            outs = ray_tpu.get(
                [r.allreduce_checked.remote(150_000, step + i + 1, "soak")
                 for i, r in enumerate(ranks)], timeout=180)
            want = float(sum(step + i + 1 for i in range(4)))
            for first, last in outs:
                assert first == want and last == want, (
                    f"ring allreduce corrupted under chaos: got "
                    f"({first}, {last}), want {want}")

        if kills:
            # participant kill mid-group: survivors must fail CLEAN
            victims = [
                Rank.options(
                    resources={("left" if i % 2 == 0 else "right"): 1}
                ).remote()
                for i in range(3)
            ]
            ray_tpu.get([r.init_group.remote(3, i, "doomed")
                         for i, r in enumerate(victims)], timeout=120)
            ray_tpu.get(
                [r.allreduce_checked.remote(1000, 1.0, "doomed")
                 for r in victims], timeout=120)
            ray_tpu.kill(victims[2])
            time.sleep(0.5)
            refs = [r.allreduce_checked.remote(1000, 1.0, "doomed", 5000)
                    for r in victims[:2]]
            for ref in refs:
                try:
                    ray_tpu.get(ref, timeout=120)
                    raise AssertionError(
                        "collective with a dead participant returned a "
                        "result instead of a clean error")
                except AssertionError:
                    raise
                except Exception as e:  # noqa: BLE001 — the expected path
                    msg = str(e).lower()
                    assert ("timed out" in msg or "unreachable" in msg
                            or "dead" in msg or "closed" in msg), (
                        f"unclean error from dead-peer collective: {e!r}")
    finally:
        chaos.set_fault_controller(None)  # calm teardown
        _maybe_flight_dump()  # before shutdown, while dumps exist
        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
        cluster.shutdown()
        chaos.reset()


def run_collective_overlap_chaos(
    seed: int,
    *,
    drop_prob: float = 0.02,
    dup_prob: float = 0.05,
    delay_prob: float = 0.05,
    delay_max_ms: int = 20,
    kills: bool = True,
) -> None:
    """One seeded chaos run against the ASYNC overlap collective path.

    Same 2-node / 4-rank cross-node ring and fault schedule as
    ``run_collective_chaos``, but every step goes through
    ``allreduce_coalesced_async`` handles: two submissions in flight per
    step, simulated compute between submit and wait, waits OUT OF ORDER
    — sums must stay exact under drop/dup/delay. With ``kills``, a rank
    dies with async work in flight: every pending handle at the
    survivors must raise a clean error, the group must poison (a later
    submit fails fast), and destroy must leave no pins behind — never a
    hang or a silently wrong gradient.
    """
    import numpy as np

    import ray_tpu
    from ray_tpu._private import chaos
    from ray_tpu._private.chaos import FaultController
    from ray_tpu._private.config import Config
    from ray_tpu.cluster_utils import Cluster

    cfg = Config.from_env()
    cfg.chaos_seed = seed
    cfg.chaos_drop_prob = drop_prob
    cfg.chaos_dup_prob = dup_prob
    cfg.chaos_delay_prob = delay_prob
    cfg.chaos_delay_max_ms = delay_max_ms
    cfg.chaos_methods = CHAOS_METHODS
    cfg.collective_chunk_bytes = 128 * 1024

    cluster = Cluster(config=cfg)
    try:
        cluster.add_node(num_cpus=4, resources={"left": 100})
        cluster.add_node(num_cpus=4, resources={"right": 100})
        cluster.wait_for_nodes(2)
        ray_tpu.init(address=cluster.address)
        chaos.set_fault_controller(FaultController(
            seed=seed, drop_prob=drop_prob, dup_prob=dup_prob,
            delay_prob=delay_prob, delay_max_ms=delay_max_ms,
            methods=CHAOS_METHODS))

        @ray_tpu.remote
        class Rank:
            def init_group(self, world, rank, name, algo=None):
                from ray_tpu.util import collective as col

                col.init_collective_group(world, rank, backend="host",
                                          group_name=name, algo=algo)
                return rank

            def algo(self, name):
                from ray_tpu.util.collective.collective import _manager

                return _manager.get(name).algo

            def warm(self, name, timeout_ms=60000):
                from ray_tpu.util import collective as col

                out = col.allreduce(np.full(10, 1.0, np.float64),
                                    group_name=name, timeout_ms=timeout_ms)
                return float(out[0])

            def overlapped_step(self, name, step, n, timeout_ms=120000):
                """Two async submissions in flight, compute between,
                waits out of order; returns firsts of each result."""
                from ray_tpu.util import collective as col

                a = [np.full(n, step + 1.0), np.full(n // 2, step + 2.0)]
                b = [np.full(n // 4, step + 3.0)]
                w1 = col.allreduce_coalesced_async(
                    a, group_name=name, timeout_ms=timeout_ms, overlap=True)
                w2 = col.allreduce_coalesced_async(
                    b, group_name=name, timeout_ms=timeout_ms, overlap=True)
                time.sleep(0.02)  # simulated device compute
                r2 = w2.wait(timeout_ms)
                r1 = w1.wait(timeout_ms)
                assert w1.overlapped and w2.overlapped, \
                    "chaos overlap step fell back to the sync path"
                return (float(r1[0][0]), float(r1[1][0]), float(r2[0][0]))

            def overlap_fail_probe(self, name, timeout_ms=5000):
                from ray_tpu.util import collective as col

                w1 = col.allreduce_coalesced_async(
                    [np.ones(5000, np.float64)], group_name=name,
                    timeout_ms=timeout_ms, overlap=True)
                w2 = col.allreduce_coalesced_async(
                    [np.ones(100, np.float64)], group_name=name,
                    timeout_ms=timeout_ms, overlap=True)
                errs = []
                for w in (w2, w1):
                    try:
                        w.wait(timeout_ms * 5)
                        errs.append("NO-ERROR")
                    except Exception as e:  # noqa: BLE001 — expected
                        errs.append(f"{type(e).__name__}: {e}")
                try:
                    col.allreduce_coalesced_async(
                        [np.ones(10, np.float64)], group_name=name,
                        overlap=True)
                    poisoned = False
                except Exception as e:  # noqa: BLE001
                    poisoned = "poisoned" in str(e).lower()
                col.destroy_collective_group(name)  # pins must unwind
                return errs, poisoned

        ranks = [
            Rank.options(
                resources={("left" if i % 2 == 0 else "right"): 1}).remote()
            for i in range(4)
        ]
        ray_tpu.get([r.init_group.remote(4, i, "ovl_soak")
                     for i, r in enumerate(ranks)], timeout=120)
        ray_tpu.get([r.warm.remote("ovl_soak") for r in ranks], timeout=120)
        assert ray_tpu.get(ranks[0].algo.remote("ovl_soak"),
                           timeout=60) == "ring", \
            "cross-node group did not resolve to the ring data plane"
        for step in range(4):
            outs = ray_tpu.get(
                [r.overlapped_step.remote("ovl_soak", step, 60_000)
                 for r in ranks], timeout=240)
            for f1, f1b, f2 in outs:
                assert f1 == 4 * (step + 1.0), (f1, step)
                assert f1b == 4 * (step + 2.0), (f1b, step)
                assert f2 == 4 * (step + 3.0), (f2, step)

        if kills:
            victims = [
                Rank.options(
                    resources={("left" if i % 2 == 0 else "right"): 1}
                ).remote()
                for i in range(3)
            ]
            ray_tpu.get([r.init_group.remote(3, i, "ovl_doomed")
                         for i, r in enumerate(victims)], timeout=120)
            ray_tpu.get([r.warm.remote("ovl_doomed") for r in victims],
                        timeout=120)
            ray_tpu.kill(victims[2])
            time.sleep(0.5)
            for probe in ray_tpu.get(
                    [r.overlap_fail_probe.remote("ovl_doomed")
                     for r in victims[:2]], timeout=240):
                errs, poisoned = probe
                for e in errs:
                    low = e.lower()
                    assert ("timed out" in low or "unreachable" in low
                            or "dead" in low or "closed" in low
                            or "destroyed" in low or "poisoned" in low), (
                        f"unclean error from in-flight handle: {e!r}")
                assert poisoned, \
                    "submit after mid-flight failure did not fail fast"
    finally:
        chaos.set_fault_controller(None)  # calm teardown
        _maybe_flight_dump()  # before shutdown, while dumps exist
        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
        cluster.shutdown()
        chaos.reset()


def run_pipeline_chaos(
    seed: int,
    *,
    drop_prob: float = 0.02,
    dup_prob: float = 0.05,
    delay_prob: float = 0.05,
    delay_max_ms: int = 20,
    kills: bool = True,
    virtual_stages: int = 1,
    tensor_parallel: int = 1,
    dp: int = 1,
) -> None:
    """One seeded chaos run against the MPMD pipeline trainer.

    Builds a 2-node cluster with the two pipeline stages split across it
    (every activation/gradient hop is a cross-node mirror push, chunked
    small so each streams several attacked ``channel_write_chunk`` +
    ``channel_commit`` frames), then trains a tiny transformer for three
    steps: every step's loss must MATCH a single-process reference to
    fp32 tolerance — chaos may cost retries, never a wrong loss (absolute
    slot-ring versions make dropped/duplicated push frames converge).
    With ``virtual_stages=2`` the same two actors run the INTERLEAVED
    four-chunk schedule, so every per-chunk act/grad hop — twice as many
    of them — is a cross-node chunked push under the same attack.
    With ``kills``, a stage actor is then hard-killed mid-flush: the
    in-flight step must surface a clean ChannelClosedError/ActorDiedError
    (never a hang, never a silently wrong loss), teardown must unwind,
    and the driver's channel pins must return to baseline.
    With ``tensor_parallel=2`` (and ``dp=2``) the same two nodes carry
    the full 3D grid — tp=2 x dp=2 x S=2, eight actors, every stage's
    four (dp, tp) replicas pinned to one node so the tp partial-sum
    reduces stay same-node while every pp act/grad hop still crosses
    nodes under the attack. Losses must still match the fused
    single-process reference exactly, and every steady report must show
    the tp groups engaged.
    """
    import threading

    import numpy as np

    import ray_tpu
    from ray_tpu._private import chaos
    from ray_tpu._private.chaos import FaultController
    from ray_tpu._private.config import Config
    from ray_tpu.cluster_utils import Cluster

    # single-process reference trajectory FIRST (pure jax, no cluster)
    import jax
    import optax

    from ray_tpu.models import presets
    from ray_tpu.models.transformer import init_params, loss_fn

    V = int(virtual_stages)
    TP = int(tensor_parallel)
    DP = int(dp)
    if TP == 1:
        mcfg = presets.llama_debug(
            num_layers=2 * V, vocab_size=128, max_seq_len=32, embed_dim=32,
            num_heads=2, num_kv_heads=1, mlp_dim=64)
    else:
        # tp must divide the head/kv-head/mlp counts
        mcfg = presets.llama_debug(
            num_layers=2 * V, vocab_size=128, max_seq_len=32, embed_dim=32,
            num_heads=2 * TP, num_kv_heads=TP, mlp_dim=64)
    batch = np.random.default_rng(0).integers(
        0, 128, (16, 16)).astype(np.int32)
    M = 4

    params = init_params(mcfg, jax.random.PRNGKey(0))
    opt = optax.sgd(0.05)
    ost = opt.init(params)

    def mb_loss(p, toks):
        loss, _ = loss_fn(mcfg, p, {"tokens": toks})
        return loss

    gfn = jax.jit(jax.value_and_grad(mb_loss))
    ref_losses = []
    for _ in range(4):
        acc, losses = None, []
        for m in range(M):
            loss, g = gfn(params, batch[m * 4:(m + 1) * 4])
            losses.append(float(loss))
            acc = g if acc is None else jax.tree.map(
                lambda a, b: a + b, acc, g)
        grads = jax.tree.map(lambda g: g / M, acc)
        upd, ost = opt.update(grads, ost, params)
        params = optax.apply_updates(params, upd)
        ref_losses.append(float(np.mean(losses)))

    cfg = Config.from_env()
    cfg.chaos_seed = seed
    cfg.chaos_drop_prob = drop_prob
    cfg.chaos_dup_prob = dup_prob
    cfg.chaos_delay_prob = delay_prob
    cfg.chaos_delay_max_ms = delay_max_ms
    cfg.chaos_methods = CHAOS_METHODS
    # ~8 KB activations stream as several chunk frames per push
    cfg.object_transfer_chunk_bytes = 2048

    cluster = Cluster(config=cfg)
    try:
        # the 3D grid packs the tp x dp replicas of each stage on one node
        ncpu = 4 if TP == 1 and DP == 1 else 4 * TP * DP
        cluster.add_node(num_cpus=ncpu, resources={"left": 100})
        cluster.add_node(num_cpus=ncpu, resources={"right": 100})
        cluster.wait_for_nodes(2)
        ray_tpu.init(address=cluster.address)
        chaos.set_fault_controller(FaultController(
            seed=seed, drop_prob=drop_prob, dup_prob=dup_prob,
            delay_prob=delay_prob, delay_max_ms=delay_max_ms,
            methods=CHAOS_METHODS))

        from ray_tpu._private import api as _api
        from ray_tpu.train import PipelineTrainer

        def store_pins():
            core = _api._core
            stats = core._run(core.clients.get(core.supervisor_addr).call(
                "store_stats", timeout=60))
            return stats["pins_total"]

        pins_before = store_pins()
        extra = {}
        if TP > 1:
            # keep the 3D grid's 2x ring count inside the object store
            extra["buffer_bytes"] = 1 * 1024 * 1024
        trainer = PipelineTrainer(
            presets.pipeline_stage_defs(mcfg, 2, virtual_stages=V,
                                        seed=0, tensor_parallel=TP),
            num_microbatches=M, dp=DP, virtual_stages=V,
            tensor_parallel=TP, optimizer=("sgd", 0.05),
            stage_options=[{"resources": {"left": 1}},
                           {"resources": {"right": 1}}], **extra)
        assert trainer.is_channel_backed and trainer.channel_depth > 1, (
            "pipeline chaos run is not on the slot-ring channel substrate")
        assert trainer.virtual_stages == V, (
            "pipeline chaos run is not on the requested interleaved "
            "schedule")
        assert trainer.tensor_parallel == TP, (
            "pipeline chaos run is not on the requested tp width")
        for step in range(3):
            out = trainer.step(batch)
            assert abs(out["loss"] - ref_losses[step]) < 1e-4, (
                f"step {step}: pipeline loss {out['loss']} != reference "
                f"{ref_losses[step]} — chaos corrupted training")
            if TP > 1:
                for rep in out["reports"]:
                    assert rep["tp"] == TP and rep["tp_reduce_calls"] > 0, (
                        f"step {step}: tp groups not engaged: {rep}")

        if kills:
            # stage kill MID-FLUSH: the in-flight step must fail clean
            box = {}

            def stepper():
                try:
                    box["out"] = trainer.step(batch)
                except Exception as e:  # noqa: BLE001 — the expected path
                    box["err"] = e

            t = threading.Thread(target=stepper)
            t.start()
            time.sleep(0.05)
            ray_tpu.kill(trainer._actors[0][1][0])
            t.join(timeout=180)
            assert not t.is_alive(), "step hung after a stage-actor kill"
            if "err" in box:
                msg = str(box["err"]).lower()
                assert ("closed" in msg or "dead" in msg
                        or "died" in msg), (
                    f"unclean error after stage kill: {box['err']!r}")
            else:
                # the kill landed after the flush completed: the loss
                # must still be exact, and the NEXT step must fail clean
                assert abs(box["out"]["loss"] - ref_losses[3]) < 1e-4, (
                    "post-kill completed step returned a wrong loss")
                try:
                    trainer.step(batch)
                    raise AssertionError(
                        "step with a dead stage returned instead of "
                        "raising")
                except AssertionError:
                    raise
                except Exception as e:  # noqa: BLE001 — expected
                    msg = str(e).lower()
                    assert ("closed" in msg or "dead" in msg
                            or "died" in msg), (
                        f"unclean error after stage kill: {e!r}")
        trainer.shutdown()

        # pins back to baseline. The release RPCs run under the same
        # fault schedule, so a dropped unpin falls back to the bulk
        # release path a departing driver uses (one RPC per node).
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and store_pins() != pins_before:
            time.sleep(0.3)
        if store_pins() != pins_before:
            core = _api._core
            for _ in range(3):
                try:
                    core._run(core.clients.get(core.supervisor_addr).call(
                        "store_release_client",
                        {"client": core._store_client_id}, timeout=10))
                    break
                except Exception:
                    continue
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline \
                    and store_pins() != pins_before:
                time.sleep(0.3)
        assert store_pins() == pins_before, (
            "pipeline channel pins did not return to baseline")
    finally:
        chaos.set_fault_controller(None)  # calm teardown
        _maybe_flight_dump()  # before shutdown, while dumps exist
        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
        cluster.shutdown()
        chaos.reset()


def _data_chaos_transform(b):
    """Module-level so the chaos workload's map chain pickles cleanly
    into reader/transform actors and remote tasks alike."""
    return {"id": b["id"] * 3 + 1}


def run_data_chaos(
    seed: int,
    *,
    drop_prob: float = 0.02,
    dup_prob: float = 0.05,
    delay_prob: float = 0.05,
    delay_max_ms: int = 20,
    kills: bool = True,
) -> None:
    """One seeded chaos run against the streaming data plane.

    Builds a 2-node cluster and places the ingest stages ALTERNATING
    across it (readers and the batcher opposite the driver, transforms
    on the driver's node), so every reader->transform->batcher->consumer
    hop is a cross-node mirror push — chunked small so each block/batch
    streams several attacked ``channel_write_chunk`` + ``channel_commit``
    frames. Two full epochs (shuffled) must match the task-based
    loader's batches EXACTLY at the same seed — chaos may cost retries,
    never a wrong or reordered batch (absolute slot-ring versions make
    dropped/duplicated push frames converge). With ``kills``, a reader
    is then hard-killed mid-epoch: the consumer must surface a clean
    ChannelClosedError/ActorDiedError (never a hang, never a silently
    truncated epoch) and the driver's channel pins must return to
    baseline.
    """
    import numpy as np

    import ray_tpu
    from ray_tpu._private import chaos
    from ray_tpu._private.chaos import FaultController
    from ray_tpu._private.config import Config
    from ray_tpu.cluster_utils import Cluster

    cfg = Config.from_env()
    cfg.chaos_seed = seed
    cfg.chaos_drop_prob = drop_prob
    cfg.chaos_dup_prob = dup_prob
    cfg.chaos_delay_prob = delay_prob
    cfg.chaos_delay_max_ms = delay_max_ms
    cfg.chaos_methods = CHAOS_METHODS
    # blocks/batches stream as several chunk frames per push
    cfg.object_transfer_chunk_bytes = 2048

    cluster = Cluster(config=cfg)
    try:
        cluster.add_node(num_cpus=4, resources={"n0": 100})
        cluster.add_node(num_cpus=4, resources={"n1": 100})
        cluster.wait_for_nodes(2)
        ray_tpu.init(address=cluster.address)
        chaos.set_fault_controller(FaultController(
            seed=seed, drop_prob=drop_prob, dup_prob=dup_prob,
            delay_prob=delay_prob, delay_max_ms=delay_max_ms,
            methods=CHAOS_METHODS))

        from ray_tpu import data as rd
        from ray_tpu._private import api as _api
        from ray_tpu._private.exceptions import (ActorDiedError,
                                                 ChannelClosedError,
                                                 TaskError)
        from ray_tpu.data._internal import streaming as dstream

        # which resource tag is the driver's node? (stage placement
        # alternates against it so every hop crosses the wire)
        @ray_tpu.remote
        def _where():
            from ray_tpu._private import api

            return tuple(api._core.supervisor_addr)

        core = _api._core
        n0_addr = ray_tpu.get(
            _where.options(resources={"n0": 1}).remote(), timeout=60)
        here = "n0" if tuple(core.supervisor_addr) == n0_addr else "n1"
        there = "n1" if here == "n0" else "n0"

        def store_pins():
            stats = core._run(core.clients.get(core.supervisor_addr).call(
                "store_stats", timeout=60))
            return stats["pins_total"]

        d = rd.range(600, parallelism=12).map_batches(
            _data_chaos_transform)
        R = 2
        base_seed = 100 + seed
        stage_kw = dict(
            reader_options=[{"resources": {there: 1}}] * R,
            transform_options=[{"resources": {here: 1}}] * R,
            batcher_options={"resources": {there: 1}})

        pins_before = store_pins()
        ex = dstream.StreamingExecutor(
            d._ops, batch_size=40, epochs=2, seed=base_seed,
            shuffle_buffer=96, num_readers=R, **stage_kw)
        assert ex.is_channel_backed and ex.channel_depth > 1, (
            "data chaos run is not on the slot-ring channel substrate")
        got = [[], []]
        for b in ex.batches():
            got[len(ex.epoch_stats)].append(b)
        for epoch, act in enumerate(got, start=1):
            exp = list(dstream.task_epoch_batches(
                d._ops, batch_size=40, epoch=epoch, seed=base_seed,
                shuffle_buffer=96))
            assert len(exp) == len(act), (
                f"epoch {epoch}: {len(act)} streamed batches != "
                f"{len(exp)} from the task loader")
            for i, (e, a) in enumerate(zip(exp, act)):
                for k in e:
                    assert np.array_equal(e[k], a[k]), (
                        f"epoch {epoch} batch {i} column {k}: streaming "
                        f"diverged from the task loader — chaos "
                        f"corrupted the stream")
        ex.shutdown()
        _drain_pins_to_baseline(pins_before)

        if kills:
            # reader hard-kill MID-EPOCH: the in-flight epoch must fail
            # clean — a partially-consumed epoch raises, never truncates
            ex = dstream.StreamingExecutor(
                d._ops, batch_size=10, epochs=3, seed=base_seed,
                num_readers=R, depth=2, **stage_kw)
            it = ex.batches()
            for _ in range(3):
                next(it)
            ray_tpu.kill(ex._readers[seed % R])
            try:
                deadline = time.monotonic() + 120
                while time.monotonic() < deadline:
                    next(it)
                raise AssertionError(
                    "stream kept yielding past a dead reader")
            except (ChannelClosedError, ActorDiedError, TaskError) as e:
                msg = str(e).lower()
                assert ("closed" in msg or "dead" in msg or "died" in msg
                        or isinstance(e, (ActorDiedError, TaskError))), (
                    f"unclean error after reader kill: {e!r}")
            except StopIteration:
                raise AssertionError(
                    "stream ended silently after a mid-epoch reader kill")
            ex.shutdown()
            _drain_pins_to_baseline(pins_before)
    finally:
        chaos.set_fault_controller(None)  # calm teardown
        _maybe_flight_dump()  # before shutdown, while dumps exist
        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
        cluster.shutdown()
        chaos.reset()


def run_shuffle_chaos(
    seed: int,
    *,
    drop_prob: float = 0.02,
    dup_prob: float = 0.05,
    delay_prob: float = 0.05,
    delay_max_ms: int = 20,
    kills: bool = True,
) -> None:
    """One seeded chaos run against the streaming all-to-all exchange
    (`data/_internal/exchange.py`).

    Builds a 2-node cluster with the R producers opposite the driver
    and the C consumers SPLIT across both nodes, so the R x C mesh
    carries both edge kinds at once: producer->consumer bucket frames
    into the driver-side consumer cross the wire, and the far-side
    consumer's batch channel back to the driver crosses it the other
    way — all chunked small (``bucket_rows`` under the per-bucket row
    count + 2 KiB transfer chunks) so every bucket streams several
    attacked ``channel_write_chunk`` + ``channel_commit`` frames. Two
    full shuffled epochs must match the task-based barrier AllToAll's
    batches EXACTLY at the same seed — chaos may cost retries, never a
    wrong, reordered, or mis-bucketed batch (absolute slot-ring
    versions make dropped/duplicated push frames converge). With
    ``kills``, a mesh participant is then hard-killed mid-shuffle —
    even seeds a PRODUCER, odd seeds a CONSUMER — and the whole mesh
    must close: the driver surfaces a clean ChannelClosedError/
    ActorDiedError (never a hang, never a silently truncated epoch)
    and the channel pins must return to baseline.
    """
    import numpy as np

    import ray_tpu
    from ray_tpu._private import chaos
    from ray_tpu._private.chaos import FaultController
    from ray_tpu._private.config import Config
    from ray_tpu.cluster_utils import Cluster

    cfg = Config.from_env()
    cfg.chaos_seed = seed
    cfg.chaos_drop_prob = drop_prob
    cfg.chaos_dup_prob = dup_prob
    cfg.chaos_delay_prob = delay_prob
    cfg.chaos_delay_max_ms = delay_max_ms
    cfg.chaos_methods = CHAOS_METHODS
    # bucket frames stream as several chunk frames per push
    cfg.object_transfer_chunk_bytes = 2048

    cluster = Cluster(config=cfg)
    try:
        cluster.add_node(num_cpus=4, resources={"n0": 100})
        cluster.add_node(num_cpus=4, resources={"n1": 100})
        cluster.wait_for_nodes(2)
        ray_tpu.init(address=cluster.address)
        chaos.set_fault_controller(FaultController(
            seed=seed, drop_prob=drop_prob, dup_prob=dup_prob,
            delay_prob=delay_prob, delay_max_ms=delay_max_ms,
            methods=CHAOS_METHODS))

        from ray_tpu import data as rd
        from ray_tpu._private import api as _api
        from ray_tpu._private.exceptions import (ActorDiedError,
                                                 ChannelClosedError,
                                                 TaskError)
        from ray_tpu.data._internal import exchange as dx

        @ray_tpu.remote
        def _where():
            from ray_tpu._private import api

            return tuple(api._core.supervisor_addr)

        core = _api._core
        n0_addr = ray_tpu.get(
            _where.options(resources={"n0": 1}).remote(), timeout=60)
        here = "n0" if tuple(core.supervisor_addr) == n0_addr else "n1"
        there = "n1" if here == "n0" else "n0"

        def store_pins():
            stats = core._run(core.clients.get(core.supervisor_addr).call(
                "store_stats", timeout=60))
            return stats["pins_total"]

        base_seed = 100 + seed
        d = rd.range(600, parallelism=12).map_batches(
            _data_chaos_transform).random_shuffle(seed=200 + seed)
        R = C = 2
        stage_kw = dict(
            producer_options=[{"resources": {there: 1}}] * R,
            consumer_options=[{"resources": {here: 1}},
                              {"resources": {there: 1}}])

        pins_before = store_pins()
        ex = dx.ExchangeExecutor(
            d._ops, batch_size=40, epochs=2, seed=base_seed,
            num_producers=R, num_consumers=C, bucket_rows=16, **stage_kw)
        assert ex.is_channel_backed and ex.channel_depth > 1, (
            "shuffle chaos run is not on the slot-ring channel mesh")
        got = [[], []]
        for b in ex.batches():
            got[len(ex.epoch_stats)].append(b)
        for epoch, act in enumerate(got, start=1):
            exp = list(dx.task_exchange_batches(
                d._ops, batch_size=40, num_consumers=C, epoch=epoch,
                seed=base_seed))
            assert len(exp) == len(act), (
                f"epoch {epoch}: {len(act)} exchanged batches != "
                f"{len(exp)} from the barrier baseline")
            for i, (e, a) in enumerate(zip(exp, act)):
                for k in e:
                    assert np.array_equal(e[k], a[k]), (
                        f"epoch {epoch} batch {i} column {k}: the "
                        f"exchange diverged from the barrier baseline — "
                        f"chaos corrupted the shuffle")
        ex.shutdown()
        _drain_pins_to_baseline(pins_before)

        if kills:
            # participant hard-kill MID-SHUFFLE: the mesh is one
            # dataflow, so killing EITHER role must close every channel
            # and fail the in-flight epoch clean — never truncate it
            ex = dx.ExchangeExecutor(
                d._ops, batch_size=8, epochs=50, seed=base_seed,
                num_producers=R, num_consumers=C, depth=2,
                bucket_rows=16, **stage_kw)
            it = ex.batches()
            for _ in range(3):
                next(it)
            victim = (ex._producers[(seed // 2) % R] if seed % 2 == 0
                      else ex._consumers[(seed // 2) % C])
            ray_tpu.kill(victim)
            try:
                deadline = time.monotonic() + 120
                while time.monotonic() < deadline:
                    next(it)
                raise AssertionError(
                    "exchange kept yielding past a dead participant")
            except (ChannelClosedError, ActorDiedError, TaskError) as e:
                msg = str(e).lower()
                assert ("closed" in msg or "dead" in msg or "died" in msg
                        or isinstance(e, (ActorDiedError, TaskError))), (
                    f"unclean error after mesh participant kill: {e!r}")
            except StopIteration:
                raise AssertionError(
                    "exchange ended silently after a mid-shuffle kill")
            ex.shutdown()
            _drain_pins_to_baseline(pins_before)
    finally:
        chaos.set_fault_controller(None)  # calm teardown
        _maybe_flight_dump()  # before shutdown, while dumps exist
        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
        cluster.shutdown()
        chaos.reset()


def run_podracer_chaos(
    seed: int,
    *,
    drop_prob: float = 0.02,
    dup_prob: float = 0.05,
    delay_prob: float = 0.05,
    delay_max_ms: int = 20,
    kills: bool = True,
) -> None:
    """One seeded chaos run against the Sebulba RL topology.

    Computes the reference trajectory FIRST with the dynamic local loop
    (pure in-process, no cluster — learner parity pins sebulba == dynamic
    at broadcast_interval=1), then builds a 2-node cluster with the
    runner and learner split across it: every trajectory batch is a
    chunked cross-node mirror push (small chunk bytes so each streams
    several attacked ``channel_write_chunk``/``channel_commit`` frames)
    and every parameter broadcast rides the cross-node ring
    (``collective_chunk`` frames attacked). Three iterations must match
    the reference losses to 1e-4 — chaos may cost retries, never a wrong
    update. With ``kills``, a runner (even seeds) or the learner (odd
    seeds) is hard-killed mid-iteration: the in-flight step must surface
    a clean ChannelClosedError/ActorDiedError (never a hang, never a
    silently wrong loss), teardown must unwind, and the driver's channel
    pins must return to baseline.
    """
    import threading

    import ray_tpu
    from ray_tpu._private import chaos
    from ray_tpu._private.chaos import FaultController
    from ray_tpu._private.config import Config
    from ray_tpu.cluster_utils import Cluster

    def make_cfg(topology):
        from ray_tpu.rllib import IMPALAConfig

        return (IMPALAConfig()
                .environment("CartPole-v1")
                .env_runners(num_env_runners=0 if topology == "dynamic"
                             else 1,
                             num_envs_per_env_runner=8,
                             rollout_fragment_length=16)
                .training(num_batches_per_iteration=1,
                          broadcast_interval=1,
                          model={"hiddens": (16,)})
                .learners(topology=topology)
                .debugging(seed=0))

    # reference FIRST: the dynamic local loop, pure in-process (no
    # cluster, no RPCs — the fault schedule cannot touch it)
    ref_algo = make_cfg("dynamic").build()
    try:
        ref_losses = [ref_algo.train()["total_loss"] for _ in range(4)]
    finally:
        ref_algo.stop()

    cfg = Config.from_env()
    cfg.chaos_seed = seed
    cfg.chaos_drop_prob = drop_prob
    cfg.chaos_dup_prob = dup_prob
    cfg.chaos_delay_prob = delay_prob
    cfg.chaos_delay_max_ms = delay_max_ms
    cfg.chaos_methods = CHAOS_METHODS
    # ~10 KB trajectory payloads stream as several chunk frames per push
    cfg.object_transfer_chunk_bytes = 1024

    cluster = Cluster(config=cfg)
    try:
        cluster.add_node(num_cpus=4, resources={"left": 100})
        cluster.add_node(num_cpus=4, resources={"right": 100})
        cluster.wait_for_nodes(2)
        ray_tpu.init(address=cluster.address)
        chaos.set_fault_controller(FaultController(
            seed=seed, drop_prob=drop_prob, dup_prob=dup_prob,
            delay_prob=delay_prob, delay_max_ms=delay_max_ms,
            methods=CHAOS_METHODS))

        from ray_tpu._private import api as _api
        from ray_tpu.rllib.algorithms.impala import IMPALA
        from ray_tpu.rllib.podracer import (ImpalaSebulbaProgram,
                                            SebulbaTopology)

        def store_pins():
            core = _api._core
            stats = core._run(core.clients.get(core.supervisor_addr).call(
                "store_stats", timeout=60))
            return stats["pins_total"]

        pins_before = store_pins()
        config = make_cfg("sebulba")
        spec = config.rl_module_spec()
        program = ImpalaSebulbaProgram(
            spec=spec, loss_fn=IMPALA.loss_fn,
            loss_cfg={
                "gamma": config.gamma,
                "clip_rho": config.vtrace_clip_rho_threshold,
                "clip_c": config.vtrace_clip_c_threshold,
                "vf_loss_coeff": config.vf_loss_coeff,
                "entropy_coeff": config.entropy_coeff,
            },
            opt_cfg={"lr": config.lr, "grad_clip": config.grad_clip},
            broadcast_interval=1)
        topo = SebulbaTopology(
            config, program,
            runner_options=[{"resources": {"left": 1}}],
            learner_options=[{"resources": {"right": 1}}])
        assert topo.is_channel_backed, (
            "podracer chaos run is not on the channel substrate")
        for step in range(3):
            out = topo.step()
            got = out["metrics"]["total_loss"]
            assert abs(got - ref_losses[step]) < 1e-4, (
                f"step {step}: sebulba loss {got} != reference "
                f"{ref_losses[step]} — chaos corrupted training")
            for rep in out["reports"]:
                assert rep["iteration"] == step + 1

        if kills:
            # participant kill MID-ITERATION: step must fail clean
            box = {}

            def stepper():
                try:
                    box["out"] = topo.step()
                except Exception as e:  # noqa: BLE001 — the expected path
                    box["err"] = e

            t = threading.Thread(target=stepper)
            t.start()
            time.sleep(0.05)
            victim = (topo._runners[0] if seed % 2 == 0
                      else topo._learners[0])
            ray_tpu.kill(victim)
            t.join(timeout=180)
            assert not t.is_alive(), \
                "step hung after a participant kill"
            if "err" in box:
                msg = str(box["err"]).lower()
                assert ("closed" in msg or "dead" in msg
                        or "died" in msg or "torn" in msg), (
                    f"unclean error after kill: {box['err']!r}")
            else:
                # the kill landed after the iteration completed: the
                # loss must still be exact, and the NEXT step must fail
                # clean
                got = box["out"]["metrics"]["total_loss"]
                assert abs(got - ref_losses[3]) < 1e-4, (
                    "post-kill completed step returned a wrong loss")
                try:
                    topo.step()
                    raise AssertionError(
                        "step with a dead participant returned instead "
                        "of raising")
                except AssertionError:
                    raise
                except Exception as e:  # noqa: BLE001 — expected
                    msg = str(e).lower()
                    assert ("closed" in msg or "dead" in msg
                            or "died" in msg or "torn" in msg), (
                        f"unclean error after kill: {e!r}")
        topo.shutdown()

        # pins back to baseline. The release RPCs run under the same
        # fault schedule, so a dropped unpin falls back to the bulk
        # release path a departing driver uses (one RPC per node).
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and store_pins() != pins_before:
            time.sleep(0.3)
        if store_pins() != pins_before:
            core = _api._core
            for _ in range(3):
                try:
                    core._run(core.clients.get(core.supervisor_addr).call(
                        "store_release_client",
                        {"client": core._store_client_id}, timeout=10))
                    break
                except Exception:
                    continue
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline \
                    and store_pins() != pins_before:
                time.sleep(0.3)
        assert store_pins() == pins_before, (
            "podracer channel pins did not return to baseline")
    finally:
        chaos.set_fault_controller(None)  # calm teardown
        _maybe_flight_dump()  # before shutdown, while dumps exist
        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
        cluster.shutdown()
        chaos.reset()


def run_serve_chaos(
    seed: int,
    *,
    drop_prob: float = 0.02,
    dup_prob: float = 0.05,
    delay_prob: float = 0.05,
    delay_max_ms: int = 20,
    kills: bool = True,
) -> None:
    """One seeded chaos run against the PAGED + PREFIX-CACHE serve
    scheduler (ISSUE 13).

    Deploys 2 LLM replicas (paged KV arena + radix prefix cache, the
    default) and drives a shared-prefix request burst under drop/dup/delay.
    With ``kills``, one replica is hard-killed MID-BURST: burst requests
    must either complete with the exact temperature-0 reference output or
    fail cleanly (never a wrong token), the controller's health sweep must
    replace the replica, and afterwards the surviving/replacement
    schedulers' paged state must be back at baseline — every slot retired,
    every radix refcount zero, and the page gauge equal to the resident
    prefix-cache pages (gauge-proven; a leak would show as
    pages_in_use > radix_resident_pages). A cancel-mid-stream scenario
    then proves a walked-away consumer retires its pages WITHOUT
    contaminating a later admit that hits the same cached prefix
    (exact-output-asserted against a cold reference).
    """
    import asyncio
    import threading

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private import chaos
    from ray_tpu._private.chaos import FaultController
    from ray_tpu._private.config import Config
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.serve.llm import LLMServerImpl

    cfg = Config.from_env()
    cfg.chaos_seed = seed
    cfg.chaos_drop_prob = drop_prob
    cfg.chaos_dup_prob = dup_prob
    cfg.chaos_delay_prob = delay_prob
    cfg.chaos_delay_max_ms = delay_max_ms
    cfg.chaos_methods = CHAOS_METHODS

    cluster = Cluster(config=cfg)
    try:
        cluster.add_node(num_cpus=4)
        cluster.wait_for_nodes(1)
        ray_tpu.init(address=cluster.address)
        chaos.set_fault_controller(FaultController(
            seed=seed, drop_prob=drop_prob, dup_prob=dup_prob,
            delay_prob=delay_prob, delay_max_ms=delay_max_ms,
            methods=CHAOS_METHODS))

        class _ChaosLLMImpl(LLMServerImpl):
            async def __call__(self, request=None):
                if isinstance(request, dict) and request.get("__die__"):
                    os._exit(1)  # the mid-burst replica kill
                return await super().__call__(request)

        dep = serve.deployment(name="llmchaos", max_ongoing_requests=32)(
            _ChaosLLMImpl)
        # shared preamble longer than several pages + unique tails: the
        # burst exercises splice/insert/refcount churn on every admit
        preamble = "You are a terse assistant. Answer briefly. "
        prompts = [preamble + f"q{i:02d}?" for i in range(6)]
        h = serve.run(dep.options(num_replicas=2).bind(
            max_new_tokens=6, slots=4, prefill_chunk=8, page_tokens=8),
            name="servechaos", route_prefix="/servechaos")

        # temperature-0 references (replicas are identical; the first
        # answer per prompt is the reference the rest must equal)
        refs = {}
        for p in prompts:
            refs[p] = h.remote({"prompt": p}).result(timeout=300)["text"]
            assert refs[p], "reference generation empty"

        n_burst = 24
        outs = [None] * n_burst
        errs = []

        def call(i):
            try:
                outs[i] = h.remote(
                    {"prompt": prompts[i % len(prompts)]}).result(
                        timeout=300)
            except Exception as e:  # noqa: BLE001 — asserted below
                errs.append(e)

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(n_burst)]
        for t in threads:
            t.start()
        if kills:
            time.sleep(0.3)  # let the burst land on both replicas
            try:
                h.remote({"__die__": True}).result(timeout=30)
            except Exception:
                pass  # the dying replica cannot answer
        for t in threads:
            t.join()
        for o in outs:
            if o is not None:
                assert o["text"] == refs[o["prompt"]], (
                    "burst output diverged from the temperature-0 "
                    f"reference for {o['prompt']!r}")
        done = sum(1 for o in outs if o is not None)
        assert done >= 1, f"every burst request failed: {errs[:3]}"
        if not kills:
            assert not errs, f"requests failed without a kill: {errs[:2]}"

        # recovery: the health sweep replaces the killed replica and the
        # deployment serves the exact reference again
        deadline = time.monotonic() + 60
        ok = 0
        while time.monotonic() < deadline and ok < 8:
            try:
                out = h.remote(
                    {"prompt": prompts[ok % len(prompts)]}).result(
                        timeout=30)
                assert out["text"] == refs[out["prompt"]], (
                    "post-recovery output diverged: "
                    f"{out['text']!r} for {out['prompt']!r}")
                ok += 1
            except AssertionError:
                raise
            except Exception:
                time.sleep(0.5)
        assert ok >= 8, "deployment did not recover from the replica kill"

        # paged-state hygiene, gauge-proven on the live replicas: every
        # slot retired, no dangling radix refs, and the page gauge equal
        # to the resident prefix-cache pages (a leaked slot/page would
        # leave pages_in_use > radix_resident_pages forever)
        deadline = time.monotonic() + 30
        clean = 0
        hits_seen = 0
        attn_bytes_seen = 0
        # prefix_hits/attn_bytes are tracked across ALL samples, not read
        # off the final one: after a kill the stats call can route to the
        # freshly-replaced replica whose counters are legitimately zero
        while time.monotonic() < deadline and (clean < 4 or hits_seen == 0):
            st = h.scheduler_stats.remote().result(timeout=30)
            assert st["mode"] == "continuous", st
            hits_seen = max(hits_seen, st["prefix_hits"])
            attn_bytes_seen = max(attn_bytes_seen, st["attn_bytes_moved"])
            if (st["active_slots"] == 0 and st["radix_active_refs"] == 0
                    and st["pages_in_use"] == st["radix_resident_pages"]):
                clean += 1  # sampled across routing to both replicas
                if hits_seen == 0:
                    time.sleep(0.2)  # resample: routing may alternate
            else:
                time.sleep(0.5)
        assert clean >= 4, (
            f"paged arena did not return to baseline: {st}")
        assert hits_seen > 0, (
            "the shared-prefix burst never hit the radix cache on any "
            f"sampled replica: {st}")
        assert st["attn_lane"] in ("reference", "pallas"), st
        assert attn_bytes_seen > 0, (
            "no sampled replica moved attention bytes — the paged "
            f"attention lane never engaged: {st}")

        serve.shutdown()

        # ---- cancel-mid-stream vs the prefix cache (driver-local: the
        # scheduler itself is RPC-free; chaos stays armed around it) ----
        srv = LLMServerImpl(max_new_tokens=6, slots=2, prefill_chunk=8,
                            page_tokens=8, share_weights=False)
        try:
            victim = preamble + "stream me something long please"

            async def cold(p):
                return (await srv({"prompt": p}))["text"]

            ref_text = asyncio.run(cold(victim))
            st0 = srv.scheduler_stats()

            async def cancel_then_readmit():
                gen = await srv({"prompt": victim, "stream": True,
                                 "max_new_tokens": 32})
                it = gen.__aiter__()
                await it.__anext__()
                await it.__anext__()
                await gen.aclose()  # consumer walks away mid-decode
                deadline = time.monotonic() + 10
                while time.monotonic() < deadline:
                    st = srv.scheduler_stats()
                    if st["active_slots"] == 0 \
                            and st["radix_active_refs"] == 0:
                        break
                    await asyncio.sleep(0.05)
                st = srv.scheduler_stats()
                assert st["active_slots"] == 0, st
                assert st["radix_active_refs"] == 0, st
                return (await srv({"prompt": victim}))["text"]

            again = asyncio.run(cancel_then_readmit())
            assert again == ref_text, (
                "admit after cancel-mid-stream diverged through the "
                "cached prefix")
            st1 = srv.scheduler_stats()
            assert st1["prefix_hits"] > st0["prefix_hits"], (
                "re-admit never hit the prefix the cancelled stream "
                f"cached: {st1}")
        finally:
            srv.shutdown()
    finally:
        chaos.set_fault_controller(None)  # calm teardown
        _maybe_flight_dump()
        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
        cluster.shutdown()
        chaos.reset()


def run_fleet_chaos(
    seed: int,
    *,
    drop_prob: float = 0.02,
    dup_prob: float = 0.05,
    delay_prob: float = 0.05,
    delay_max_ms: int = 20,
    kills: bool = True,
) -> None:
    """One seeded chaos run against the FLEET serve path (ISSUE 18):
    prefix-affinity steering with a replica kill landed MID-MIGRATION.

    Deploys 3 LLM replicas with affinity routing on, warms a shared
    preamble onto one holder, then fail-marks the holder so a burst of
    same-preamble requests falls back with a migration hint — every
    fallback PULLS the prefix pages cross-replica. With ``kills`` the
    seed's parity picks the victim: even seeds hard-kill the HOLDER
    (exporter dies under the pull; the puller must degrade to a
    bit-identical cold prefill), odd seeds hard-kill a PULLER (its
    in-flight splices die with it; the fleet serves on). Burst requests
    must complete with the exact temperature-0 reference output or fail
    cleanly, the router must re-steer within the fail-mark window
    (first exact post-kill answer within FAIL_PENALTY_S), the health
    sweep must replace the victim, at least one migration pull or
    migration failure must be recorded on the survivors, and every live
    replica's paged state must return to baseline — slots retired, radix
    refcounts zero, no pending migrations, page gauge equal to the
    resident prefix pages."""
    import threading

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu._private import chaos
    from ray_tpu._private.chaos import FaultController
    from ray_tpu._private.config import Config
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.serve.llm import LLMServerImpl

    cfg = Config.from_env()
    cfg.chaos_seed = seed
    cfg.chaos_drop_prob = drop_prob
    cfg.chaos_dup_prob = dup_prob
    cfg.chaos_delay_prob = delay_prob
    cfg.chaos_delay_max_ms = delay_max_ms
    cfg.chaos_methods = CHAOS_METHODS

    cluster = Cluster(config=cfg)
    try:
        cluster.add_node(num_cpus=8)
        cluster.wait_for_nodes(1)
        ray_tpu.init(address=cluster.address)
        chaos.set_fault_controller(FaultController(
            seed=seed, drop_prob=drop_prob, dup_prob=dup_prob,
            delay_prob=delay_prob, delay_max_ms=delay_max_ms,
            methods=CHAOS_METHODS))

        class _ChaosLLMImpl(LLMServerImpl):
            async def __call__(self, request=None):
                if isinstance(request, dict) and request.get("__die__"):
                    os._exit(1)  # the mid-migration replica kill
                return await super().__call__(request)

        dep = serve.deployment(name="llmfleet", max_ongoing_requests=32)(
            _ChaosLLMImpl)
        # preamble spans several pages (page_tokens=8): a pull moves a
        # real multi-page chain, not a single splice
        preamble = ("You are a helpful fleet assistant serving many "
                    "users. Answer tersely and exactly. ")
        prompts = [preamble + f"q{i:02d}?" for i in range(6)]
        h = serve.run(dep.options(num_replicas=3).bind(
            max_new_tokens=6, slots=4, prefill_chunk=8, page_tokens=8),
            name="fleetchaos", route_prefix="/fleetchaos")

        refs = {}
        for p in prompts:
            refs[p] = h.remote({"prompt": p}).result(timeout=300)["text"]
            assert refs[p], "reference generation empty"

        # wait for the digest long-poll so steering has a holder to aim
        # at; the poke requests double as cache warmers
        router = h._get_router()
        holder_key = None
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            h.remote({"prompt": prompts[0]}).result(timeout=300)
            with router._lock:
                if router._affinity.ready():
                    keys = [router._replica_key(r)
                            for r in router._replicas]
                    chain = router._affinity.chain_for(prompts[0])
                    if chain:
                        holder_key, depth = router._affinity.steer(
                            chain, keys)
                        if holder_key is not None and depth >= 2:
                            break
            holder_key = None
            time.sleep(0.5)
        assert holder_key is not None, "digests never advertised a holder"
        with router._lock:
            by_key = {router._replica_key(r): r for r in router._replicas}
        holder_rep = by_key[holder_key]
        pullers = [r for k, r in by_key.items() if k != holder_key]

        # fail-mark the holder: every burst request for the preamble now
        # falls back with a migration hint and PULLS from the holder
        router._note_result(holder_key, ok=False)

        n_burst = 16
        outs = [None] * n_burst
        errs = []

        def call(i):
            try:
                outs[i] = h.remote(
                    {"prompt": prompts[i % len(prompts)]}).result(
                        timeout=300)
            except Exception as e:  # noqa: BLE001 — asserted below
                errs.append(e)

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(n_burst)]
        for t in threads:
            t.start()
        t_kill = None
        victim = None
        if kills:
            time.sleep(0.25)  # land the kill while pulls are in flight
            victim = holder_rep if seed % 2 == 0 else pullers[0]
            t_kill = time.monotonic()
            try:
                ray_tpu.get(victim.handle_request.remote(
                    "__call__", ({"__die__": True},), {}), timeout=30)
            except Exception:
                pass  # the dying replica cannot answer

        if t_kill is not None:
            # re-steer within the fail-mark window, asserted at the
            # ROUTING layer (service-time recovery is asserted below —
            # burst drain on the survivors is capacity, not routing).
            # The dead replica keeps its digest advertisement until the
            # controller sweeps it out, so the completion-failure fail
            # mark is what diverts traffic. Route one through the exact
            # path a failed completion takes (_note_result is what
            # _watch_completion calls), lift the synthetic pre-burst
            # mark from the holder so only the victim is penalized, and
            # every fresh pick inside the window must avoid the corpse
            dead_key = router._replica_key(victim)
            router._note_result(holder_key, ok=True)
            router._note_result(dead_key, ok=False)
            with router._lock:
                chain = router._affinity.chain_for(prompts[0])
            for _ in range(8):
                idx, rep, _hint = router._pick("", chain)
                with router._lock:
                    router._inflight[idx] -= 1  # probe pick, not a call
                assert router._replica_key(rep) != dead_key, (
                    "a fresh pick landed on the dead replica inside "
                    "the fail-mark window")
        for t in threads:
            t.join()
        for o in outs:
            if o is not None:
                assert o["text"] == refs[o["prompt"]], (
                    "burst output diverged from the temperature-0 "
                    f"reference for {o['prompt']!r}")
        done = sum(1 for o in outs if o is not None)
        assert done >= 1, f"every burst request failed: {errs[:3]}"
        if not kills:
            assert not errs, f"requests failed without a kill: {errs[:2]}"

        # recovery: the health sweep replaces the victim and the
        # deployment keeps serving the exact references
        deadline = time.monotonic() + 60
        ok = 0
        while time.monotonic() < deadline and ok < 8:
            try:
                out = h.remote(
                    {"prompt": prompts[ok % len(prompts)]}).result(
                        timeout=30)
                assert out["text"] == refs[out["prompt"]], (
                    "post-kill output diverged: "
                    f"{out['text']!r} for {out['prompt']!r}")
                ok += 1
            except AssertionError:
                raise
            except Exception:
                time.sleep(0.3)
        assert ok >= 8, "fleet did not recover from the replica kill"

        def live_stats():
            with router._lock:
                reps = list(router._replicas)
            out = []
            for rep in reps:
                try:
                    out.append(ray_tpu.get(rep.handle_request.remote(
                        "scheduler_stats", (), {}), timeout=30))
                except Exception:
                    pass  # a replica mid-replacement; resampled below
            return out

        # migration evidence on the survivors: the fail-marked holder
        # forced fallback pulls, so SOMEONE recorded a completed pull
        # (odd seeds: holder alive) or a failed one (even seeds: the
        # exporter died under the puller)
        stats = live_stats()
        pulled = sum(s.get("migrations", 0) + s.get("migration_failures", 0)
                     for s in stats)
        assert pulled >= 1, (
            f"no migration was even attempted: "
            f"{[{k: s.get(k) for k in ('migrations', 'migration_failures')} for s in stats]}")
        assert sum(s.get("prefix_hits", 0) for s in stats) > 0, stats

        # paged-state hygiene on every live replica, gauge-proven
        deadline = time.monotonic() + 45
        clean = False
        while time.monotonic() < deadline and not clean:
            stats = live_stats()
            clean = len(stats) >= 2 and all(
                s["active_slots"] == 0 and s["radix_active_refs"] == 0
                and s["migrations_pending"] == 0
                and s["pages_in_use"] == s["radix_resident_pages"]
                for s in stats)
            if not clean:
                time.sleep(0.5)
        assert clean, (
            f"fleet paged state did not return to baseline: {stats}")

        serve.shutdown()
    finally:
        chaos.set_fault_controller(None)  # calm teardown
        _maybe_flight_dump()
        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
        cluster.shutdown()
        chaos.reset()


def _drain_pins_to_baseline(pins_before: int) -> None:
    """Shared tail of every channel-workload scenario: wait for the
    driver's channel pins to return to baseline, falling back to the
    departing-driver bulk release (the release RPCs run under the same
    fault schedule, so a dropped unpin must not fail the seed)."""
    from ray_tpu._private import api as _api

    def store_pins():
        core = _api._core
        stats = core._run(core.clients.get(core.supervisor_addr).call(
            "store_stats", timeout=60))
        return stats["pins_total"]

    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and store_pins() != pins_before:
        time.sleep(0.3)
    if store_pins() != pins_before:
        core = _api._core
        for _ in range(3):
            try:
                core._run(core.clients.get(core.supervisor_addr).call(
                    "store_release_client",
                    {"client": core._store_client_id}, timeout=10))
                break
            except Exception:
                continue
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and store_pins() != pins_before:
            time.sleep(0.3)
    assert store_pins() == pins_before, (
        "channel pins did not return to baseline after the controller "
        "restart scenario")


# outbound methods a stage/runner/learner WORKER may move during a
# controller outage: the p2p mirror-push stream (worker -> remote
# supervisor, the data plane itself) plus the recovery re-subscribe.
# Everything else — leases, task pushes/completions, kv, actor ops,
# object-store traffic — must stay at ZERO: the step in flight neither
# touched the (dead) controller nor fell back off the channel substrate.
_OUTAGE_ALLOWED_WORKER_METHODS = frozenset({
    "channel_push", "channel_write_chunk", "channel_commit",
    "collective_chunk",  # cross-node ring broadcast: worker <-> worker
    "subscribe",
})


def _worker_method_deltas(cluster):
    """Per-(worker, method) outbound rpc-call totals, scraped through each
    supervisor's metrics_all relay (no controller round trip — usable
    while it is down or freshly restarted)."""
    import asyncio as _asyncio
    import re as _re

    from ray_tpu._private.rpc import RpcClient

    async def scrape():
        found = {}
        for node in cluster.nodes:
            client = RpcClient(node.address)
            try:
                rows = await client.call("metrics_all", timeout=30)
            finally:
                await client.close()
            for name, text in rows:
                if not name.startswith("worker:"):
                    continue  # supervisors legitimately gossip/re-register
                for line in text.splitlines():
                    m = _re.match(
                        r'ray_tpu_rpc_client_calls_total\{'
                        r'method="([^"]+)"\} ([0-9.e+-]+)', line)
                    if m:
                        found[(name, m.group(1))] = float(m.group(2))
        return found

    return _asyncio.run(scrape())


def _assert_outage_deltas_clean(before: dict, after: dict) -> None:
    moved = {k: after[k] - before.get(k, 0.0)
             for k in after if after[k] - before.get(k, 0.0) > 0}
    bad = {k: v for k, v in moved.items()
           if k[1] not in _OUTAGE_ALLOWED_WORKER_METHODS}
    assert not bad, (
        f"workers issued control RPCs during the controller outage "
        f"(the data plane is not controller-free): {bad}")


def _restart_controller_mid(cluster, work, *, settle_s: float = 0.05,
                            join_s: float = 300.0):
    """Run ``work()`` in a thread and SIGKILL+restart the controller while
    it is in flight. Returns work()'s result; re-raises its error."""
    import threading

    box = {}

    def runner():
        try:
            box["out"] = work()
        except Exception as e:  # noqa: BLE001 — re-raised below
            box["err"] = e

    t = threading.Thread(target=runner)
    t.start()
    time.sleep(settle_s)
    cluster.restart_controller()
    t.join(timeout=join_s)
    assert not t.is_alive(), \
        "in-flight workload hung across the controller restart"
    cluster.wait_for_nodes(len(cluster.nodes), timeout=60)
    if "err" in box:
        raise box["err"]
    return box.get("out")


def _assert_cluster_recovered() -> None:
    """Post-recovery: the control plane schedules FRESH work (leases,
    worker spawns, actor registration all through the new incarnation)."""
    import ray_tpu

    @ray_tpu.remote
    def probe(x):
        return x + 1

    assert ray_tpu.get([probe.remote(i) for i in range(4)],
                       timeout=120) == [1, 2, 3, 4]


def _controller_chaos_pipeline(seed: int, cluster) -> None:
    """Controller killed MID PIPELINE FLUSH: the compiled-graph stage
    loops (cross-node chunked mirror pushes) must keep streaming through
    the outage with 0 control-plane RPCs, and every flush's loss must
    match the single-process reference exactly."""
    import jax
    import numpy as np
    import optax

    import ray_tpu
    from ray_tpu.models import presets
    from ray_tpu.models.transformer import init_params, loss_fn
    from ray_tpu.train import PipelineTrainer

    mcfg = presets.llama_debug(
        num_layers=2, vocab_size=128, max_seq_len=32, embed_dim=32,
        num_heads=2, num_kv_heads=1, mlp_dim=64)
    batch = np.random.default_rng(0).integers(
        0, 128, (16, 16)).astype(np.int32)
    M = 4

    params = init_params(mcfg, jax.random.PRNGKey(0))
    opt = optax.sgd(0.05)
    ost = opt.init(params)

    def mb_loss(p, toks):
        loss, _ = loss_fn(mcfg, p, {"tokens": toks})
        return loss

    gfn = jax.jit(jax.value_and_grad(mb_loss))
    ref_losses = []
    for _ in range(4):
        acc, losses = None, []
        for m in range(M):
            loss, g = gfn(params, batch[m * 4:(m + 1) * 4])
            losses.append(float(loss))
            acc = g if acc is None else jax.tree.map(
                lambda a, b: a + b, acc, g)
        grads = jax.tree.map(lambda g: g / M, acc)
        upd, ost = opt.update(grads, ost, params)
        params = optax.apply_updates(params, upd)
        ref_losses.append(float(np.mean(losses)))

    from ray_tpu._private import api as _api

    core = _api._core
    pins_before = core._run(core.clients.get(core.supervisor_addr).call(
        "store_stats", timeout=60))["pins_total"]
    trainer = PipelineTrainer(
        presets.pipeline_stage_defs(mcfg, 2, seed=0),
        num_microbatches=M, optimizer=("sgd", 0.05),
        stage_options=[{"resources": {"left": 1}},
                       {"resources": {"right": 1}}])
    assert trainer.is_channel_backed and trainer.channel_depth > 1, (
        "controller chaos run is not on the slot-ring channel substrate")
    try:
        for step in range(2):  # warm flushes: jits built, zero-RPC steady
            out = trainer.step(batch)
            assert abs(out["loss"] - ref_losses[step]) < 1e-4, (
                f"step {step}: loss {out['loss']} != {ref_losses[step]}")
        before = _worker_method_deltas(cluster)
        out = _restart_controller_mid(cluster,
                                      lambda: trainer.step(batch))
        assert abs(out["loss"] - ref_losses[2]) < 1e-4, (
            f"outage flush corrupted: {out['loss']} != {ref_losses[2]}")
        # 0 control RPCs through the outage: only the p2p mirror-push
        # stream (and recovery re-subscribes) may have moved on any
        # stage rank — no lease/task/kv/store/actor traffic
        _assert_outage_deltas_clean(before, _worker_method_deltas(cluster))
        out = trainer.step(batch)  # post-recovery flush
        assert abs(out["loss"] - ref_losses[3]) < 1e-4, (
            f"post-recovery flush corrupted: {out['loss']} != "
            f"{ref_losses[3]}")
    finally:
        trainer.shutdown()
    _drain_pins_to_baseline(pins_before)
    _assert_cluster_recovered()


def _controller_chaos_serve(seed: int, cluster) -> None:
    """Controller killed MID SERVE LOADGEN: the continuous scheduler's
    decode iterations run on the replica's own thread and the handle path
    is direct actor pushes — a request burst STRADDLING the outage must
    complete with outputs exactly equal to the pre-outage reference, and
    the deployment must keep serving after recovery."""
    import threading

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.llm import build_app

    h = serve.run(build_app(max_new_tokens=6, num_replicas=1,
                            slots=4, prefill_chunk=8),
                  name="ctrlchaos", route_prefix="/ctrlchaos")
    try:
        solo = h.remote({"prompt": "hello 123"}).result(timeout=300)
        assert solo["text"], "reference generation empty"

        outs = [None] * 8
        errs = []

        def call(i):
            try:
                outs[i] = h.remote(
                    {"prompt": "hello 123"}).result(timeout=300)
            except Exception as e:  # noqa: BLE001 — asserted below
                errs.append(e)

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(8)]

        def burst():
            for t in threads:
                t.start()
            for t in threads:
                t.join()

        _restart_controller_mid(cluster, burst, settle_s=0.2,
                                join_s=600.0)
        assert not errs, f"requests failed across the outage: {errs[:2]}"
        assert all(o is not None and o["text"] == solo["text"]
                   for o in outs), (
            "serve outputs diverged from the temperature-0 reference "
            "across the controller outage")
        st = h.scheduler_stats.remote().result(timeout=120)
        assert st["mode"] == "continuous", st
        assert st["retired"] >= 9, st  # every request decoded + retired
        # post-recovery: the deployment still serves
        again = h.remote({"prompt": "hello 123"}).result(timeout=300)
        assert again["text"] == solo["text"]
    finally:
        serve.shutdown()
    _assert_cluster_recovered()


def _controller_chaos_sebulba(seed: int, cluster) -> None:
    """Controller killed MID SEBULBA ITERATION: trajectory channels and
    the device-to-device param broadcast never touch the controller, so
    the iteration in flight must complete with the exact dynamic-loop
    reference loss and 0 control RPCs on every rank."""
    import ray_tpu
    from ray_tpu.rllib import IMPALAConfig
    from ray_tpu.rllib.algorithms.impala import IMPALA
    from ray_tpu.rllib.podracer import (ImpalaSebulbaProgram,
                                        SebulbaTopology)

    def make_cfg(topology):
        return (IMPALAConfig()
                .environment("CartPole-v1")
                .env_runners(num_env_runners=0 if topology == "dynamic"
                             else 1,
                             num_envs_per_env_runner=8,
                             rollout_fragment_length=16)
                .training(num_batches_per_iteration=1,
                          broadcast_interval=1,
                          model={"hiddens": (16,)})
                .learners(topology=topology)
                .debugging(seed=0))

    ref_algo = make_cfg("dynamic").build()
    try:
        ref_losses = [ref_algo.train()["total_loss"] for _ in range(4)]
    finally:
        ref_algo.stop()

    from ray_tpu._private import api as _api

    core = _api._core
    pins_before = core._run(core.clients.get(core.supervisor_addr).call(
        "store_stats", timeout=60))["pins_total"]
    config = make_cfg("sebulba")
    spec = config.rl_module_spec()
    program = ImpalaSebulbaProgram(
        spec=spec, loss_fn=IMPALA.loss_fn,
        loss_cfg={
            "gamma": config.gamma,
            "clip_rho": config.vtrace_clip_rho_threshold,
            "clip_c": config.vtrace_clip_c_threshold,
            "vf_loss_coeff": config.vf_loss_coeff,
            "entropy_coeff": config.entropy_coeff,
        },
        opt_cfg={"lr": config.lr, "grad_clip": config.grad_clip},
        broadcast_interval=1)
    topo = SebulbaTopology(
        config, program,
        runner_options=[{"resources": {"left": 1}}],
        learner_options=[{"resources": {"right": 1}}])
    assert topo.is_channel_backed, (
        "controller chaos run is not on the channel substrate")
    try:
        for step in range(2):  # warm: rendezvous, pins, jits
            out = topo.step()
            got = out["metrics"]["total_loss"]
            assert abs(got - ref_losses[step]) < 1e-4, (
                f"step {step}: loss {got} != {ref_losses[step]}")
        before = _worker_method_deltas(cluster)
        out = _restart_controller_mid(cluster, topo.step)
        got = out["metrics"]["total_loss"]
        assert abs(got - ref_losses[2]) < 1e-4, (
            f"outage iteration corrupted: {got} != {ref_losses[2]}")
        # 0 control RPCs through the outage on runner AND learner ranks:
        # trajectory-channel pushes + the param broadcast's ring frames
        # are worker<->worker, so only channel/push methods may move
        _assert_outage_deltas_clean(before, _worker_method_deltas(cluster))
        out = topo.step()  # post-recovery iteration
        got = out["metrics"]["total_loss"]
        assert abs(got - ref_losses[3]) < 1e-4, (
            f"post-recovery iteration corrupted: {got} != "
            f"{ref_losses[3]}")
    finally:
        topo.shutdown()
    _drain_pins_to_baseline(pins_before)
    _assert_cluster_recovered()


def run_controller_chaos(
    seed: int,
    *,
    drop_prob: float = 0.02,
    dup_prob: float = 0.05,
    delay_prob: float = 0.05,
    delay_max_ms: int = 20,
) -> None:
    """One seeded controller-HA chaos run (ISSUE 12, ROADMAP item 1).

    The controller is SIGKILLed and restarted from WAL+snapshot while a
    tentpole workload is MID-FLIGHT — ``seed % 3`` picks which: a
    pipeline flush (0), a serve request burst (1), or a Sebulba
    iteration (2), so the default 0..2 sweep covers all three. The
    drop/dup/delay schedule keeps attacking every control RPC
    throughout, INCLUDING the recovery handshake (node_register /
    node_sync / kv_put re-registrations). Required end state: the
    zero-RPC data plane streamed through the outage (in-band rpc-counter
    deltas stay 0 on every rank), post-recovery outputs/losses are
    EXACT, channel pins return to baseline, and the recovered control
    plane schedules fresh work.
    """
    import ray_tpu
    from ray_tpu._private import chaos
    from ray_tpu._private.chaos import FaultController
    from ray_tpu._private.config import Config
    from ray_tpu.cluster_utils import Cluster

    scenario = seed % 3
    cfg = Config.from_env()
    cfg.chaos_seed = seed
    cfg.chaos_drop_prob = drop_prob
    cfg.chaos_dup_prob = dup_prob
    cfg.chaos_delay_prob = delay_prob
    cfg.chaos_delay_max_ms = delay_max_ms
    cfg.chaos_methods = CHAOS_METHODS
    if scenario != 1:
        # cross-node channel hops stream as several chunk frames each
        cfg.object_transfer_chunk_bytes = 2048 if scenario == 0 else 1024

    cluster = Cluster(config=cfg)
    try:
        if scenario == 1:
            cluster.add_node(num_cpus=6)
            cluster.wait_for_nodes(1)
        else:
            cluster.add_node(num_cpus=4, resources={"left": 100})
            cluster.add_node(num_cpus=4, resources={"right": 100})
            cluster.wait_for_nodes(2)
        ray_tpu.init(address=cluster.address)
        chaos.set_fault_controller(FaultController(
            seed=seed, drop_prob=drop_prob, dup_prob=dup_prob,
            delay_prob=delay_prob, delay_max_ms=delay_max_ms,
            methods=CHAOS_METHODS))
        if scenario == 0:
            _controller_chaos_pipeline(seed, cluster)
        elif scenario == 1:
            _controller_chaos_serve(seed, cluster)
        else:
            _controller_chaos_sebulba(seed, cluster)
    finally:
        chaos.set_fault_controller(None)  # calm teardown
        _maybe_flight_dump()  # before shutdown, while dumps exist
        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
        cluster.shutdown()
        chaos.reset()


def _preempt_pipeline(seed: int, cluster) -> None:
    """A dp stage replica is hard-killed BETWEEN flushes (seeded victim +
    timing); the elastic trainer respawns it, reshards the declarative dp
    group at the next generation, and streams params + optimizer state to
    the joiner over collective.broadcast — no checkpoint restore. Every
    loss, including the step that healed, must match the single-process
    reference EXACTLY (between-flush kills are replayable), the
    steady-state zero-RPC counter must re-prove after the membership
    change, and pins must return to baseline."""
    import random

    import jax
    import numpy as np
    import optax

    from ray_tpu.models import presets
    from ray_tpu.models.transformer import init_params, loss_fn
    from ray_tpu.train import PipelineTrainer

    rng = random.Random(seed)
    mcfg = presets.llama_debug(
        num_layers=2, vocab_size=128, max_seq_len=32, embed_dim=32,
        num_heads=2, num_kv_heads=1, mlp_dim=64)
    batch = np.random.default_rng(0).integers(
        0, 128, (16, 16)).astype(np.int32)
    M, STEPS = 4, 6

    # single-process reference first: both dp rows see the SAME batch,
    # so the MEAN-reduced dp trajectory equals the single-row one
    params = init_params(mcfg, jax.random.PRNGKey(0))
    opt = optax.sgd(0.05)
    ost = opt.init(params)

    def mb_loss(p, toks):
        loss, _ = loss_fn(mcfg, p, {"tokens": toks})
        return loss

    gfn = jax.jit(jax.value_and_grad(mb_loss))
    ref_losses = []
    for _ in range(STEPS):
        acc, losses = None, []
        for m in range(M):
            loss, g = gfn(params, batch[m * 4:(m + 1) * 4])
            losses.append(float(loss))
            acc = g if acc is None else jax.tree.map(
                lambda a, b: a + b, acc, g)
        grads = jax.tree.map(lambda g: g / M, acc)
        upd, ost = opt.update(grads, ost, params)
        params = optax.apply_updates(params, upd)
        ref_losses.append(float(np.mean(losses)))

    import ray_tpu
    from ray_tpu._private import api as _api

    core = _api._core
    pins_before = core._run(core.clients.get(core.supervisor_addr).call(
        "store_stats", timeout=60))["pins_total"]
    trainer = PipelineTrainer(
        presets.pipeline_stage_defs(mcfg, 2, seed=0),
        num_microbatches=M, dp=2, optimizer=("sgd", 0.05), elastic=True,
        stage_options=[{"resources": {"left": 1}},
                       {"resources": {"right": 1}}])
    both = np.concatenate([batch, batch])
    try:
        kill_after = rng.choice([1, 2])  # seeded preemption schedule
        victim_r, victim_s = rng.randrange(2), rng.randrange(2)
        got = []
        for step in range(kill_after + 1):
            got.append(trainer.step(both)["loss"])
        victim = trainer._actors[victim_r][victim_s][0]
        ray_tpu.kill(victim)
        deadline = time.monotonic() + 60
        while not trainer._heal_pending and time.monotonic() < deadline:
            time.sleep(0.05)
        assert trainer._heal_pending, \
            "death fan-out never marked the elastic trainer for healing"
        got.append(trainer.step(both)["loss"])   # heals, then steps
        got.append(trainer.step(both)["loss"])   # warm post-heal flush
        # zero-steady-state-RPC re-proven AFTER the membership change:
        # only the mirror-push / collective frames may move on any rank
        before = _worker_method_deltas(cluster)
        got.append(trainer.step(both)["loss"])
        _assert_outage_deltas_clean(before, _worker_method_deltas(cluster))
        assert np.allclose(got, ref_losses, atol=1e-5), (
            f"elastic dp losses diverged from the uninterrupted "
            f"reference: {got} != {ref_losses}")
    finally:
        trainer.shutdown()

    from ray_tpu._private.elastic import m_joins, m_reshards
    assert m_joins.total() >= 1, "no elastic join was recorded"
    assert m_reshards.total() >= 1, "no dp reshard was recorded"
    _drain_pins_to_baseline(pins_before)


def _preempt_sebulba(seed: int, cluster) -> None:
    """An env-runner is hard-killed mid-run (seeded victim); the elastic
    topology respawns it into the same seed slot and the replacement
    rejoins over the next-epoch parameter broadcast (iteration-0
    sync_params — no checkpoint restore). Runner kills are NOT exactly
    replayable (live env state dies with the actor), so the contract is:
    training continues with finite losses, iteration reports advance,
    the steady-state zero-RPC counter re-proves after the membership
    change, and pins return to baseline."""
    import random

    import numpy as np

    import ray_tpu
    from ray_tpu._private import api as _api
    from ray_tpu.rllib import IMPALAConfig
    from ray_tpu.rllib.algorithms.impala import IMPALA
    from ray_tpu.rllib.podracer import (ImpalaSebulbaProgram,
                                        SebulbaTopology)

    rng = random.Random(seed)
    config = (IMPALAConfig()
              .environment("CartPole-v1")
              .env_runners(num_env_runners=2,
                           num_envs_per_env_runner=4,
                           rollout_fragment_length=16)
              .training(num_batches_per_iteration=1,
                        broadcast_interval=1,
                        model={"hiddens": (16,)})
              .learners(topology="sebulba")
              .debugging(seed=0))
    spec = config.rl_module_spec()
    program = ImpalaSebulbaProgram(
        spec=spec, loss_fn=IMPALA.loss_fn,
        loss_cfg={
            "gamma": config.gamma,
            "clip_rho": config.vtrace_clip_rho_threshold,
            "clip_c": config.vtrace_clip_c_threshold,
            "vf_loss_coeff": config.vf_loss_coeff,
            "entropy_coeff": config.entropy_coeff,
        },
        opt_cfg={"lr": config.lr, "grad_clip": config.grad_clip},
        broadcast_interval=1)

    core = _api._core
    pins_before = core._run(core.clients.get(core.supervisor_addr).call(
        "store_stats", timeout=60))["pins_total"]
    topo = SebulbaTopology(
        config, program, elastic=True,
        runner_options=[{"resources": {"left": 1}},
                        {"resources": {"right": 1}}],
        learner_options=[{"resources": {"right": 1}}])
    try:
        for _ in range(2):
            out = topo.step()
            assert np.isfinite(out["metrics"]["total_loss"])
        it_before = out["reports"][0]["iteration"]
        victim = topo._runners[rng.randrange(2)]
        ray_tpu.kill(victim)
        deadline = time.monotonic() + 60
        while not topo._heal_pending and time.monotonic() < deadline:
            time.sleep(0.05)
        assert topo._heal_pending, \
            "death fan-out never marked the elastic topology for healing"
        out = topo.step()            # heals (runner respawn + epoch bump),
        assert topo._epoch >= 1      # then streams the iteration
        assert np.isfinite(out["metrics"]["total_loss"])
        out = topo.step()            # warm post-heal iteration
        assert np.isfinite(out["metrics"]["total_loss"])
        # zero-steady-state-RPC re-proven AFTER the membership change
        before = _worker_method_deltas(cluster)
        out = topo.step()
        _assert_outage_deltas_clean(before, _worker_method_deltas(cluster))
        assert np.isfinite(out["metrics"]["total_loss"])
        assert out["reports"][0]["iteration"] > it_before, (
            "iterations did not advance across the runner preemption")
    finally:
        topo.shutdown()

    from ray_tpu._private.elastic import m_joins
    assert m_joins.total() >= 1, "no elastic join was recorded"
    _drain_pins_to_baseline(pins_before)


def _preempt_serve(seed: int, cluster) -> None:
    """The serve autoscaler REALLY drains a node: a 2-replica fleet on
    two dedicated pool nodes idles down to min_replicas=1, and with
    ``drain_nodes`` set the scale-down issues the controller's
    node_drain for the vacated node — which dies IMMEDIATELY (its
    supervisor is still healthy, so only the drain can explain the
    death; no health-grace debounce is involved) while the surviving
    replica keeps serving."""
    import ray_tpu
    from ray_tpu import serve

    @serve.deployment(
        name="fleet", num_replicas=2,
        ray_actor_options={"num_cpus": 0, "resources": {"pool": 1}},
        autoscaling_config={"min_replicas": 1, "max_replicas": 2,
                            "target_ongoing_requests": 2,
                            "drain_nodes": True})
    class Echo:
        def __call__(self, x):
            return {"echo": x}

    h = serve.run(Echo.bind(), name="fleet", route_prefix="/fleet")
    try:
        assert h.remote({"n": 1}).result(timeout=120) == {
            "echo": {"n": 1}}

        def pool_nodes():
            return [v for v in ray_tpu.nodes()
                    if v.get("total", {}).get("pool")]

        assert len([v for v in pool_nodes() if v["alive"]]) == 2

        # idle fleet -> autoscaler targets min_replicas=1 -> the popped
        # replica's node is vacated and must be DRAINED, not debounced
        deadline = time.monotonic() + 60
        drained = []
        while time.monotonic() < deadline and not drained:
            drained = [v for v in pool_nodes() if v.get("drained")]
            time.sleep(0.25)
        assert drained, (
            "autoscaler scale-down never drained the vacated node "
            f"(pool nodes: {pool_nodes()})")
        assert len(drained) == 1, drained
        alive = [v for v in pool_nodes() if v["alive"]]
        assert len(alive) == 1, (
            f"expected exactly one surviving pool node: {pool_nodes()}")
        # the fleet still serves from the surviving replica
        deadline = time.monotonic() + 60
        ok = False
        while time.monotonic() < deadline and not ok:
            try:
                ok = h.remote({"n": 2}).result(timeout=30) == {
                    "echo": {"n": 2}}
            except Exception:
                time.sleep(0.5)
        assert ok, "fleet stopped serving after the node drain"
    finally:
        serve.shutdown()


def run_preempt_chaos(
    seed: int,
    *,
    drop_prob: float = 0.02,
    dup_prob: float = 0.05,
    delay_prob: float = 0.05,
    delay_max_ms: int = 20,
) -> None:
    """One seeded preemption run (ISSUE 16, elastic world membership).

    Workers are killed and replaced on a seeded schedule mid-run —
    ``seed % 3`` picks the workload: an elastic dp pipeline (0, losses
    EXACT vs the uninterrupted reference), elastic Sebulba (1, runner
    respawn + rejoin over broadcast, not replayable so finite-and-
    advancing), or the serve fleet whose autoscaler really drains the
    vacated node (2). The drop/dup/delay schedule keeps attacking every
    control RPC throughout, INCLUDING the respawn/re-rendezvous/drain
    machinery. Required end state per scenario: automatic respawn +
    rejoin via broadcast with no checkpoint restore, the steady-state
    zero-RPC counter re-proven after the membership change, pins and
    gauges back to baseline.
    """
    import ray_tpu
    from ray_tpu._private import chaos
    from ray_tpu._private.chaos import FaultController
    from ray_tpu._private.config import Config
    from ray_tpu.cluster_utils import Cluster

    scenario = seed % 3
    cfg = Config.from_env()
    cfg.chaos_seed = seed
    cfg.chaos_drop_prob = drop_prob
    cfg.chaos_dup_prob = dup_prob
    cfg.chaos_delay_prob = delay_prob
    cfg.chaos_delay_max_ms = delay_max_ms
    cfg.chaos_methods = CHAOS_METHODS

    cluster = Cluster(config=cfg)
    try:
        if scenario == 2:
            # head holds the driver + serve controller; the two
            # cpu-less pool nodes hold exactly one replica each, so the
            # scale-down fully vacates (and may drain) one of them
            cluster.add_node(num_cpus=6)
            cluster.add_node(num_cpus=0, resources={"pool": 1})
            cluster.add_node(num_cpus=0, resources={"pool": 1})
            cluster.wait_for_nodes(3)
        else:
            cluster.add_node(num_cpus=4, resources={"left": 100})
            cluster.add_node(num_cpus=4, resources={"right": 100})
            cluster.wait_for_nodes(2)
        ray_tpu.init(address=cluster.address)
        chaos.set_fault_controller(FaultController(
            seed=seed, drop_prob=drop_prob, dup_prob=dup_prob,
            delay_prob=delay_prob, delay_max_ms=delay_max_ms,
            methods=CHAOS_METHODS))
        if scenario == 0:
            _preempt_pipeline(seed, cluster)
        elif scenario == 1:
            _preempt_sebulba(seed, cluster)
        else:
            _preempt_serve(seed, cluster)
    finally:
        chaos.set_fault_controller(None)  # calm teardown
        _maybe_flight_dump()  # before shutdown, while dumps exist
        if ray_tpu.is_initialized():
            ray_tpu.shutdown()
        cluster.shutdown()
        chaos.reset()


def _run_one(seed: int, args) -> None:
    global _CURRENT_SEED
    _CURRENT_SEED = seed
    if args.flight_dump:
        os.environ["RAY_TPU_CHAOS_FLIGHT_DUMP"] = args.flight_dump
    if args.controller:
        run_controller_chaos(
            seed,
            drop_prob=args.drop, dup_prob=args.dup, delay_prob=args.delay,
            delay_max_ms=args.delay_max_ms)
        return
    if args.preempt:
        run_preempt_chaos(
            seed,
            drop_prob=args.drop, dup_prob=args.dup, delay_prob=args.delay,
            delay_max_ms=args.delay_max_ms)
        return
    if args.podracer:
        run_podracer_chaos(
            seed,
            drop_prob=args.drop, dup_prob=args.dup, delay_prob=args.delay,
            delay_max_ms=args.delay_max_ms, kills=not args.no_kills)
        return
    if args.serve:
        run_serve_chaos(
            seed,
            drop_prob=args.drop, dup_prob=args.dup, delay_prob=args.delay,
            delay_max_ms=args.delay_max_ms, kills=not args.no_kills)
        return
    if args.fleet:
        run_fleet_chaos(
            seed,
            drop_prob=args.drop, dup_prob=args.dup, delay_prob=args.delay,
            delay_max_ms=args.delay_max_ms, kills=not args.no_kills)
        return
    if args.pipeline:
        # both schedules per seed: the PR-8 one-chunk chain, then the
        # interleaved V=2 variant (twice the cross-node act/grad hops,
        # same actors) under the identical fault schedule
        for v in (1, 2):
            run_pipeline_chaos(
                seed,
                drop_prob=args.drop, dup_prob=args.dup,
                delay_prob=args.delay,
                delay_max_ms=args.delay_max_ms, kills=not args.no_kills,
                virtual_stages=v)
        # then the full 3D grid (ISSUE 17): tp=2 x dp=2 x S=2, eight
        # actors across the same two nodes — every pp hop still crosses
        # nodes under the identical fault schedule while the tp
        # partial-sum reduces run same-node
        run_pipeline_chaos(
            seed,
            drop_prob=args.drop, dup_prob=args.dup,
            delay_prob=args.delay,
            delay_max_ms=args.delay_max_ms, kills=not args.no_kills,
            virtual_stages=1, tensor_parallel=2, dp=2)
        return
    if args.data:
        run_data_chaos(
            seed,
            drop_prob=args.drop, dup_prob=args.dup, delay_prob=args.delay,
            delay_max_ms=args.delay_max_ms, kills=not args.no_kills)
        return
    if args.shuffle:
        run_shuffle_chaos(
            seed,
            drop_prob=args.drop, dup_prob=args.dup, delay_prob=args.delay,
            delay_max_ms=args.delay_max_ms, kills=not args.no_kills)
        return
    if args.collective_overlap:
        run_collective_overlap_chaos(
            seed,
            drop_prob=args.drop, dup_prob=args.dup, delay_prob=args.delay,
            delay_max_ms=args.delay_max_ms, kills=not args.no_kills)
        return
    if args.collective:
        run_collective_chaos(
            seed,
            drop_prob=args.drop, dup_prob=args.dup, delay_prob=args.delay,
            delay_max_ms=args.delay_max_ms, kills=not args.no_kills)
        return
    run_chaos_workload(
        seed,
        drop_prob=args.drop, dup_prob=args.dup, delay_prob=args.delay,
        delay_max_ms=args.delay_max_ms,
        kills=not args.no_kills, train=not args.no_train,
        # the DEFAULT sweep now also restarts the controller mid-run
        # (ISSUE 12): recovery is part of the baseline fault envelope
        controller_restart=not args.no_controller_restart)
    if not args.no_preempt:
        # preemption joined the default sweep (ISSUE 16): every default
        # seed also runs one elastic-membership scenario (seed%3 picks
        # pipeline-dp / Sebulba / serve-fleet drain)
        run_preempt_chaos(
            seed,
            drop_prob=args.drop, dup_prob=args.dup, delay_prob=args.delay,
            delay_max_ms=args.delay_max_ms)
    if not args.no_shuffle:
        # the streaming all-to-all joined the default sweep (ISSUE 19):
        # every default seed also attacks the exchange mesh (parity vs
        # the barrier baseline + a producer/consumer kill by seed parity)
        run_shuffle_chaos(
            seed,
            drop_prob=args.drop, dup_prob=args.dup, delay_prob=args.delay,
            delay_max_ms=args.delay_max_ms, kills=not args.no_kills)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--seeds", type=int, default=10,
                        help="number of seeds to sweep (from --start)")
    parser.add_argument("--start", type=int, default=0)
    parser.add_argument("--one", type=int, default=None,
                        help="run exactly this seed in-process (replay mode)")
    parser.add_argument("--drop", type=float, default=0.02)
    parser.add_argument("--dup", type=float, default=0.05)
    parser.add_argument("--delay", type=float, default=0.05)
    parser.add_argument("--delay-max-ms", type=int, default=20)
    parser.add_argument("--no-kills", action="store_true")
    parser.add_argument("--no-train", action="store_true")
    parser.add_argument("--collective", action="store_true",
                        help="attack the p2p collective data plane (ring "
                             "chunk frames + participant kill) instead of "
                             "the task/actor/training workload")
    parser.add_argument("--collective-overlap", action="store_true",
                        help="attack the ASYNC overlap collective path: "
                             "in-flight allreduce_coalesced_async handles "
                             "with out-of-order waits under drop/dup/delay "
                             "+ a participant kill mid-flight")
    parser.add_argument("--pipeline", action="store_true",
                        help="attack the MPMD pipeline trainer (the "
                             "plain and V=2 interleaved schedules, then "
                             "the tp=2 x dp=2 x S=2 3D grid): "
                             "cross-node "
                             "1F1B microbatch pushes (chunked channel "
                             "frames) under drop/dup/delay must train to "
                             "EXACT reference losses; a mid-flush stage "
                             "kill must fail clean and unwind")
    parser.add_argument("--data", action="store_true",
                        help="attack the streaming data plane: every "
                             "reader->transform->batcher->consumer hop a "
                             "cross-node chunked push under drop/dup/delay; "
                             "two shuffled epochs must match the task-based "
                             "loader's batches EXACTLY, a mid-epoch reader "
                             "kill must fail clean and unwind pins")
    parser.add_argument("--shuffle", action="store_true",
                        help="attack the streaming all-to-all exchange "
                             "(ISSUE 19): an R x C producer/consumer "
                             "mesh split across 2 nodes, bucket frames "
                             "as small chunked pushes under "
                             "drop/dup/delay; two shuffled epochs must "
                             "match the barrier AllToAll baseline "
                             "EXACTLY, then a mid-shuffle kill (even "
                             "seeds a producer, odd seeds a consumer) "
                             "must close the whole mesh clean and "
                             "unwind pins")
    parser.add_argument("--no-shuffle", action="store_true",
                        help="default workload only: skip the exchange "
                             "scenario that joined the default sweep "
                             "with ISSUE 19")
    parser.add_argument("--flight-dump", default="",
                        help="directory for a merged flight-recorder "
                             "timeline (Perfetto JSON) per seed; a red "
                             "seed ALWAYS dumps (to a temp dir when this "
                             "is unset) so failures leave a debuggable "
                             "trace instead of just an exit code")
    parser.add_argument("--controller", action="store_true",
                        help="controller-HA mode: SIGKILL + restart the "
                             "controller MID-WORKLOAD (seed%%3 picks a "
                             "pipeline flush / serve burst / Sebulba "
                             "iteration) under drop/dup/delay — the "
                             "data plane must stream through the outage "
                             "(0 control RPCs, counter-asserted), "
                             "outputs/losses exact, pins to baseline, "
                             "fresh work schedulable after recovery")
    parser.add_argument("--no-controller-restart", action="store_true",
                        help="default workload only: skip the mid-run "
                             "controller kill+restart (it is part of "
                             "the default fault envelope since ISSUE 12)")
    parser.add_argument("--preempt", action="store_true",
                        help="elastic-membership mode (ISSUE 16): kill "
                             "and replace workers on a seeded schedule "
                             "mid-run — seed%%3 picks an elastic dp "
                             "pipeline (exact losses vs the "
                             "uninterrupted reference), elastic Sebulba "
                             "(runner respawn + rejoin over broadcast), "
                             "or the serve fleet whose autoscaler "
                             "drains the vacated node; zero-RPC steady "
                             "state re-proven after every membership "
                             "change, pins back to baseline")
    parser.add_argument("--no-preempt", action="store_true",
                        help="default workload only: skip the elastic "
                             "preemption scenario that joined the "
                             "default sweep with ISSUE 16")
    parser.add_argument("--podracer", action="store_true",
                        help="attack the Sebulba RL topology: cross-node "
                             "trajectory-channel pushes + ring parameter "
                             "broadcasts under drop/dup/delay must match "
                             "the dynamic-loop reference losses; a "
                             "mid-iteration runner/learner kill must fail "
                             "clean and unwind")
    parser.add_argument("--serve", action="store_true",
                        help="attack the paged+prefix serve scheduler: a "
                             "shared-prefix burst with a mid-burst replica "
                             "kill must yield exact-or-clean-error outputs, "
                             "recover, and return every page and radix "
                             "refcount to baseline (gauge-proven); cancel-"
                             "mid-stream must leave the cached prefix "
                             "uncontaminated for a later admit")
    parser.add_argument("--fleet", action="store_true",
                        help="attack the fleet serve path (ISSUE 18): "
                             "prefix-affinity steering with a replica "
                             "hard-killed mid-migration — even seeds kill "
                             "the page-export HOLDER, odd seeds kill a "
                             "PULLER; outputs must be exact or cleanly "
                             "errored, the router must re-steer within "
                             "the fail-mark window, and every live "
                             "replica's paged state must return to "
                             "baseline (gauge-proven)")
    args = parser.parse_args()

    if args.one is not None:
        _run_one(args.one, args)
        print(f"seed {args.one}: OK")
        return 0

    for seed in range(args.start, args.start + args.seeds):
        t0 = time.monotonic()
        child = [sys.executable, "-m", "ray_tpu.scripts.chaos_soak",
                 "--one", str(seed),
                 "--drop", str(args.drop), "--dup", str(args.dup),
                 "--delay", str(args.delay),
                 "--delay-max-ms", str(args.delay_max_ms)]
        if args.flight_dump:
            child.extend(["--flight-dump", args.flight_dump])
        if args.no_kills:
            child.append("--no-kills")
        if args.no_train:
            child.append("--no-train")
        if args.no_controller_restart:
            child.append("--no-controller-restart")
        if args.no_preempt:
            child.append("--no-preempt")
        if args.no_shuffle:
            child.append("--no-shuffle")
        if args.shuffle:
            child.append("--shuffle")
        if args.data:
            child.append("--data")
        if args.controller:
            child.append("--controller")
        if args.preempt:
            child.append("--preempt")
        if args.collective:
            child.append("--collective")
        if args.collective_overlap:
            child.append("--collective-overlap")
        if args.pipeline:
            child.append("--pipeline")
        if args.podracer:
            child.append("--podracer")
        if args.serve:
            child.append("--serve")
        if args.fleet:
            child.append("--fleet")
        proc = subprocess.run(child)
        took = time.monotonic() - t0
        if proc.returncode != 0:
            print(f"FIRST FAILING SEED: {seed} (rc={proc.returncode}, "
                  f"{took:.0f}s) — replay with:\n"
                  f"  python -m ray_tpu.scripts.chaos_soak --one {seed}")
            return 1
        print(f"seed {seed}: OK ({took:.0f}s)")
    print(f"all {args.seeds} seeds passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
