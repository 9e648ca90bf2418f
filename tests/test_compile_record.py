"""The compile record (ISSUE 57): what every jitted program cost this
process, by name, from ``jax.monitoring`` (``_private/compile_cache.py``),
where it is read (``scheduler_stats()``, the ``profile`` RPC's ``compiles``
kind, ``flight`` spans, profiler host events), and the replica's build by
phase. CPU, toy sizes: counts and structure, never a time; the one sum of
seconds asserted is a clock's identity with itself."""

import json
import os
import subprocess
import sys
import threading

import pytest

from ray_tpu._private import compile_cache, flight, profiling

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def recorder_on():
    was = flight.is_enabled()
    flight.configure(enabled=True)
    yield
    flight.configure(enabled=was)


def rows():
    return compile_cache.record()["jit_programs"]


def test_a_program_is_a_row_under_its_own_name_and_a_new_shape_raises_it():
    import jax
    import jax.numpy as jnp

    assert compile_cache.watch() is True

    def compile_record_probe(x):
        return jnp.tanh(x) * 2 + 1

    def compile_record_bystander(x):
        return x - 1

    probe, other = jax.jit(compile_record_probe), jax.jit(
        compile_record_bystander)
    probe(jnp.ones((3,)))
    other(jnp.ones((3,)))
    first = rows()
    assert first["compile_record_probe"]["n"] == 1
    assert first["compile_record_bystander"]["n"] == 1
    # tanh and multiply were traced INSIDE the probe: the probe's time
    for stage in ("trace_s", "lower_s", "compile_s"):
        assert first["compile_record_probe"][stage] > 0, stage
    probe(jnp.ones((3,)))  # a shape it has seen: nothing compiles
    assert rows()["compile_record_probe"]["n"] == 1
    before = compile_cache.record()
    probe(jnp.ones((5,)))
    after = compile_cache.record()
    assert after["jit_programs"]["compile_record_probe"]["n"] == 2
    assert after["jit_programs"]["compile_record_bystander"]["n"] == 1
    # the totals are the table's sums, column by column
    for total, column in zip(compile_cache.TOTALS, compile_cache.COLUMNS):
        assert after[total] == pytest.approx(sum(
            row[column] for row in after["jit_programs"].values())), total
    assert after["jit_compile_events"] - before["jit_compile_events"] >= 1
    names = {e["name"] for e in flight.local_timeline()}
    for span in ("jit.trace", "jit.lower", "jit.compile"):
        assert f"{span} compile_record_probe" in names, span


def test_a_trace_inside_a_trace_is_the_outer_programs_time():
    import jax
    import jax.numpy as jnp

    compile_cache.watch()

    @jax.jit
    def compile_record_inner(x):
        return jnp.sin(x)

    @jax.jit
    def compile_record_outer(x):
        return compile_record_inner(x) * 2

    compile_record_outer(jnp.ones((7,)))
    table = rows()
    assert table["compile_record_outer"]["n"] == 1
    assert "compile_record_inner" not in table


def test_no_second_is_counted_twice_whatever_nests():
    """The listeners on hand-made events (the seconds are given, not
    measured): an eager operation that compiles inside a function traced
    inside a program is its own row, and comes off the PROGRAM's trace."""
    trace, lower, backend = compile_cache._STAGES
    miss = "/jax/compilation_cache/cache_misses"
    before = compile_cache.record()
    compile_cache._on_start(trace, 0.0, fun_name="nest_program")
    compile_cache._on_start(trace, 0.0, fun_name="nest_inner")
    compile_cache._on_start(backend, 0.0, fun_name="jit(nest_eager)")
    compile_cache._on_event(miss)
    compile_cache._on_duration(backend, 2.0, fun_name="jit(nest_eager)")
    compile_cache._on_duration(trace, 3.0, fun_name="nest_inner")
    compile_cache._on_duration(trace, 5.0, fun_name="nest_program")
    compile_cache._on_start(lower, 0.0, fun_name="jit(nest_program)")
    compile_cache._on_duration(lower, 1.5, fun_name="jit(nest_program)")
    after = compile_cache.record()
    table = after["jit_programs"]
    assert "nest_inner" not in table
    assert table["nest_eager"] == dict(
        dict.fromkeys(compile_cache.COLUMNS, 0), n=1, compile_s=2.0,
        cache_misses=1)
    assert table["nest_program"]["trace_s"] == 3.0  # 5 less the 2 inside
    assert table["nest_program"]["lower_s"] == 1.5
    grew = sum(after[k] - before[k]
               for k in ("jit_trace_s", "jit_lower_s", "jit_compile_s"))
    assert grew == pytest.approx(6.5)  # the wall time of the two stages
    assert compile_cache._stack() == []


def test_watch_twice_registers_once():
    from jax._src import monitoring

    assert compile_cache.watch() and compile_cache.watch()
    assert monitoring.get_event_duration_listeners().count(
        compile_cache._on_duration) == 1
    assert monitoring.get_event_listeners().count(
        compile_cache._on_event) == 1
    assert monitoring.get_scalar_listeners().count(
        compile_cache._on_start) == 1


def run_python(code, **env):
    return subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, text=True,
        capture_output=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT, **env))


def test_a_process_that_never_imported_jax_is_not_made_to():
    out = run_python(
        "import sys\n"
        "from ray_tpu._private import compile_cache, profiling\n"
        "assert compile_cache.watch() is False\n"
        "got = profiling.collect('compiles')\n"
        "assert got['watching'] is False and not got['jax_initialized']\n"
        "assert got['jit_compile_events'] == 0 and got['jit_programs'] == {}\n"
        "assert 'jax' not in sys.modules\n"
        "print('off jax')\n")
    assert out.returncode == 0 and "off jax" in out.stdout, out.stderr[-2000:]


CACHED = (
    "import json, jax, jax.numpy as jnp\n"
    "from ray_tpu._private import compile_cache\n"
    "compile_cache.watch()\n"
    "def cached_probe(x):\n"
    "    return jnp.cos(x) + 1\n"
    "jax.jit(cached_probe)(jnp.ones((4,)))\n"
    "jax.jit(cached_probe)(jnp.ones((6,)))\n"
    "print('RECORD ' + json.dumps(compile_cache.record()))\n")


def test_a_second_process_on_the_same_cache_directory_reads_hits(tmp_path):
    env = {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache"),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
           "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1"}
    records = []
    for _ in range(2):
        out = run_python(CACHED, **env)
        assert out.returncode == 0, out.stderr[-2000:]
        records.append(json.loads([ln for ln in out.stdout.splitlines()
                                   if ln.startswith("RECORD ")][0][7:]))
    cold, warm = records
    assert cold["jit_compile_events"] == warm["jit_compile_events"] > 0
    assert (cold["jit_cache_hits"], cold["jit_cache_misses"]) == (
        0, cold["jit_compile_events"])
    assert (warm["jit_cache_hits"], warm["jit_cache_misses"]) == (
        warm["jit_compile_events"], 0)
    # a cache event went to the compile that enclosed it: row by row
    for name, row in warm["jit_programs"].items():
        assert row["cache_hits"] == row["n"], name
    assert warm["jit_programs"]["cached_probe"]["cache_hits"] == 2
    assert compile_cache.ORPHAN not in warm["jit_programs"]


def test_collect_names_the_fourth_kind():
    with pytest.raises(ValueError, match="stack|memory|device|compiles"):
        profiling.collect("compile")
    got = profiling.collect("compiles")
    assert got["pid"] == os.getpid()
    assert set(compile_cache.TOTALS) < set(got) and "jit_programs" in got


def test_the_profile_rpcs_compiles_kind_returns_a_workers_record(ray_init):
    import ray_tpu
    from ray_tpu.util import state as state_api

    @ray_tpu.remote
    class Jitter:
        def __init__(self):
            import jax  # noqa: F401

        def run(self, n):
            import jax
            import jax.numpy as jnp

            def worker_probe(x):
                return x * 3

            return float(jax.jit(worker_probe)(jnp.ones((n,)))[0])

    a = Jitter.options(name="compileprof").remote()
    ray_tpu.get(a.run.remote(2))  # before anyone listened: not in the record
    first = state_api.profile_actor("compileprof", kind="compiles")
    assert first["watching"] and first["jax_initialized"]
    assert first["pid"] != os.getpid()
    ray_tpu.get(a.run.remote(3))
    second = state_api.profile_actor("compileprof", kind="compiles")
    assert second["jit_programs"]["worker_probe"]["n"] == 1
    assert (second["jit_compile_events"]
            > first["jit_compile_events"])
    ray_tpu.kill(a)


def _server():
    from ray_tpu.serve.llm import LLMServerImpl

    return LLMServerImpl(preset="llama_debug", max_new_tokens=4,
                         share_weights=False, slots=2, prefill_chunk=8)


def test_the_builds_three_phases_are_stamped_and_sum_to_the_clocks_span():
    from ray_tpu.serve.llm import BUILD_KEYS, BUILD_PHASES

    made = {}

    def build():  # a thread of its own: a ring of its own
        made["srv"] = _server()
        made["tid"] = threading.get_ident()

    t = threading.Thread(target=build, name="build")
    t.start()
    t.join()
    srv = made["srv"]
    try:
        stats = srv.scheduler_stats()
        seconds = [stats[k] for k in BUILD_KEYS]
        assert all(s > 0 for s in seconds), seconds
        # the build is over: a later snapshot reads the same seconds
        assert [srv.scheduler_stats()[k] for k in BUILD_KEYS] == seconds
        spans = [e for e in flight.local_timeline()
                 if e["name"] in BUILD_PHASES
                 and e["tid"].endswith(f"({made['tid']})")]
        assert [e["name"] for e in spans] == list(BUILD_PHASES)
        # leaf phases of one clock: each starts where the one before ended,
        # and together they are the clock's span, first start to last end
        # (wall-clock microseconds as floats: good to a quarter of one)
        for a, b in zip(spans, spans[1:]):
            assert b["ts"] == pytest.approx(a["ts"] + a["dur"], abs=1.0)
        span_us = spans[-1]["ts"] + spans[-1]["dur"] - spans[0]["ts"]
        assert sum(seconds) * 1e6 == pytest.approx(span_us, abs=2.0)
        # what the build compiled is in the same snapshot
        assert stats["jit_compile_events"] > 0
        assert stats["jit_programs"]
    finally:
        srv.shutdown()


def test_a_program_compiled_after_set_up_shows_three_ways(tmp_path):
    import jax
    import jax.numpy as jnp

    from perfbench.lib import trace

    srv = _server()
    try:
        def late_program(x):
            return jnp.exp(x) - 1

        late = jax.jit(late_program)
        late(jnp.ones((2,)))
        before = srv.scheduler_stats()
        trace.start(str(tmp_path))
        late(jnp.ones((9,)))  # a shape it has not seen
        loaded = trace.load(trace.stop(str(tmp_path)))
        after = srv.scheduler_stats()
    finally:
        srv.shutdown()
    host = {name for name, _, _ in loaded["host"]}
    assert "jit.compile late_program" in host, sorted(
        n for n in host if n.startswith("jit."))
    assert {"jit.trace late_program", "jit.lower late_program"} <= host
    assert (after["jit_programs"]["late_program"]["n"]
            == before["jit_programs"]["late_program"]["n"] + 1 == 2)
    # what a run's ``delta`` note prints: the numeric keys' difference
    assert after["jit_compile_events"] - before["jit_compile_events"] >= 1
