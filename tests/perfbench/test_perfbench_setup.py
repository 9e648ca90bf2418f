"""The six readers of set-up's own record (ISSUE 57): the entries
``BENCHMARK.json`` lists for them, held by name and order behind what was
there; the readers on hand-made ``ctx``s (a program without the record: 0;
with it and nothing counted: nothing; the arithmetic); a traced line of
every serving cell with the six and with the parent's six zeros; and ONE CPU
rehearsal, ``tiny_chat`` traced over a tiny manifest MADE HERE (the fifth,
``tiny/BENCHMARK_turns.json``, plus the six names, written under
``tmp_path`` as ``test_perfbench_mellum.py`` makes its own: an older test
holds that no sixth manifest file and no further rehearsal script come).
Counts, shares and structure only: no number here is a device number."""

import copy
import json
import os
import subprocess
import sys

import pytest

from perfbench.lib import contract, setup_work
from perfbench.lib import manifest as manifest_lib
from tests.perfbench import held, rehearse_turns
from tests.perfbench.test_perfbench_additions import add_a_prs_entries

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = manifest_lib.load()
TINY_DIR = os.path.join(HERE, "tiny")
TURNS = manifest_lib.load(rehearse_turns.TURNS_MANIFEST)
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}

# name -> (unit, better, source, layer), in the order they were appended
ROW = {
    "setup.jit_trace_lower_s":
        ("s", "lower", "program_counter", "jitted step"),
    "setup.jit_compile_s": ("s", "lower", "program_counter", "jitted step"),
    "setup.jit_cache_hit_share":
        ("%", "higher", "program_counter", "jitted step"),
    "setup.jit_compile_events":
        ("programs", "lower", "program_counter", "jitted step"),
    "setup.weights_s":
        ("s", "lower", "program_span", "handle, router and replica"),
    "setup.scheduler_build_s": ("s", "lower", "program_span", "paging"),
}
SIX = list(ROW)
SERVING = ["mistral7b_chat", "mistral7b_docs", "olmoe_reason",
           "minicpm_sala_longdoc", "brumby_longgen", "mellum2_shortlong",
           "keye_longctx", "glm47_flash_longdocs"]
TRAINING = ["gpt2s_train", "mistral7b_train_4chip"]
FREE = 63  # what PR 56 left: the six stand behind it


def bare(manifest):
    return copy.deepcopy({k: v for k, v in manifest.items() if k != "_dir"})


def first_of(manifest):
    return [m["name"] for m in manifest["per_layer"]].index(SIX[0])


def pr57_entries(manifest):
    """This PR's hold of its own entries: the six in their order from where
    the first stands, never before ``FREE``, each as it was appended, its
    cells the eight serving cells FIRST (a later cell joins behind them)."""
    i = first_of(manifest)
    assert i >= FREE
    rows = manifest["per_layer"][i:i + len(SIX)]
    assert [m["name"] for m in rows] == SIX
    for m in rows:
        assert (m["unit"], m["better"], m["source"], m["layer"]) == ROW[
            m["name"]]
        assert m["moves"] == "setup_s"
        assert m["workloads"][:len(SERVING)] == SERVING
        assert not set(TRAINING) & set(m["workloads"])
        assert callable(manifest_lib.metric_reader(m["name"]))


def without_this_pr(manifest):
    """The manifest this PR found: ``per_layer`` cut at this PR's first
    entry, whatever followed it taken out too."""
    out = bare(manifest)
    out["per_layer"] = out["per_layer"][:first_of(out)]
    return out


# ------------------------------------------------------ the manifest's part


def test_this_pr_added_by_adding_and_holds_its_own_entries():
    pr57_entries(BENCH)
    for check in held.CHECKS + held.FOUND:  # every earlier PR's hold
        check(BENCH)
    parent = without_this_pr(BENCH)
    assert "setup." not in json.dumps(parent["per_layer"])
    assert len(parent["per_layer"]) == FREE
    held.only_added(parent, BENCH)
    held.static_rules(BENCH)
    # nothing else of the manifest grew with this PR
    for group in ("configs", "workloads", "end_to_end"):
        assert parent[group] == bare(BENCH)[group]


@pytest.mark.parametrize("tags", [("later",), ("later", "and_later")],
                         ids=["one_pr_behind_it", "two_prs_behind_it"])
def test_its_hold_accepts_what_later_prs_add(tags):
    later = bare(BENCH)
    for tag in tags:
        add_a_prs_entries(later, tag)
    pr57_entries(later)
    held.only_added(BENCH, later)
    held.static_rules(later)
    held.only_added(without_this_pr(later), later)


@pytest.mark.parametrize("edit", [
    lambda m, i: m["per_layer"].insert(i, m["per_layer"].pop(i + 1)),
    lambda m, i: m["per_layer"].pop(i + 4),
    lambda m, i: m["per_layer"][i + 2]["workloads"].insert(0, "gpt2s_train"),
    lambda m, i: m["per_layer"][i + 3]["workloads"].append(
        "mistral7b_train_4chip"),
    lambda m, i: m["per_layer"][i + 1]["workloads"].remove("keye_longctx"),
    lambda m, i: m["per_layer"][i].update(moves="gap_p95_ms"),
    lambda m, i: m["per_layer"][i + 5].update(source="device_trace"),
    lambda m, i: m["per_layer"][i + 3].update(better="higher"),
    lambda m, i: m["per_layer"].insert(0, m["per_layer"].pop(i)),
], ids=["two_readers_swapped", "a_reader_taken_away",
        "a_training_cell_put_first", "a_training_cell_listed",
        "a_serving_cell_taken_out", "set_up_moved_to_the_gap",
        "a_span_called_a_device_trace", "more_programs_called_better",
        "a_reader_put_first"])
def test_its_hold_refuses_an_edit_of_its_entries(edit):
    edited = bare(BENCH)
    edit(edited, first_of(edited))
    with pytest.raises((AssertionError, KeyError, IndexError, ValueError)):
        pr57_entries(edited)


def test_the_six_are_listed_for_the_serving_cells_and_no_training_cell():
    for cell in SERVING + TRAINING:
        traced = {m["name"] for m in manifest_lib.metrics_for(
            BENCH, cell, True)} & set(SIX)
        assert traced == (set(SIX) if cell in SERVING else set()), cell
        assert not {m["name"] for m in manifest_lib.metrics_for(
            BENCH, cell, False)} & set(SIX)


def tiny_manifest(tmp_path):
    """The fifth tiny manifest plus the six names, each as ``BENCHMARK.json``
    lists it but for its cells; paths made absolute, since the file stands
    outside the tiny tree."""
    tiny = {k: v for k, v in copy.deepcopy(TURNS).items() if k != "_dir"}
    tiny["paths"] = [TINY_DIR]
    for config in tiny["configs"]:
        config["file"] = os.path.join(TINY_DIR, config["file"])
    rows = {m["name"]: m for m in BENCH["per_layer"]}
    for name in SIX:
        tiny["per_layer"].append(dict(rows[name],
                                      workloads=["tiny_chat", "tiny_docs"]))
    path = tmp_path / "BENCHMARK_setup.json"
    path.write_text(json.dumps(tiny))
    return str(path)


def test_the_tiny_manifest_is_the_fifth_plus_the_six_names(tmp_path):
    tiny = manifest_lib.load(tiny_manifest(tmp_path))
    n = len(TURNS["per_layer"])
    assert tiny["per_layer"][:n] == TURNS["per_layer"]
    assert [m["name"] for m in tiny["per_layer"][n:]] == SIX
    assert tiny["workloads"] == TURNS["workloads"]
    assert tiny["end_to_end"] == TURNS["end_to_end"]
    for cell, listed in (("tiny_chat", set(SIX)), ("tiny_train", set())):
        assert {m["name"] for m in manifest_lib.metrics_for(
            tiny, cell, True)} & set(SIX) == listed


# --------------------------------------------------------------- the readers

# a warm replica's snapshot at the window's end, cut to what the six read
WARM = {"jit_trace_s": 7.5, "jit_lower_s": 2.25, "jit_compile_s": 1.5,
        "jit_compile_events": 48, "jit_cache_hits": 48,
        "jit_cache_misses": 0, "jit_cache_saved_s": 61.0,
        "jit_programs": {"paged_decode_step": {"n": 1}},
        "setup_config_s": 0.01, "setup_weights_s": 6.5,
        "setup_scheduler_s": 2.75}
EXPECTED = {"setup.jit_trace_lower_s": 9.75, "setup.jit_compile_s": 1.5,
            "setup.jit_cache_hit_share": 100.0,
            "setup.jit_compile_events": 48, "setup.weights_s": 6.5,
            "setup.scheduler_build_s": 2.75}
# the parent's: the scheduler's counters and nothing of the record
PARENT = {"phase_park_s": 3.0, "gap_plain_tokens": 7, "tokens_generated": 9}


def ctx_of(end, delta=None):
    return {"counters": {"delta": delta or {}, "end": end}, "trace": None,
            "device": V5E, "cell": {}, "sizes": {}}


def read(metric, ctx):
    return manifest_lib.metric_reader(metric)(ctx)


@pytest.mark.parametrize("metric", SIX)
def test_a_reader_over_a_hand_made_snapshot(metric):
    assert read(metric, ctx_of(WARM)) == pytest.approx(EXPECTED[metric])
    # the window's own difference is not what they read
    assert read(metric, ctx_of(WARM, dict.fromkeys(WARM, 0))) == \
        pytest.approx(EXPECTED[metric])


@pytest.mark.parametrize("metric", SIX)
def test_a_program_without_the_record_reads_zero_not_nothing(metric):
    assert read(metric, ctx_of(PARENT)) == 0
    assert read(metric, ctx_of({})) == 0
    assert read(metric, {"counters": {}}) == 0  # a training cell's ctx
    assert read(metric, {}) == 0


@pytest.mark.parametrize("metric", SIX)
def test_a_program_that_counted_nothing_reads_nothing(metric):
    """The recorder off (no phase stamped), no cache in use (neither a hit
    nor a miss), nothing jitted: the key is there and says 0, and a 0 would
    be a time or a share nobody measured."""
    zero = {k: ({} if k == "jit_programs" else 0) for k in WARM}
    assert read(metric, ctx_of(zero)) is None


def test_the_cache_share_is_hits_over_hits_and_misses():
    cold = dict(WARM, jit_cache_hits=0, jit_cache_misses=48)
    assert read("setup.jit_cache_hit_share", ctx_of(cold)) == 0
    mixed = dict(WARM, jit_cache_hits=36, jit_cache_misses=12)
    assert read("setup.jit_cache_hit_share", ctx_of(mixed)) == 75.0
    assert setup_work.cache_hit_share_percent(ctx_of(mixed)) == 75.0
    # a count is a count whatever the cache did: warm and cold read the same
    assert read("setup.jit_compile_events", ctx_of(cold)) == 48
    half = {k: v for k, v in WARM.items() if k != "jit_lower_s"}
    assert read("setup.jit_trace_lower_s", ctx_of(half)) == 0


@pytest.mark.parametrize("cell", SERVING)
def test_a_traced_line_is_accepted_with_the_six_and_with_the_parents_zeros(
        cell):
    mine = manifest_lib.metrics_for(BENCH, cell, True)
    assert set(SIX) <= {m["name"] for m in mine}
    line = contract.build_line(
        correct=True, attempted=30, failed=0,
        device=dict(V5E, memory_peak_bytes=14_200_000_000, window_s=3.0,
                    busy_s=2.99),
        metrics={m["name"]: {"value": 12.5, "unit": m["unit"]}
                 for m in mine},
        breakdown={"device_ops": [], "idle_gaps": []})
    for name in SIX:
        line["metrics"][name]["value"] = read(name, ctx_of(WARM))
    assert contract.check_line(line, BENCH, cell, True) == []
    for name in SIX:  # the parent's program under these readers
        line["metrics"][name]["value"] = read(name, ctx_of(PARENT))
        assert line["metrics"][name]["value"] == 0
    assert contract.check_line(line, BENCH, cell, True) == []
    line["metrics"].pop(SIX[0])
    assert contract.check_line(line, BENCH, cell, True)


# ---------------------------------------------------------------- rehearsal


def test_serving_rehearsal_reads_set_ups_own_record(tmp_path):
    """``tiny_chat`` traced, once, on a cache directory of its own: the six
    in the line, the count a count, the cache cold (every compile a miss),
    and no compile in the window, by the record itself."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    env.pop("BENCH_RUN", None)
    path = tiny_manifest(tmp_path)
    script = (
        "import sys; sys.path.insert(0, {root!r}); "
        "from tests.perfbench import rehearse; "
        "sys.exit(rehearse.main({path!r}, 'rehearse_setup'))").format(
            root=ROOT, path=path)
    proc = subprocess.run(
        [sys.executable, "-c", script, "--workload", "tiny_chat",
         "--seed", str(2**31 + 57), "--seconds", "2", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=400)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = contract.last_line(proc.stdout)
    assert contract.check_line(last, manifest_lib.load(path), "tiny_chat",
                               True) == []
    line = json.loads(last)
    assert line["correct"] is True and line["failed"] == 0
    value = {n: line["metrics"][n]["value"] for n in SIX}
    assert all(v > 0 for n, v in value.items()
               if n != "setup.jit_cache_hit_share"), value
    assert value["setup.jit_cache_hit_share"] == 0  # an empty directory
    events = value["setup.jit_compile_events"]
    assert isinstance(events, int) and events >= 2  # the scheduler's two
    notes = {json.loads(ln)["note"]: json.loads(ln)
             for ln in proc.stdout.splitlines() if ln.startswith('{"note":')}
    assert notes["left_running"]["processes"] == []
    # a recompile in the window would print in the delta note by itself
    delta = notes["checks"]["delta"]
    assert not [k for k in delta if k.startswith(("jit_", "setup_"))], delta
    assert notes["checks"]["compiles_in_window"] == 0
    # every program the run cached is a program the record counted
    assert notes["cache"]["cache_entries"] <= events
