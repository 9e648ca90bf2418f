"""The six readers of the scheduler's own gaps (ISSUE 39): the entries
``BENCHMARK.json`` is to list for them (``pr39_entries.json``), held by name
and order behind what is there (``pr39_entries``); the readers on hand-made
``ctx``s (a program without the counters: 0; with them and nothing counted:
nothing; the arithmetic); and the CPU rehearsal of ``tiny_chat`` and
``tiny_docs`` over the fifth tiny manifest (``tiny/BENCHMARK_turns.json``),
traced into a directory of its own. Counts, shares and structure only: no
number here is a device number.

PR 39 could add the readers' FILES and not their ENTRIES:
``test_perfbench_sala.py`` holds PR 32's addition by the total it left
(``len(parent["per_layer"]) == 33``), which any appended entry breaks, and a
PR that is no ``benchmark`` PR may edit no file of the benchmark. So the six
entries wait in ``pr39_entries.json`` for the ``benchmark`` PR that frees
that line, and everything here holds ``BENCHMARK.json`` WITH them appended
(``PROPOSED``): every function of ``held.py``, PR 32's hold and this PR's
own, on that manifest and on the synthetic additions of
``test_perfbench_additions.py`` on top of it. Once ``BENCHMARK.json`` lists
them, ``PROPOSED`` is the committed manifest and the same tests hold it.
"""

import argparse
import copy
import json
import os
import subprocess
import sys

import pytest

from perfbench.lib import contract, turns
from perfbench.lib import manifest as manifest_lib
from tests.perfbench import held, rehearse_turns
from tests.perfbench.test_perfbench_additions import add_a_prs_entries
from tests.perfbench.test_perfbench_sala import pr32_entries

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = manifest_lib.load()
with open(os.path.join(HERE, "pr39_entries.json")) as f:
    ENTRIES = json.load(f)
LAYERS = manifest_lib.load(os.path.join(HERE, "tiny",
                                        "BENCHMARK_layers.json"))
TINY = manifest_lib.load(rehearse_turns.TURNS_MANIFEST)
PLAIN = ("sched.decode_turn_ms", "sched.prefill_turn_ms",
         "sched.prefill_turn_share")
SIX = [name + suffix for name in PLAIN for suffix in ("", ".gap")]
FREE = 37  # behind PR 32's four readers, per_layer[33:37]
# the cells of each twin, in the order they were listed: the plain turn's has
# no minicpm_sala_longdoc, where about one turn in twelve is plain and a
# window without one would read nothing
GAP_CELLS = {"sched.decode_turn_ms.gap": ["mistral7b_chat", "olmoe_reason"],
             "sched.prefill_turn_ms.gap": ["mistral7b_chat", "olmoe_reason",
                                           "minicpm_sala_longdoc"],
             "sched.prefill_turn_share.gap": ["mistral7b_chat", "olmoe_reason",
                                              "minicpm_sala_longdoc"]}
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def with_the_entries(manifest):
    """``manifest`` with the six entries at the end of ``per_layer``, unless
    it lists them already."""
    out = copy.deepcopy({k: v for k, v in manifest.items() if k != "_dir"})
    if SIX[0] not in {m["name"] for m in out["per_layer"]}:
        out["per_layer"] += copy.deepcopy(ENTRIES)
    if "_dir" in manifest:
        out["_dir"] = manifest["_dir"]
    return out


PROPOSED = with_the_entries(BENCH)


def first_of(manifest):
    return [m["name"] for m in manifest["per_layer"]].index(SIX[0])


def pr39_entries(manifest):
    """The six readers in their order, one block behind what PR 32 listed
    (``per_layer[37:]``), each plain name in the docs cell first and its
    twin in the cells that report ``gap_p95_ms`` first; all of the
    scheduler's layer, all read from the program's counters."""
    rows = {m["name"]: m for m in manifest["per_layer"]}
    first = first_of(manifest)
    assert first >= FREE
    assert [m["name"] for m in manifest["per_layer"][first:first + 6]] == SIX
    for name in PLAIN:
        plain, twin = rows[name], rows[name + ".gap"]
        assert plain["workloads"][:1] == ["mistral7b_docs"]
        assert plain["moves"] == "serve_tokens_per_s"
        cells = GAP_CELLS[name + ".gap"]
        assert twin["workloads"][:len(cells)] == cells
        assert twin["moves"] == "gap_p95_ms"
        for row in (plain, twin):
            assert (row["layer"], row["source"], row["better"]) == (
                "scheduler", "program_counter", "lower")
            assert row["unit"] == ("%" if "share" in name else "ms")
    layers_of = {m["layer"] for m in manifest["per_layer"][:FREE]}
    assert "scheduler" in layers_of  # a layer the benchmark already named


def parent_of(manifest):
    """The manifest this PR found: its own entries taken out again."""
    old = copy.deepcopy({k: v for k, v in manifest.items() if k != "_dir"})
    old["per_layer"] = [m for m in old["per_layer"] if m["name"] not in SIX]
    return old


# ------------------------------------------------------ the manifest's part


def test_the_entries_are_added_by_adding_and_held_by_name():
    assert [m["name"] for m in ENTRIES] == SIX
    pr39_entries(PROPOSED)
    pr32_entries(PROPOSED)
    for check in held.CHECKS:
        check(PROPOSED)
    parent = parent_of(PROPOSED)
    assert [m["name"] for m in parent["per_layer"][33:FREE]] == [
        "kernel.linear_attn_roofline", "kernel.sparse_attn_roofline",
        "attn.selected_share", "step.mixer_share"]
    held.only_added(parent, PROPOSED)
    held.only_added(BENCH, PROPOSED)


def test_the_committed_manifest_lists_all_six_or_none():
    """None until a ``benchmark`` PR appends ``pr39_entries.json``; then the
    entries there are the file's, letter for letter."""
    rows = {m["name"]: m for m in BENCH["per_layer"]}
    listed = [name for name in SIX if name in rows]
    assert listed in ([], SIX)
    for entry in ENTRIES if listed else ():
        n = len(entry["workloads"])
        assert {**rows[entry["name"]], "workloads": 0} == {
            **entry, "workloads": 0}
        assert rows[entry["name"]]["workloads"][:n] == entry["workloads"]


@pytest.mark.parametrize("tags", [("later",), ("later", "and_later")],
                         ids=["one_pr_behind_it", "two_prs_behind_it"])
def test_its_hold_accepts_what_later_prs_add(tags):
    later = copy.deepcopy({k: v for k, v in PROPOSED.items()
                           if k != "_dir"})
    for tag in tags:
        add_a_prs_entries(later, tag)
    pr39_entries(later)
    held.only_added(PROPOSED, later)
    held.static_rules(later)


@pytest.mark.parametrize("edit", [
    lambda m, i: m["per_layer"].insert(i, m["per_layer"].pop(i + 1)),
    lambda m, i: m["per_layer"].pop(i + 5),
    lambda m, i: m["per_layer"][i + 1]["workloads"].insert(
        0, "minicpm_sala_longdoc"),
    lambda m, i: m["per_layer"][i + 3]["workloads"].remove(
        "minicpm_sala_longdoc"),
    lambda m, i: m["per_layer"][i].update(moves="gap_p95_ms"),
    lambda m, i: m["per_layer"][i + 4].update(source="device_trace"),
    lambda m, i: m["per_layer"].insert(0, m["per_layer"].pop(i)),
], ids=["two_readers_swapped", "a_reader_taken_away",
        "a_cell_put_first_in_a_list", "a_cell_taken_out_of_a_list",
        "the_plain_name_moves_the_twins_metric",
        "a_counter_called_a_device_trace", "a_reader_put_first"])
def test_its_hold_refuses_an_edit_of_its_entries(edit):
    edited = copy.deepcopy({k: v for k, v in PROPOSED.items()
                            if k != "_dir"})
    edit(edited, first_of(edited))
    with pytest.raises((AssertionError, KeyError, IndexError)):
        pr39_entries(edited)


def test_each_serving_cell_lists_the_readers_the_issue_gives_it():
    listed = {cell: {m["name"] for m in manifest_lib.metrics_for(
        PROPOSED, cell, True)} & set(SIX)
        for cell in ("mistral7b_docs", "mistral7b_chat", "olmoe_reason",
                     "minicpm_sala_longdoc", "gpt2s_train")}
    assert listed["mistral7b_docs"] == set(PLAIN)
    assert listed["mistral7b_chat"] == listed["olmoe_reason"] == {
        n + ".gap" for n in PLAIN}
    assert listed["minicpm_sala_longdoc"] == {
        "sched.prefill_turn_ms.gap", "sched.prefill_turn_share.gap"}
    assert listed["gpt2s_train"] == set()


def test_the_fifth_tiny_manifest_is_the_second_plus_the_six_names():
    """And one word: ``tiny_chat`` is offered by a closed loop there (more
    callers than slots, as ``mistral7b_chat``), so a window of two seconds
    is certain to hold gaps of both kinds."""
    def but(manifest, *keys):
        return {k: v for k, v in manifest.items()
                if k not in keys + ("_dir",)}

    assert but(TINY, "per_layer", "workloads") == but(
        LAYERS, "per_layer", "workloads")
    n = len(LAYERS["per_layer"])
    assert TINY["per_layer"][:n] == LAYERS["per_layer"]
    assert [m["name"] for m in TINY["per_layer"][n:]] == SIX
    for mine, theirs in zip(TINY["workloads"], LAYERS["workloads"]):
        same = {k: v for k, v in mine.items() if k not in ("traffic", "why")}
        assert same == {k: v for k, v in theirs.items()
                        if k not in ("traffic", "why")}
        assert (mine["traffic"] == theirs["traffic"]) == (
            mine["name"] != "tiny_chat")
    mix = manifest_lib.read_json(TINY, "traffic", "tiny_chat_closed")
    slots = manifest_lib.read_json(TINY, "cells", "tiny_chat")[
        "deployment"]["slots"]
    assert mix["arrival"] == {"mode": "closed", "clients": slots + 2}
    for m in TINY["per_layer"][n:]:
        assert m["workloads"] == (["tiny_chat"] if m["name"].endswith(".gap")
                                  else ["tiny_docs"])
        assert callable(manifest_lib.metric_reader(m["name"]))


# --------------------------------------------------------------- the readers


def ctx_of(delta=None, end=None):
    """A serving cell's ctx cut to what the readers touch; ``end`` defaults
    to a program that counts its gaps."""
    if end is None:
        end = {"gap_plain_tokens": 7, "gap_prefill_tokens": 7,
               "phase_park_s": 1.0}
    return {"counters": {"delta": delta or {}, "end": end}, "trace": None,
            "device": V5E, "cell": {}, "sizes": {}}


def read(metric, ctx):
    return manifest_lib.metric_reader(metric)(ctx)


WINDOW = {"gap_plain_tokens": 90_000, "gap_plain_s": 1_179.0,
          "gap_prefill_tokens": 6_000, "gap_prefill_s": 148.2,
          "first_tokens": 223, "tokens_generated": 96_223}
EXPECTED = {"sched.decode_turn_ms": 13.1, "sched.prefill_turn_ms": 24.7,
            "sched.prefill_turn_share": 6.25}


@pytest.mark.parametrize("metric", SIX)
def test_a_reader_over_a_hand_made_window(metric):
    assert read(metric, ctx_of(WINDOW)) == pytest.approx(
        EXPECTED[metric.replace(".gap", "")])


@pytest.mark.parametrize("metric", SIX)
def test_a_program_without_the_counters_reads_zero_not_nothing(metric):
    """The parent, which the driver runs traced with these readers laid
    over it: a line that lacks a listed metric is refused, so the reader
    says 0, whatever else the program counts."""
    parent = {"phase_park_s": 3.0, "tokens_generated": 9, "first_tokens": 2}
    assert read(metric, ctx_of({"tokens_generated": 9}, parent)) == 0
    assert read(metric, ctx_of({}, {})) == 0
    assert read(metric, {"counters": {}}) == 0  # a training cell's ctx


@pytest.mark.parametrize("metric", SIX)
def test_a_program_that_counted_nothing_reads_nothing(metric):
    zero = dict.fromkeys(WINDOW, 0)
    assert read(metric, ctx_of(zero)) is None
    assert read(metric, ctx_of({})) is None


def test_a_window_with_one_kind_of_gap_reads_that_kind_alone():
    plain_only = dict(WINDOW, gap_prefill_tokens=0, gap_prefill_s=0.0)
    assert read("sched.prefill_turn_ms", ctx_of(plain_only)) is None
    assert read("sched.decode_turn_ms", ctx_of(plain_only)) == \
        pytest.approx(13.1)
    assert read("sched.prefill_turn_share", ctx_of(plain_only)) == 0
    prefill_only = dict(WINDOW, gap_plain_tokens=0, gap_plain_s=0.0)
    assert read("sched.decode_turn_ms.gap", ctx_of(prefill_only)) is None
    assert read("sched.prefill_turn_share.gap", ctx_of(prefill_only)) == 100


def test_gaps_counted_and_not_timed_are_no_time():
    """The recorder off: the counts rise, the seconds stay 0. A mean of 0 ms
    would be a time nobody measured; the share is a count and stands."""
    untimed = dict(WINDOW, gap_plain_s=0.0, gap_prefill_s=0.0)
    for kind in turns.KINDS:
        assert turns.mean_gap_ms(ctx_of(untimed), kind) is None
    assert turns.prefill_share_percent(ctx_of(untimed)) == \
        pytest.approx(6.25)


def test_a_recorded_runs_note_gives_the_three_readings():
    """What ``python -m perfbench.lib.turns < output`` prints: the readings
    of the run's ``delta`` (which leaves out a key that did not move) and
    whether its tokens add up."""
    got = turns.readings(WINDOW)
    assert {k: got[k] for k in EXPECTED} == pytest.approx(EXPECTED)
    assert got["tokens_add_up"] is True and got["gaps"] == 96_000
    assert turns.readings(dict(WINDOW, first_tokens=222))[
        "tokens_add_up"] is False
    # every turn beside a chunk: the key of the plain kind is left out
    prefill_only = {k: v for k, v in WINDOW.items() if "plain" not in k}
    got = turns.readings(dict(prefill_only, tokens_generated=6_223))
    assert got["sched.decode_turn_ms"] is None
    assert got["sched.prefill_turn_share"] == 100 and got["tokens_add_up"]
    # the parent's note: 0, as the readers say it
    assert turns.readings({"tokens_generated": 9, "first_tokens": 9})[
        "sched.prefill_turn_ms"] == 0
    out = subprocess.run(
        [sys.executable, "-m", "perfbench.lib.turns"], cwd=ROOT, text=True,
        input='{"note": "start"}\n' + json.dumps(
            {"note": "checks", "delta": WINDOW}) + '\n{"correct": true}\n',
        capture_output=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert json.loads(out.stdout)["sched.prefill_turn_share"] == 6.25


@pytest.mark.parametrize("cell", ["mistral7b_docs", "mistral7b_chat",
                                  "olmoe_reason", "minicpm_sala_longdoc"])
def test_a_traced_line_is_accepted_with_the_readers_and_not_without(cell):
    mine = manifest_lib.metrics_for(PROPOSED, cell, True)
    line = contract.build_line(
        correct=True, attempted=30, failed=0,
        device=dict(V5E, memory_peak_bytes=14_200_000_000, window_s=3.0,
                    busy_s=2.99),
        metrics={m["name"]: {"value": 12.5, "unit": m["unit"]}
                 for m in mine},
        breakdown={"device_ops": [], "idle_gaps": []})
    assert contract.check_line(line, PROPOSED, cell, True) == []
    # the parent's line under these readers: every one says 0
    parent = ctx_of({"tokens_generated": 9}, {"phase_park_s": 3.0})
    for m in mine:
        if m["name"] in SIX:
            line["metrics"][m["name"]]["value"] = read(m["name"], parent)
    assert contract.check_line(line, PROPOSED, cell, True) == []
    line["metrics"].pop([m["name"] for m in mine if m["name"] in SIX][0])
    assert contract.check_line(line, PROPOSED, cell, True)


# ---------------------------------------------------------------- rehearsal


def test_the_rehearsal_traces_into_a_directory_of_its_own():
    from perfbench import run

    args = argparse.Namespace(workload="tiny_chat", seed=1, seconds=2.0,
                              trace=1)
    ctx = rehearse_turns.build_context(args, TINY, False)
    shared = os.path.join(ROOT, ".perfbench_trace", "tiny_chat")
    assert ctx["trace_dir"] != shared
    assert ctx["trace_dir"].startswith(rehearse_turns.TRACE_ROOT + os.sep)
    assert ctx["trace_dir"].endswith(os.sep + "tiny_chat")
    assert run.ROOT == ROOT  # put back: the workers' PYTHONPATH reads it


def rehearse(workload, trace, cache_dir, seed=2**31 + 39):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    env.pop("BENCH_RUN", None)
    return subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_turns.py"),
         "--workload", workload, "--seed", str(seed), "--seconds", "2",
         "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=400)


@pytest.mark.parametrize("workload,suffix,trace", [
    ("tiny_chat", ".gap", 1), ("tiny_docs", "", 1), ("tiny_chat", ".gap", 0)],
    ids=["tiny_chat-traced", "tiny_docs-traced", "tiny_chat-untraced"])
def test_serving_rehearsal_reads_the_schedulers_own_gaps(workload, suffix,
                                                         trace, tmp_path):
    proc = rehearse(workload, trace, tmp_path / "cache")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = contract.last_line(proc.stdout)
    assert contract.check_line(last, TINY, workload, bool(trace)) == []
    line = json.loads(last)
    assert line["correct"] is True and line["failed"] == 0
    # the identity travels in the checks note of every run, traced or not
    checks = [json.loads(ln) for ln in proc.stdout.splitlines()
              if ln.startswith('{"note": "checks"')][0]
    delta = checks["delta"]
    assert (delta["gap_plain_tokens"] + delta["gap_prefill_tokens"]
            + delta["first_tokens"]) == delta["tokens_generated"]
    assert delta["gap_plain_tokens"] > 0 < delta["gap_prefill_tokens"]
    assert delta["gap_plain_s"] > 0 < delta["gap_prefill_s"]
    assert 0 < delta["prefill_tokens"] <= (
        delta["prefill_chunks"]
        * checks["scheduler"]["prefill_chunk"])
    assert delta["turns"] >= delta["decode_steps"]
    if not trace:
        return
    value = {n: line["metrics"][n + suffix]["value"] for n in PLAIN}
    assert value["sched.prefill_turn_share"] == pytest.approx(
        100 * delta["gap_prefill_tokens"]
        / (delta["gap_plain_tokens"] + delta["gap_prefill_tokens"]))
    assert 0 < value["sched.prefill_turn_share"] < 100
    assert value["sched.decode_turn_ms"] == pytest.approx(
        1e3 * delta["gap_plain_s"] / delta["gap_plain_tokens"])
    assert value["sched.prefill_turn_ms"] == pytest.approx(
        1e3 * delta["gap_prefill_s"] / delta["gap_prefill_tokens"])
    # a turn is no host event: the idle gaps are still labelled by phases
    labels = [label for label, _ in line["breakdown"]["idle_gaps"]]
    assert not [lb for lb in labels if "serve.turn" in lb], labels
    assert [lb for lb in labels if "| host: serve." in lb], labels
