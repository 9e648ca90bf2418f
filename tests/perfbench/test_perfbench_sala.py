"""The MiniCPM-SALA configuration and its cell (ISSUE 32): what
``BENCHMARK.json`` lists for them, held by name and index
(``pr32_entries``); the arithmetic of ``perfbench/lib/sala_work.py`` against
a count by hand; the four readers on hand-made ``ctx``s; and the CPU
rehearsal of the cell at a toy size over the fourth tiny manifest
(``tiny/BENCHMARK_sala.json``). Counts and structure only: no number here is
a device number.

``held.py`` and the other files of the benchmark are as they were: this PR
added by adding (``held.only_added``), and its own hold is a function of a
manifest like theirs, applied here to the committed manifest and to the
synthetic additions of ``test_perfbench_additions.py`` on top of it, so that
it cannot refuse the next PR.
"""

import copy
import json
import os
import subprocess
import sys

import pytest

from perfbench.lib import contract, sala_work
from perfbench.lib import manifest as manifest_lib
from tests.perfbench import held
from tests.perfbench.test_perfbench_additions import add_a_prs_entries

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = manifest_lib.load()
TINY = manifest_lib.load(os.path.join(HERE, "tiny", "BENCHMARK_sala.json"))
HP = manifest_lib.config(BENCH, "minicpm_sala_l16")
CONFIG, CELL, MIX = "minicpm_sala_l16", "minicpm_sala_longdoc", "longdoc"
NEW = ("kernel.linear_attn_roofline", "kernel.sparse_attn_roofline",
       "attn.selected_share", "step.mixer_share")
# the lists of olmoe_reason's the cell joined, at their ends: every one but
# the paged kernel's share (it would read over 100%) and the experts'
JOINED = ("gap_p95_ms", "client.tokens_per_s", "client.ttft_p50_ms.gap",
          "client.ttft_p95_ms.gap", "sched.occupancy.gap",
          "sched.prefix_hit_share.gap", "paging.peak_pages_in_use.gap",
          "device.idle_share.gap") + tuple(n + ".gap" for n in held.SERVE)
NOT_JOINED = ("kernel.paged_attn_roofline",) + held.MOE
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def pr32_entries(manifest):
    """The fifth configuration and the sixth cell, behind what PR 26 held;
    the four readers at ``per_layer[33:37]``; the cell third in the lists
    it joined, and in none of the three it stayed out of."""
    entry = manifest["configs"][4]
    assert entry["name"] == CONFIG
    assert entry["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert "openbmb/MiniCPM-SALA" in entry["source"]
    cell = manifest["workloads"][5]
    assert (cell["name"], cell["config"], cell["traffic"],
            cell["chips"]) == (CELL, CONFIG, MIX, 1)
    rows = {m["name"]: m
            for m in manifest["end_to_end"] + manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"][33:37]] == list(NEW)
    for name in NEW:
        assert rows[name]["workloads"][:1] == [CELL]
        assert rows[name]["moves"] == "gap_p95_ms"
    assert [rows[n]["layer"] for n in NEW] == [
        "kernels", "kernels", "kernels", "jitted step"]
    for name in JOINED:
        assert rows[name]["workloads"][:3] == [
            "mistral7b_chat", "olmoe_reason", CELL], name
    for name in NOT_JOINED:
        assert CELL not in rows[name]["workloads"], name
    e2e = {m["name"] for m in manifest_lib.metrics_for(manifest, CELL, False)}
    assert e2e == {"gap_p95_ms", "setup_s"}


def parent_of(manifest):
    """The manifest this PR found: its own entries taken out again."""
    old = copy.deepcopy({k: v for k, v in manifest.items() if k != "_dir"})
    old["configs"] = [c for c in old["configs"] if c["name"] != CONFIG]
    old["workloads"] = [w for w in old["workloads"] if w["name"] != CELL]
    old["per_layer"] = [m for m in old["per_layer"] if m["name"] not in NEW]
    for m in old["end_to_end"] + old["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].remove(CELL)
    return old


# ------------------------------------------------------ the manifest's part


def test_this_pr_added_by_adding_and_holds_its_own_entries():
    pr32_entries(BENCH)
    for check in held.CHECKS:
        check(BENCH)
    parent = parent_of(BENCH)
    assert [w["name"] for w in parent["workloads"]] == [
        "gpt2s_train", "mistral7b_chat", "mistral7b_docs",
        "mistral7b_train_4chip", "olmoe_reason"]
    assert len(parent["per_layer"]) == 33 and len(parent["configs"]) == 4
    held.only_added(parent, BENCH)


@pytest.mark.parametrize("tags", [("later",), ("later", "and_later")],
                         ids=["one_pr_behind_it", "two_prs_behind_it"])
def test_its_hold_accepts_what_later_prs_add(tags):
    """The synthetic additions of ``test_perfbench_additions.py`` (entries
    only: the held functions that open files are exercised there)."""
    later = copy.deepcopy({k: v for k, v in BENCH.items() if k != "_dir"})
    for tag in tags:
        add_a_prs_entries(later, tag)
    pr32_entries(later)
    held.only_added(BENCH, later)
    held.static_rules(later)


@pytest.mark.parametrize("edit", [
    lambda m: m["workloads"].insert(0, m["workloads"].pop(5)),
    lambda m: m["per_layer"].pop(33),
    lambda m: [r for r in m["per_layer"] if r["name"] == NOT_JOINED[0]][0][
        "workloads"].append(CELL),
    lambda m: m["end_to_end"][2]["workloads"].remove(CELL),
    lambda m: m["configs"][4]["reduced"].pop(),
], ids=["the_cell_moved", "a_reader_taken_away",
        "the_cell_in_the_paged_kernels_list", "the_cell_out_of_gap_p95_ms",
        "the_pattern_no_longer_listed_as_reduced"])
def test_its_hold_refuses_an_edit_of_its_entries(edit):
    edited = copy.deepcopy({k: v for k, v in BENCH.items() if k != "_dir"})
    edit(edited)
    with pytest.raises((AssertionError, KeyError, IndexError)):
        pr32_entries(edited)


def test_the_configuration_is_the_published_one_cut_in_depth_only():
    """Every number of the catalog's entry under its own key; what differs
    is the depth and the pattern, four whole periods of the published 1:3."""
    published = {
        "head_dim": 128, "hidden_size": 4096, "intermediate_size": 16384,
        "lightning_head_dim": 128, "lightning_nh": 32, "lightning_nkv": 32,
        "max_position_embeddings": 524288, "num_attention_heads": 32,
        "num_hidden_layers": 32, "num_key_value_heads": 2,
        "rms_norm_eps": 1e-06, "vocab_size": 73448, "rope_theta": 10000,
        "scale_emb": 12, "scale_depth": 1.4, "mup_denominator": 32,
        "dim_model_base": 256, "qk_norm": True, "attn_use_rope": False,
        "lightning_use_rope": True, "tie_word_embeddings": False,
        "use_output_gate": True, "use_output_norm": True,
        "attn_use_output_gate": True, "model_type": "minicpm_sala"}
    differs = {k for k, v in published.items() if HP.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"} and HP["num_hidden_layers"] == 16
    assert HP["mixer_types"] == (["minicpm4"] + ["lightning-attn"] * 3) * 4
    assert set(HP["reduced"]) == {"num_hidden_layers", "mixer_types"}
    assert HP["published_num_hidden_layers"] == 32
    cell = manifest_lib.read_json(BENCH, "cells", CELL)
    dep = cell["deployment"]
    assert dep["page_tokens"] == HP["sparse_config"]["kernel_stride"]
    assert dep["kv_pages"] == dep["slots"] * dep["arena_len"] // 16 + 1
    assert dep["prefix_cache"] is False
    assert cell["check_prompt_tokens"] > HP["sparse_config"]["dense_len"]
    mix = manifest_lib.read_json(BENCH, "traffic", MIX)
    assert mix["prompt_tokens"]["min"] >= HP["sparse_config"]["dense_len"]
    assert (mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
            <= dep["arena_len"])


# ------------------------------------------------------------ the arithmetic


def test_the_work_of_the_two_mixers_by_hand():
    # a 512 chunk of one linear layer: 4 blocks x 32 heads x 4 products of
    # 2 x 128^3 operations
    assert sala_work.linear_chunk_flops(HP, 512) == 4 * 32 * 4 * 2 * 128 ** 3
    assert sala_work.linear_state_bytes(HP) == 32 * 128 * 128 * 4  # 2 MB
    assert sala_work.kv_bytes_per_token(HP) == 1024                 # 1 KB
    assert sala_work.pooled_bytes_per_token(HP) == 2 * 128 * 4 / 16
    assert sala_work.sparse_flops(HP, 1000, 16000) == (
        4 * 4096 * 1000 + 2 * 4096 * 1000)


COUNTERS = {"decode_steps": 1000, "prefill_chunks": 500, "sparse_rows": 1,
            "linear_chunk_calls": 6000, "linear_step_rows": 96000,
            "sparse_tokens_attended": 3.0e9, "sparse_tokens_context": 9.0e9,
            "sparse_step_tokens_attended": 1.0e9,
            "sparse_step_tokens_context": 4.0e9}
PROGRAMS = {"jit_paged_decode_step": {"count": 100, "sum_s": 2.2,
                                      "median_s": 0.022},
            "jit_paged_prefill_chunk": {"count": 50, "sum_s": 2.0,
                                        "median_s": 0.04}}
OPS = {"linear_attention_chunk [custom-call]": {"count": 600, "sum_s": 0.06},
       "linear_attention_step [custom-call]": {"count": 1200, "sum_s": 0.12},
       "sparse_select [custom-call]": {"count": 600, "sum_s": 0.03},
       "sparse_paged_attention [custom-call]": {"count": 600, "sum_s": 0.17},
       "fusion": {"count": 9000, "sum_s": 3.0}}


def ctx_of(delta, programs=PROGRAMS, ops=OPS):
    trace = (None if programs is None
             else {"programs": programs, "ops": ops, "busy_s": 4.0})
    return {"counters": {"delta": delta}, "trace": trace, "config": HP,
            "device": V5E, "cell": manifest_lib.read_json(BENCH, "cells",
                                                          CELL)}


def read(metric, ctx):
    return manifest_lib.metric_reader(metric)(ctx)


def test_the_readers_on_a_hand_made_window():
    ctx = ctx_of(COUNTERS)
    # a tenth of the window's programs are in the trace
    chunk = 600 * 4 * 32 * 4 * 2 * 128 ** 3 / 197e12
    steps = 9600 * 2 * 2 ** 21 / 819e9
    assert read("kernel.linear_attn_roofline", ctx) == pytest.approx(
        100 * (chunk + steps) / 0.18)
    moved = (1.0e9 * 1024 + 4.0e9 * 64) / 819e9
    flops = (4 * 4096 * 2.0e9 + 2 * 4096 * 5.0e9 / 16) / 197e12
    assert read("kernel.sparse_attn_roofline", ctx) == pytest.approx(
        100 * 0.1 * (moved + flops) / 0.20)
    assert read("attn.selected_share", ctx) == pytest.approx(100 / 3)
    assert read("step.mixer_share", ctx) == pytest.approx(100 * 0.38 / 4.0)
    for name in NEW:
        assert 0 < read(name, ctx) < 100


@pytest.mark.parametrize("metric", NEW)
def test_a_reader_that_finds_nothing_returns_nothing(metric):
    """Another model's program, or the parent's under these readers: no
    counters and no kernels. Nothing, never 0, and nothing is raised."""
    other = {"decode_steps": 40, "prefill_chunks": 9, "tokens_generated": 7}
    if metric != "step.mixer_share":  # which reads the trace alone
        assert read(metric, ctx_of(other)) is None
    assert read(metric, ctx_of(other, PROGRAMS, {"fusion": OPS["fusion"]})) \
        is None
    assert read(metric, ctx_of({}, None)) is None
    if metric != "attn.selected_share":  # the device's: no trace, no value
        assert read(metric, ctx_of(COUNTERS, None)) is None
        assert read(metric, ctx_of(COUNTERS, PROGRAMS,
                                   {"fusion": OPS["fusion"]})) is None


def test_a_line_of_the_cell_is_accepted_with_its_metrics_and_not_without():
    for traced in (False, True):
        mine = [m for m in manifest_lib.metrics_for(BENCH, CELL, traced)]
        assert set(NEW) <= {m["name"] for m in mine} or not traced
        device = dict(V5E, memory_peak_bytes=14_200_000_000)
        if traced:
            device.update(window_s=3.0, busy_s=2.99)
        line = contract.build_line(
            correct=True, attempted=30, failed=0, device=device,
            metrics={m["name"]: {"value": 12.5, "unit": m["unit"]}
                     for m in mine},
            breakdown={"device_ops": [], "idle_gaps": []} if traced else None)
        assert contract.check_line(line, BENCH, CELL, traced) == []
        line["metrics"].pop(NEW[0] if traced else "gap_p95_ms")
        assert contract.check_line(line, BENCH, CELL, traced)


# ---------------------------------------------------------------- rehearsal


def rehearse(trace, cache_dir, seed=2**31 + 32):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    env.pop("BENCH_RUN", None)
    return subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_sala.py"),
         "--workload", "tiny_longdoc", "--seed", str(seed), "--seconds", "2",
         "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=400)


@pytest.mark.parametrize("trace", [0, 1], ids=["untraced", "traced"])
def test_rehearsal_of_the_two_kinds_cell(trace, tmp_path):
    """The toy model through ``serve.run``, the scheduler and the paged
    programs, checked against ``reference/minicpm_sala.py`` by the harness
    on a prompt past the toy's ``dense_len``; the mixers' counters in the
    run's ``delta`` note. The kernels are interpreted here, so the three
    readers of their device time have nothing to read and the tiny manifest
    lists ``attn.selected_share`` alone of the four."""
    proc = rehearse(trace, tmp_path / "cache")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = contract.last_line(proc.stdout)
    assert contract.check_line(last, TINY, "tiny_longdoc", bool(trace)) == []
    line = json.loads(last)
    assert line["correct"] is True and line["failed"] == 0
    checks = [json.loads(ln) for ln in proc.stdout.splitlines()
              if ln.startswith('{"note": "checks"')][0]
    delta = checks["delta"]
    assert checks["reference_check"]["logit_err"] < 1e-4
    assert checks["reference_check"]["prompt_tokens"] == 100
    assert delta["linear_chunk_calls"] == 3 * delta["prefill_chunks"]
    assert 0 < delta["linear_step_rows"] <= 3 * 4 * delta["decode_steps"]
    assert 0 < delta["sparse_rows_dense"] < delta["sparse_rows"]
    assert (delta["sparse_step_tokens_attended"]
            < delta["sparse_tokens_attended"]
            < delta["sparse_tokens_context"])
    assert delta.get("prefix_hit_tokens", 0) == 0
    if trace:
        value = {n: line["metrics"][n]["value"] for n in (
            "attn.selected_share", "step.decode_ms.gap",
            "sched.prefix_hit_share.gap")}
        assert 50 < value["attn.selected_share"] < 100
        assert value["step.decode_ms.gap"] > 0
        assert value["sched.prefix_hit_share.gap"] == 0
        assert "jit_paged_decode_step" in checks["program_runs"]
