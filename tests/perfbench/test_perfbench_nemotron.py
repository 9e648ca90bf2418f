"""The Nemotron-3-Nano configuration and its cell ``nemotron3_nano_reason``
(ISSUE 59): what ``BENCHMARK.json`` lists for them, held by NAME and cut at
this PR's first entry (``pr59_entries``: never ``[-1]``, a total or a whole
``workloads`` list, so the next PR can add behind them); the arithmetic of
``perfbench/lib/ssm_work.py`` against counts by hand; the five readers on
hand-made ``ctx``s; and a CPU rehearsal of the cell at a toy size, over a
manifest BUILT here from the committed tiny one plus this PR's entries.
Counts and structure only: no number here is a device number.

This PR is no ``benchmark`` PR, so its hold lives in this file, which it
adds: ``tests/perfbench/held.py`` is a file the benchmark already has. A
later ``benchmark`` PR moves ``pr59_entries`` into ``held.CHECKS``.
"""

import copy
import json
import os
import subprocess
import sys

import pytest

from perfbench.lib import contract, ssm_work
from perfbench.lib import manifest as manifest_lib
from tests.perfbench import held
from tests.perfbench.test_perfbench_additions import add_a_prs_entries

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY_DIR = os.path.join(HERE, "tiny")
BENCH = manifest_lib.load()
CONFIG, CELL, MIX = ("nemotron3_nano_30b_a3b_l9", "nemotron3_nano_reason",
                     "reason_wide")
BEFORE = "glm47_flash_longdocs"  # the last cell of every list joined
HP = manifest_lib.config(BENCH, CONFIG)
REDUCED = ["num_hidden_layers", "hybrid_override_pattern", "num_experts",
           "vocab_size"]
NEW = ["kernel.ssm_chunk_roofline", "kernel.ssm_step_roofline",
       "step.ssm_share", "ssm.decode_step_roofline", "moe.held_route_share"]
ROW = {  # unit, better, source, layer
    "kernel.ssm_chunk_roofline": ("%", "higher", "device_trace", "kernels"),
    "kernel.ssm_step_roofline": ("%", "higher", "device_trace", "kernels"),
    "step.ssm_share": ("%", "lower", "device_trace", "jitted step"),
    "ssm.decode_step_roofline": ("%", "higher", "device_trace",
                                 "jitted step"),
    "moe.held_route_share": ("%", "higher", "program_counter", "experts")}
SETUP = ["setup.jit_trace_lower_s", "setup.jit_compile_s",
         "setup.jit_cache_hit_share", "setup.jit_compile_events",
         "setup.weights_s", "setup.scheduler_build_s"]
# the lists whose last cell was PR 55's ...
JOINED = ["client.tokens_per_s", "client.ttft_p50_ms.gap",
          "client.ttft_p95_ms.gap", "sched.occupancy.gap",
          "sched.prefix_hit_share.gap", "paging.peak_pages_in_use.gap",
          "device.idle_share.gap", "step.prefill_share.gap",
          "step.turn_ms.gap", "sched.queue_wait_ms.gap",
          "sched.host_share.gap", "sched.stall_share.gap",
          "replica.stream_lag_ms.gap", "sched.prefill_turn_ms.gap",
          "sched.prefill_turn_share.gap", "sched.fused_turn_share.gap",
          "moe.max_expert_load"] + SETUP
# ... and the two that read a PLAIN step, which this traffic mostly runs
# (eight turns in nine), behind the last cell that does
JOINED_BEHIND = {"step.decode_ms.gap": "mellum2_shortlong",
                 "sched.decode_turn_ms.gap": "mellum2_shortlong"}
# readers whose arithmetic is another model's
NOT_JOINED = ["kernel.paged_attn_roofline", "moe.decode_step_roofline",
              "step.mixer_share", "kernel.linear_attn_roofline",
              "kernel.sparse_attn_roofline", "attn.selected_share",
              "kernel.retention_step_roofline",
              "kernel.retention_chunk_roofline",
              "retention.decode_step_roofline", "step.retention_share",
              "kernel.window_attn_roofline", "kernel.global_attn_roofline",
              "window.decode_step_roofline", "paging.window_held_share",
              "kernel.index_score_roofline", "kernel.indexed_attn_roofline",
              "step.indexer_share", "attn.indexed_share",
              "indexed.turn_roofline", "kernel.latent_attn_roofline",
              "latent.turn_roofline", "step.latent_share",
              "sched.prefix_hit_share", "serve_tokens_per_s"]
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def by_name(rows):
    return {r["name"]: r for r in rows}


def pr59_entries(manifest):
    """This PR's entries as it wrote them, found by name; whatever a later
    PR put behind them is free."""
    config = by_name(manifest["configs"])[CONFIG]
    assert config["file"] == f"perfbench/configs/{CONFIG}.json"
    assert config["reduced"] == REDUCED
    assert config["source"] == (
        "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/"
        "blob/main/config.json")
    cell = by_name(manifest["workloads"])[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    names = [w["name"] for w in manifest["workloads"]]
    assert names.index(CELL) > names.index(BEFORE)
    rows = by_name({**by_name(manifest["per_layer"]),
                    **by_name(manifest["end_to_end"])}.values())
    behind = {**dict.fromkeys(JOINED + ["gap_p95_ms"], BEFORE),
              **JOINED_BEHIND}
    for name, before in behind.items():
        cells = rows[name]["workloads"]
        assert cells[cells.index(CELL) - 1] == before, name
        assert rows[name].get("moves", "gap_p95_ms") in ("gap_p95_ms",
                                                         "setup_s")
    for name in NOT_JOINED:
        assert CELL not in rows[name]["workloads"], name
    order = [m["name"] for m in manifest["per_layer"]]
    at = order.index(NEW[0])
    assert order[at:at + len(NEW)] == NEW       # together, in this order
    assert at > order.index("setup.scheduler_build_s")  # behind PR 57's
    for name in NEW:
        row = rows[name]
        # a new entry lists the PR's own cell first and no cell the
        # benchmark had (whose parent has no such counter or kernel)
        assert row["workloads"][:1] == [CELL]
        assert not set(row["workloads"]) & set(names[:names.index(CELL)])
        assert (row["unit"], row["better"], row["source"],
                row["layer"]) == ROW[name]
        assert row["moves"] == "gap_p95_ms"


def without_this_pr(manifest):
    """The manifest this PR found: its entries AND WHATEVER FOLLOWED THEM
    taken out again (every list cut at this PR's first entry, every
    ``workloads`` list at this PR's cell)."""
    out = copy.deepcopy({k: v for k, v in manifest.items() if k != "_dir"})

    def cut(rows, name):
        names = [r["name"] for r in rows]
        return rows[:names.index(name)] if name in names else rows

    out["configs"] = cut(out["configs"], CONFIG)
    out["workloads"] = cut(out["workloads"], CELL)
    out["per_layer"] = cut(out["per_layer"], NEW[0])
    for m in out["end_to_end"] + out["per_layer"]:
        if CELL in m.get("workloads", ()):
            del m["workloads"][m["workloads"].index(CELL):]
    return out


# ------------------------------------------------------ the manifest's part


def test_this_pr_added_by_adding_and_holds_its_own_entries():
    pr59_entries(BENCH)
    parent = without_this_pr(BENCH)
    assert CELL not in json.dumps(parent) and CONFIG not in json.dumps(parent)
    held.only_added(parent, BENCH)
    held.static_rules(BENCH)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(BENCH["workloads"]) >= 11


@pytest.mark.parametrize("tags", [("later",), ("later", "and_later")],
                         ids=["one_pr_behind_it", "two_prs_behind_it"])
def test_its_hold_accepts_what_later_prs_add(tags):
    later = copy.deepcopy({k: v for k, v in BENCH.items() if k != "_dir"})
    for tag in tags:
        add_a_prs_entries(later, tag)
    pr59_entries(later)
    held.only_added(BENCH, later)
    held.static_rules(later)
    held.only_added(without_this_pr(later), later)


def row_of(manifest, name):
    return by_name(manifest["per_layer"])[name]


@pytest.mark.parametrize("edit", [
    lambda m: m["workloads"].insert(0, m["workloads"].pop(
        [w["name"] for w in m["workloads"]].index(CELL))),
    lambda m: m["per_layer"].remove(row_of(m, NEW[1])),
    lambda m: row_of(m, "moe.decode_step_roofline")["workloads"].append(
        CELL),
    lambda m: by_name(m["end_to_end"])["gap_p95_ms"]["workloads"].remove(
        CELL),
    lambda m: by_name(m["configs"])[CONFIG]["reduced"].append(
        "ssm_state_size"),
    lambda m: row_of(m, NEW[0])["workloads"].insert(0, "brumby_longgen"),
    lambda m: row_of(m, NEW[4])["workloads"].append("olmoe_reason"),
    lambda m: by_name(m["workloads"])[CELL].update(chips=4),
], ids=["the_cell_moved_to_the_front", "a_reader_taken_away",
        "the_cell_on_another_models_step_roofline",
        "the_cell_out_of_gap_p95_ms", "a_width_listed_as_reduced",
        "another_cell_before_it_in_its_metric",
        "a_cell_the_benchmark_had_on_a_new_metric",
        "four_chips_for_one_chips_work"])
def test_its_hold_refuses_an_edit_of_its_entries(edit):
    edited = copy.deepcopy({k: v for k, v in BENCH.items() if k != "_dir"})
    edit(edited)
    with pytest.raises((AssertionError, KeyError, ValueError)):
        pr59_entries(edited)


def published():
    """The catalog's entry of the model, from the guide beside the builder's
    instructions where that is installed; else what this file's author read
    there."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                row = json.loads(line)
                if row["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16":
                    return row["config"]
    return None


def test_the_configuration_is_the_published_one_cut_as_the_file_says():
    """Every width as published; what differs is the depth and its pattern,
    the vocabulary's slice, and the count of experts HELD beside the router's
    published 128."""
    widths = {"hidden_size": 2688, "mamba_num_heads": 64,
              "mamba_head_dim": 64, "n_groups": 8, "ssm_state_size": 128,
              "conv_kernel": 4, "chunk_size": 128, "num_attention_heads": 32,
              "num_key_value_heads": 2, "head_dim": 128,
              "moe_intermediate_size": 1856, "intermediate_size": 1856,
              "moe_shared_expert_intermediate_size": 3712,
              "n_routed_experts": 128, "num_experts_per_tok": 6,
              "routed_scaling_factor": 2.5, "n_shared_experts": 1,
              "expand": 2, "mlp_hidden_act": "relu2", "norm_eps": 1e-05}
    assert {k: HP[k] for k in widths} == widths
    catalog = published()
    if catalog is not None:
        differs = {k for k, v in catalog.items()
                   if HP.get(k, "absent") != v}
        assert differs == {"num_hidden_layers", "hybrid_override_pattern",
                           "vocab_size"}
        assert catalog["hybrid_override_pattern"].startswith(
            HP["hybrid_override_pattern"])
    assert set(HP["reduced"]) == set(REDUCED)
    assert (HP["num_hidden_layers"], HP["hybrid_override_pattern"]) == (
        9, "MEMEM*EME")
    assert (HP["num_experts"], HP["experts_held_first"],
            HP["vocab_size"]) == (64, 0, 65536)
    assert "TWO chips" in HP["stands_for"] and "layout" in HP
    assert "two chips" in HP["reduced"]["num_experts"].lower()
    assert HP["program"]["dtype"] == "bfloat16"
    said = " ".join(HP["assumed"])
    for what in ("NO positional rotation", "n_group 1", "ties", "1e-20",
                 "e_score_correction_bias", "relu", "dt_bias", "BEFORE",
                 "float32", "num_experts 64"):
        assert what in said, what
    fam = manifest_lib.read_json_from_bench("families", "nemotron_h")
    assert fam["preset"] == "nemotron_h_debug"
    assert fam["reference"] == "nemotron_h"
    assert fam["keys"]["hybrid_override_pattern"] == "layer_pattern"
    assert fam["keys"]["num_experts"] == "moe_held_count"
    assert fam["constants"] == {"norm": "rmsnorm", "pos": "none",
                                "mlp": "moe", "moe_scoring": "sigmoid"}
    assert set(fam["keys"]) <= set(HP)


def test_the_cell_and_its_traffic_are_what_the_issue_names():
    cell = manifest_lib.read_json(BENCH, "cells", CELL)
    dep = cell["deployment"]
    assert set(dep) == {"slots", "prefill_chunk", "arena_len", "page_tokens",
                        "kv_pages", "prefix_cache"}  # no option was added
    # ISSUE 59's one fallback: 64 slots and 72 callers (at 128 / 144 the
    # spread passed 2%; the cell's file gives both sets of readings)
    assert dep["slots"] == 64 and dep["prefix_cache"] is False
    assert (dep["prefill_chunk"], dep["arena_len"]) == (512, 3072)
    assert (dep["kv_pages"] - 1) * dep["page_tokens"] == (
        dep["slots"] * dep["arena_len"])
    assert cell["check_prompt_tokens"] % 128 not in (0, 1)
    assert cell["check_prompt_tokens"] > 2 * dep["prefill_chunk"]
    assert set(cell["check_tolerance"]) == {
        "logit_err", "logit_rms_err", "served_margin", "given_logit_err",
        "given_logit_rms_err"}
    assert "float8" in cell["check_tolerance_why"]
    assert cell["warmup_s"] >= 40
    mix = manifest_lib.read_json(BENCH, "traffic", MIX)
    assert mix["arrival"]["mode"] == "closed"
    assert mix["arrival"]["clients"] == dep["slots"] * 9 // 8
    assert cell["max_ongoing_requests"] >= 160 >= mix["arrival"]["clients"]
    assert "128 slots / 144 callers" in cell["deployment_why"]
    assert "144" in mix["arrival_why"]
    assert cell["check_new_tokens"] == 32
    assert mix["prompt_tokens"] == {"min": 128, "max": 1024, "body_max": 512,
                                    "tail_share": 0.1, "tail_alpha": 1.5}
    assert mix["output_tokens"] == {"min": 512, "max": 2048,
                                    "body_max": 1536, "tail_share": 0.1,
                                    "tail_alpha": 1.5}
    assert (mix["block"], mix["shuffle"], mix["order_seed"]) == (32, 8, 23)
    assert (mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
            <= dep["arena_len"])
    why = by_name(BENCH["workloads"])[CELL]["why"]
    assert "128-1024" in why and "512-2048" in why and len(why) <= 200


# ------------------------------------------------------------ the arithmetic


def test_the_work_of_the_kind_by_hand():
    assert ssm_work.inner(HP) == 4096 and ssm_work.conv_width(HP) == 6144
    assert ssm_work.scan_state_bytes(HP) == 64 * 64 * 128 * 4 == 2_097_152
    assert ssm_work.conv_state_bytes(HP) == 3 * 6144 * 4 == 73_728
    # a token and layer: the state read and updated, the causal half of a
    # block's scores (8 groups) and of their product with the values
    assert ssm_work.chunk_flops_per_token(HP) == (
        4 * 4096 * 128 + 8 * 128 * 128 + 4096 * 128) == 2_752_512
    assert ssm_work.chunk_bytes_per_token(HP) == (6144 + 4096) * 2
    # 134 operations a byte: under the chip's 240, a chunk is the memory's
    assert 2_752_512 / 20_480 < 197e12 / 819e9
    assert ssm_work.expert_bytes(HP) == 2 * 2688 * 1856 * 2 == 19_955_712
    assert ssm_work.kv_bytes_per_token(HP) == 1024
    assert (ssm_work.layers_of(HP, "M"), ssm_work.layers_of(HP, "E"),
            ssm_work.layers_of(HP, "*")) == (4, 4, 1)
    # the weights a step reads whatever the routing: the model less its
    # routed experts and its embedding table (3,166,244,352 parameters, 64
    # experts of 9,977,856 in four layers, a table of 65,536 x 2688)
    assert ssm_work.dense_weight_bytes(HP) == 2 * (
        3_166_244_352 - 4 * 64 * 9_977_856 - 65_536 * 2688)


SIZES = {"vocab_size": 65536, "num_layers": 9, "embed_dim": 2688,
         "num_heads": 32, "num_kv_heads": 2, "head_dim": 128,
         "mlp_dim": 1856, "mlp": "moe", "max_seq_len": 262144}
# a window of 1000 turns: 900 plain steps and 100 chunks of 500 real tokens
# with the step's rows along; 120 live rows a step; 40 of 64 held experts
# hit a layer-call
COUNTERS = {
    "decode_steps": 1000, "prefill_chunks": 100, "fused_turns": 100,
    "turns": 1000, "prefill_tokens": 50_000,
    "ssm_step_rows": 4 * 120 * 1000, "ssm_chunk_calls": 4 * 100,
    "ssm_chunk_tokens": 4 * 50_000,
    "ssm_state_bytes_moved": 2 * 2_170_880 * 4 * (120 * 1000 + 100),
    "moe_layer_calls": 4 * 1100, "moe_experts_hit": 40 * 4 * 1100,
    "moe_rows_routed": 1_300_000, "moe_routes_chosen": 2_500_000}
PROGRAMS = {"jit_paged_decode_step": {"count": 45, "sum_s": 0.54,
                                      "median_s": 0.012},
            "jit_paged_prefill_chunk": {"count": 5, "sum_s": 0.1,
                                        "median_s": 0.020}}
OPS = {"ssm_chunk_scan [custom-call]": {"count": 20, "sum_s": 0.004},
       "ssm_step [custom-call]": {"count": 200, "sum_s": 0.2},
       "fusion": {"count": 9000, "sum_s": 0.3},
       "copy-done": {"count": 400, "sum_s": 0.051},
       "slice-done [async-done]": {"count": 50, "sum_s": 0.02}}
# the same ops by the program they ran in: the step kernel runs in both
# (a chunk's turn takes the decode rows along), the chunk kernel in one
BY_PROGRAM = {
    "jit_paged_decode_step": {
        "ssm_step [custom-call]": {"count": 180, "sum_s": 0.18},
        "fusion": {"count": 8000, "sum_s": 0.25},
        "copy-done": {"count": 360, "sum_s": 0.05},
        "slice-done [async-done]": {"count": 50, "sum_s": 0.02}},
    "jit_paged_prefill_chunk": {
        "ssm_chunk_scan [custom-call]": OPS["ssm_chunk_scan [custom-call]"],
        "ssm_step [custom-call]": {"count": 20, "sum_s": 0.02},
        "fusion": {"count": 1000, "sum_s": 0.05},
        "copy-done": {"count": 40, "sum_s": 0.001}},
    "jit_other": {"copy-done": {"count": 9, "sum_s": 7.0}}}


def ctx_of(delta, programs=PROGRAMS, ops=OPS, context_tokens=0):
    trace = (None if programs is None else {
        "programs": programs, "ops": ops, "busy_s": 0.63,
        "ops_by_program": {name: {k: v for k, v in rows.items() if k in ops}
                           for name, rows in BY_PROGRAM.items()}})
    return {"counters": {"delta": delta, "end": delta, "trace_window": {
                "decode_context_tokens": context_tokens}},
            "trace": trace, "config": HP, "sizes": SIZES, "device": V5E,
            "cell": manifest_lib.read_json(BENCH, "cells", CELL)}


def read(metric, ctx):
    return manifest_lib.metric_reader(metric)(ctx)


def test_the_readers_on_a_hand_made_window():
    ctx = ctx_of(COUNTERS, context_tokens=50 * 120 * 1500)
    steps = (45 + 5) / 1000     # traced runs that carried rows / steps
    chunks = 5 / 100            # traced chunk runs / chunks
    # a kernel's time is AT MOST its own column plus the asynchronous
    # ``-done`` ops of the programs it ran in (which the table counts apart
    # where they fall inside the kernel's events), no other program's
    assert ssm_work.kernel_seconds_at_most(ctx, "ssm_step") == (
        pytest.approx(0.2 + 0.05 + 0.02 + 0.001))
    assert read("kernel.ssm_step_roofline", ctx) == pytest.approx(
        100 * steps * 480_000 * 2 * 2_097_152 / 819e9 / 0.271)
    moved = 200_000 * 20_480 + 400 * 2 * 2_097_152
    assert moved / 819e9 > 200_000 * 2_752_512 / 197e12  # the memory's
    assert read("kernel.ssm_chunk_roofline", ctx) == pytest.approx(
        100 * chunks * moved / 819e9 / 0.005)
    # a summary from before ``ops_by_program``: every ``-done`` op counts
    flat = {k: v for k, v in ctx["trace"].items() if k != "ops_by_program"}
    assert ssm_work.kernel_seconds_at_most(
        {"trace": flat}, "ssm_step") == pytest.approx(0.2 + 0.051 + 0.02)
    assert read("step.ssm_share", ctx) == pytest.approx(100 * 0.204 / 0.63)
    assert read("moe.held_route_share", ctx) == pytest.approx(52.0)
    step = (ssm_work.dense_weight_bytes(HP) + 4 * 40 * 19_955_712
            + 480 * 2 * (2_097_152 + 73_728) + 120 * 1500 * 1024) / 819e9
    assert read("ssm.decode_step_roofline", ctx) == pytest.approx(
        100 * step / 0.012)
    for name in NEW:
        assert 0 < read(name, ctx) < 100, name


@pytest.mark.parametrize("metric", NEW)
def test_a_reader_that_finds_nothing_returns_nothing(metric):
    """Another model's program, or the parent's under these readers: no
    counters and no kernel of that name. Nothing, never 0, and nothing is
    raised."""
    other = {"decode_steps": 40, "prefill_chunks": 9, "tokens_generated": 7,
             "moe_layer_calls": 16, "moe_experts_hit": 90,
             "moe_rows_routed": 128, "retention_step_rows": 64}
    no_kernel = {"fusion": OPS["fusion"]}
    assert read(metric, ctx_of(other, PROGRAMS, no_kernel)) is None
    assert read(metric, ctx_of({}, None)) is None
    assert read(metric, {"counters": {}, "trace": None}) is None
    assert read(metric, {}) is None
    if metric != "moe.held_route_share":  # which reads counters alone
        assert read(metric, ctx_of(COUNTERS, None)) is None
    if metric.startswith(("kernel.", "step.")):  # counters, no such kernel
        assert read(metric, ctx_of(COUNTERS, PROGRAMS, no_kernel)) is None
    # the counters present and nothing counted: still nothing, not 0
    zeros = {k: 0 for k in COUNTERS}
    assert read(metric, ctx_of(zeros, PROGRAMS, no_kernel)) is None


def test_a_line_of_the_cell_is_accepted_with_its_metrics_and_not_without():
    for traced in (False, True):
        mine = manifest_lib.metrics_for(BENCH, CELL, traced)
        names = {m["name"] for m in mine}
        assert (set(NEW) | set(JOINED) | set(JOINED_BEHIND)) <= names if (
            traced) else names == {"gap_p95_ms", "setup_s"}
        assert not names & set(NOT_JOINED)
        device = dict(V5E, memory_peak_bytes=9_000_000_000)
        if traced:
            device.update(window_s=3.0, busy_s=2.9)
        line = contract.build_line(
            correct=True, attempted=300, failed=0, device=device,
            metrics={m["name"]: {"value": 12.5, "unit": m["unit"]}
                     for m in mine},
            breakdown={"device_ops": [], "idle_gaps": []} if traced else None)
        assert contract.check_line(line, BENCH, CELL, traced) == []
        line["metrics"].pop(NEW[0] if traced else "gap_p95_ms")
        assert contract.check_line(line, BENCH, CELL, traced)


def test_no_cell_the_benchmark_had_reports_a_metric_of_this_pr():
    """The parent's program runs the OLD cells under this PR's benchmark
    files and reports none of the new counters, so no old cell may be
    listed for a reader that needs them."""
    old = [w["name"] for w in without_this_pr(BENCH)["workloads"]]
    assert len(old) == 10
    for cell in old:
        for traced in (False, True):
            names = {m["name"] for m in manifest_lib.metrics_for(
                BENCH, cell, traced)}
            assert not names & set(NEW), (cell, names & set(NEW))


# ---------------------------------------------------------------- rehearsal

TINY_JOINED = ["client.tokens_per_s", "client.ttft_p50_ms.gap",
               "sched.occupancy.gap", "sched.prefix_hit_share.gap",
               "paging.peak_pages_in_use.gap", "device.idle_share.gap",
               "step.prefill_share.gap", "sched.prefill_turn_share.gap",
               "sched.fused_turn_share.gap", "step.turn_ms.gap"]
# the readers of this PR that find something on a CPU: the kernels run
# interpreted there and leave no event of their names
TINY_NEW = ["ssm.decode_step_roofline", "moe.held_route_share"]


def tiny_manifest(tmp_path):
    """The committed tiny manifest plus a toy Nemotron-H, its cell, the
    expert model's balance reader and this PR's readers that read no
    kernel."""
    with open(os.path.join(TINY_DIR, "BENCHMARK_turns.json")) as f:
        tiny = json.load(f)
    tiny["paths"] = [TINY_DIR]
    for config in tiny["configs"]:
        config["file"] = os.path.join(TINY_DIR, config["file"])
    tiny["configs"].append({
        "name": "tiny_nemotron", "source": "tests only",
        "file": os.path.join(TINY_DIR, "configs", "tiny_nemotron.json"),
        "reduced": [], "why": "a toy of Nemotron-3-Nano"})
    tiny["workloads"].append({
        "name": "tiny_reason_wide", "config": "tiny_nemotron",
        "traffic": "tiny_reason_wide", "chips": 1,
        "why": "the cell of a model with state-space layers, pages and a "
               "share of its experts, at a toy size"})
    by_name(tiny["end_to_end"])["gap_p95_ms"]["workloads"].append(
        "tiny_reason_wide")
    for name in TINY_JOINED:
        by_name(tiny["per_layer"])[name]["workloads"].append(
            "tiny_reason_wide")
    for name in ["moe.max_expert_load"] + TINY_NEW:
        tiny["per_layer"].append(dict(by_name(BENCH["per_layer"])[name],
                                      workloads=["tiny_reason_wide"]))
    path = tmp_path / "BENCHMARK_nemotron.json"
    path.write_text(json.dumps(tiny))
    return str(path)


def rehearse(manifest_path, trace, cache_dir, seed=2**31 + 59):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    env.pop("BENCH_RUN", None)
    script = (
        "import sys; sys.path.insert(0, {root!r}); "
        "from tests.perfbench import rehearse; "
        "sys.exit(rehearse.main({path!r}, 'rehearse_nemotron'))").format(
            root=ROOT, path=manifest_path)
    return subprocess.run(
        [sys.executable, "-c", script, "--workload", "tiny_reason_wide",
         "--seed", str(seed), "--seconds", "2", "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


def test_rehearsal_of_the_cell_with_states_pages_and_a_share(tmp_path):
    """The toy model through ``serve.run``, the scheduler and the two paged
    programs, checked against ``reference/nemotron_h.py`` by the harness —
    without choices, and GIVEN the routes; the kind's counters and the
    share's in the run's ``delta`` note; a traced line with the joined
    readers and the two of this PR that find something on a CPU (the one
    run is the traced one: it reports what the other would, and more)."""
    path = tiny_manifest(tmp_path)
    proc = rehearse(path, 1, tmp_path / "cache")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = contract.last_line(proc.stdout)
    assert contract.check_line(last, manifest_lib.load(path),
                               "tiny_reason_wide", True) == []
    line = json.loads(last)
    assert line["correct"] is True and line["failed"] == 0
    checks = [json.loads(ln) for ln in proc.stdout.splitlines()
              if ln.startswith('{"note": "checks"')][0]
    delta = checks["delta"]
    assert checks["reference_check"]["logit_err"] < 1e-4
    assert checks["reference_check"]["prompt_tokens"] == 37
    assert checks["reference_check"]["given_choices"] == "routes"
    assert checks["checks"]["reference_logits_given_choices"] is True
    assert 0 < line["compared"]["given_logit_err"]["value"] < 1e-4
    assert checks["scheduler"]["compiled_programs"] == 2
    assert delta["ssm_step_rows"] > 0 and delta["ssm_chunk_calls"] > 0
    # three Mamba-2 layers a chunk's real tokens (the two counters are read
    # a program apart, so the window's edges may differ by a chunk)
    assert delta["ssm_chunk_tokens"] % 3 == 0
    assert abs(delta["ssm_chunk_tokens"] / 3 - delta["prefill_tokens"]) <= 64
    row_bytes = 4 * (3 * 128 + 2 * 16 * 32)  # conv 3 x 128, ssm 2 x 16 x 32
    assert delta["ssm_state_bytes_moved"] == 2 * row_bytes * (
        delta["ssm_step_rows"] + delta["ssm_chunk_calls"])
    assert delta["moe_routes_chosen"] == 3 * 3 * delta["moe_live_rows"]
    assert 0 < delta["moe_rows_routed"] < delta["moe_routes_chosen"]
    assert delta["moe_shared_rows"] == 3 * delta["moe_live_rows"]
    assert "prefix_hit_tokens" not in delta   # a state forbids the cache
    assert line["metrics"]["ssm.decode_step_roofline"]["value"] > 0
    assert 0 < line["metrics"]["moe.held_route_share"]["value"] < 100
    assert line["metrics"]["moe.max_expert_load"]["value"] >= 100
    assert "left_running" in proc.stdout
