"""The DeepSeek-V3.2-Exp configuration and its cell ``deepseek_v32_longdocs``
(ISSUE 61): what ``BENCHMARK.json`` lists for them, held by NAME and cut at
this PR's first entry (``pr61_entries``: never ``[-1]``, a total or a whole
``workloads`` list, so the next PR can add behind them); the arithmetic of
``perfbench/lib/picked_work.py`` against counts by hand; the five readers on
hand-made ``ctx``s; the published model's parameter count from shapes alone;
and a CPU rehearsal of the cell at a toy size, over a manifest BUILT here
from the committed tiny one plus this PR's entries. Counts and structure
only: no number here is a device number.

This PR is no ``benchmark`` PR, so its hold lives in this file, which it
adds: ``tests/perfbench/held.py`` is a file the benchmark already has. A
later ``benchmark`` PR moves ``pr61_entries`` into ``held.CHECKS``.
"""

import copy
import json
import os
import subprocess
import sys

import pytest

from perfbench.lib import contract, latent_work, picked_work
from perfbench.lib import manifest as manifest_lib
from tests.perfbench import held
from tests.perfbench.test_perfbench_additions import add_a_prs_entries

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY_DIR = os.path.join(HERE, "tiny")
BENCH = manifest_lib.load()
CONFIG, CELL, MIX = "deepseek_v32_exp_l5", "deepseek_v32_longdocs", "longdocs"
BEFORE = "nemotron3_nano_reason"  # the last cell of the lists joined
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
           "vocab_size", "num_nextn_predict_layers"]
HP = manifest_lib.config(BENCH, CONFIG)
NEW = ["kernel.picked_latent_attn_roofline",
       "kernel.index_score_roofline.latent", "step.picked_share",
       "attn.picked_share", "picked.turn_roofline"]
ROW = {  # unit, better, source, layer
    NEW[0]: ("%", "higher", "device_trace", "kernels"),
    NEW[1]: ("%", "higher", "device_trace", "kernels"),
    NEW[2]: ("%", "higher", "device_trace", "jitted step"),
    NEW[3]: ("%", "lower", "program_counter", "kernels"),
    NEW[4]: ("%", "higher", "device_trace", "jitted step")}
JOINED = ["client.tokens_per_s", "client.ttft_p50_ms.gap",
          "client.ttft_p95_ms.gap", "sched.occupancy.gap",
          "sched.prefix_hit_share.gap", "paging.peak_pages_in_use.gap",
          "device.idle_share.gap", "step.prefill_share.gap",
          "step.turn_ms.gap", "sched.queue_wait_ms.gap",
          "sched.host_share.gap", "sched.stall_share.gap",
          "replica.stream_lag_ms.gap", "sched.prefill_turn_ms.gap",
          "sched.prefill_turn_share.gap", "sched.fused_turn_share.gap",
          "moe.max_expert_load", "moe.held_route_share",
          "setup.jit_trace_lower_s", "setup.jit_compile_s",
          "setup.jit_cache_hit_share", "setup.jit_compile_events",
          "setup.weights_s", "setup.scheduler_build_s"]
# readers whose arithmetic or counters are another model's, and the two that
# read a PLAIN step, which this traffic rarely runs (ROADMAP R0.11)
NOT_JOINED = ["kernel.paged_attn_roofline", "moe.decode_step_roofline",
              "step.mixer_share", "kernel.linear_attn_roofline",
              "kernel.sparse_attn_roofline", "attn.selected_share",
              "kernel.retention_step_roofline",
              "kernel.retention_chunk_roofline",
              "kernel.window_attn_roofline", "kernel.global_attn_roofline",
              "paging.window_held_share", "kernel.index_score_roofline",
              "kernel.indexed_attn_roofline", "step.indexer_share",
              "attn.indexed_share", "indexed.turn_roofline",
              "kernel.latent_attn_roofline", "latent.turn_roofline",
              "step.latent_share", "kernel.ssm_chunk_roofline",
              "kernel.ssm_step_roofline", "step.ssm_share",
              "ssm.decode_step_roofline", "step.decode_ms.gap",
              "sched.decode_turn_ms.gap", "sched.prefix_hit_share",
              "serve_tokens_per_s"]
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def by_name(rows):
    return {r["name"]: r for r in rows}


def pr61_entries(manifest):
    """This PR's entries as it wrote them, found by name; whatever a later
    PR put behind them is free."""
    config = by_name(manifest["configs"])[CONFIG]
    assert config["file"] == f"perfbench/configs/{CONFIG}.json"
    assert config["reduced"] == REDUCED
    assert config["source"] == ("https://huggingface.co/deepseek-ai/"
                                "DeepSeek-V3.2-Exp/blob/main/config.json")
    cell = by_name(manifest["workloads"])[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    names = [w["name"] for w in manifest["workloads"]]
    assert names.index(CELL) > names.index(BEFORE)
    rows = by_name({**by_name(manifest["per_layer"]),
                    **by_name(manifest["end_to_end"])}.values())
    for name in JOINED + ["gap_p95_ms"]:
        cells = rows[name]["workloads"]
        assert cells[cells.index(CELL) - 1] == BEFORE, name
        assert rows[name].get("moves", "gap_p95_ms") in ("gap_p95_ms",
                                                         "setup_s")
    for name in NOT_JOINED:
        assert CELL not in rows[name]["workloads"], name
    order = [m["name"] for m in manifest["per_layer"]]
    at = order.index(NEW[0])
    assert order[at:at + len(NEW)] == NEW       # together, in this order
    assert at > order.index("moe.held_route_share")  # behind PR 59's
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
    pr61_entries(BENCH)
    parent = without_this_pr(BENCH)
    assert CELL not in json.dumps(parent) and CONFIG not in json.dumps(parent)
    held.only_added(parent, BENCH)
    held.static_rules(BENCH)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(BENCH["workloads"]) >= 12 and len(BENCH["configs"]) >= 11


@pytest.mark.parametrize("tags", [("later",), ("later", "and_later")],
                         ids=["one_pr_behind_it", "two_prs_behind_it"])
def test_its_hold_accepts_what_later_prs_add(tags):
    later = copy.deepcopy({k: v for k, v in BENCH.items() if k != "_dir"})
    for tag in tags:
        add_a_prs_entries(later, tag)
    pr61_entries(later)
    held.only_added(BENCH, later)
    held.static_rules(later)
    held.only_added(without_this_pr(later), later)


def row_of(manifest, name):
    return by_name(manifest["per_layer"])[name]


@pytest.mark.parametrize("edit", [
    lambda m: m["workloads"].insert(0, m["workloads"].pop(
        [w["name"] for w in m["workloads"]].index(CELL))),
    lambda m: m["per_layer"].remove(row_of(m, NEW[1])),
    lambda m: row_of(m, "kernel.latent_attn_roofline")["workloads"].append(
        CELL),
    lambda m: by_name(m["end_to_end"])["gap_p95_ms"]["workloads"].remove(
        CELL),
    lambda m: by_name(m["configs"])[CONFIG]["reduced"].append(
        "kv_lora_rank"),
    lambda m: row_of(m, NEW[0])["workloads"].insert(
        0, "glm47_flash_longdocs"),
    lambda m: row_of(m, NEW[4])["workloads"].append("keye_longctx"),
    lambda m: by_name(m["workloads"])[CELL].update(chips=4),
], ids=["the_cell_moved_to_the_front", "a_reader_taken_away",
        "the_cell_on_the_dense_latent_kernels_roofline",
        "the_cell_out_of_gap_p95_ms", "a_width_listed_as_reduced",
        "another_cell_before_it_in_its_metric",
        "a_cell_the_benchmark_had_on_a_new_metric",
        "four_chips_for_one_chips_work"])
def test_its_hold_refuses_an_edit_of_its_entries(edit):
    edited = copy.deepcopy({k: v for k, v in BENCH.items() if k != "_dir"})
    edit(edited)
    with pytest.raises((AssertionError, KeyError, ValueError)):
        pr61_entries(edited)


def published():
    """The catalog's entry of the model, from the guide beside the builder's
    instructions where that is installed."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                row = json.loads(line)
                if row["name"] == "DeepSeek-V3.2-Exp":
                    return row["config"]
    return None


def test_the_configuration_is_the_published_one_cut_as_the_file_says():
    """Every width as published; what differs is the depth and its leading
    dense layers, the experts HELD beside the router's published 256, the
    vocabulary's slice and the prediction block."""
    widths = {"hidden_size": 7168, "num_attention_heads": 128,
              "q_lora_rank": 1536, "kv_lora_rank": 512,
              "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
              "v_head_dim": 128, "index_n_heads": 64, "index_head_dim": 128,
              "index_topk": 2048, "intermediate_size": 18432,
              "moe_intermediate_size": 2048, "n_group": 8, "topk_group": 4,
              "num_experts_per_tok": 8, "n_shared_experts": 1,
              "routed_scaling_factor": 2.5, "rms_norm_eps": 1e-06,
              "router_experts": 256}
    assert {k: HP[k] for k in widths} == widths
    assert HP["rope_scaling"] == {
        "beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
        "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
        "type": "yarn"}
    catalog = published()
    if catalog is not None:
        differs = {k for k, v in catalog.items()
                   if HP.get(k, "absent") != v}
        assert differs == set(REDUCED)
    assert set(HP["reduced"]) == set(REDUCED)
    assert (HP["num_hidden_layers"], HP["first_k_dense_replace"]) == (5, 1)
    assert (HP["n_routed_experts"], HP["num_experts"],
            HP["experts_held_first"], HP["vocab_size"],
            HP["num_nextn_predict_layers"]) == (16, 16, 0, 16160, 0)
    assert "SIXTEEN chips" in HP["stands_for"] and "layout" in HP
    assert "sixteen" in HP["reduced"]["n_routed_experts"].lower()
    assert "11.17 GB" in HP["reduced"]["num_hidden_layers"]
    assert HP["program"]["dtype"] == "bfloat16"
    said = " ".join(HP["assumed"])
    for what in ("HALF-SPLIT", "YaRN", "1.8738", "bottleneck", "LayerNorm",
                 "ties", "Hadamard", "FP8", "TWO largest", "1e-20",
                 "e_score_correction_bias", "num_experts 16", "TWO ROWS"):
        assert what in said, what
    fam = manifest_lib.read_json_from_bench("families", "deepseek_v32")
    assert fam["preset"] == "deepseek_v32_debug"
    assert fam["reference"] == "deepseek_v32"
    assert (fam["keys"]["n_routed_experts"], fam["keys"]["router_experts"],
            fam["keys"]["experts_held_first"]) == (
        "moe_held_count", "moe_num_experts", "moe_held_first")
    assert (fam["keys"]["n_group"], fam["keys"]["topk_group"]) == (
        "moe_groups", "moe_top_groups")
    assert fam["constants"] == {"norm": "rmsnorm", "pos": "rope",
                                "mlp": "moe", "moe_scoring": "sigmoid"}
    assert set(fam["keys"]) - {"num_key_value_heads"} <= set(HP)


def test_the_cell_and_its_traffic_are_what_the_issue_names():
    cell = manifest_lib.read_json(BENCH, "cells", CELL)
    dep = cell["deployment"]
    assert set(dep) == {"slots", "prefill_chunk", "arena_len", "page_tokens",
                        "kv_pages", "prefix_cache"}  # no option was added
    assert dep["prefix_cache"] is True
    assert (dep["slots"], dep["prefill_chunk"], dep["page_tokens"]) == (
        8, 512, 16)
    # ISSUE 61's first choice, or its one fallback
    assert (dep["arena_len"], dep["kv_pages"]) in ((66048, 32769),
                                                   (49664, 24833))
    assert cell["check_prompt_tokens"] > HP["index_topk"]  # the check selects
    assert cell["check_prompt_tokens"] % dep["page_tokens"] == 1
    assert cell["check_prompt_tokens"] > 2 * dep["prefill_chunk"]
    assert set(cell["check_tolerance"]) == {
        "logit_err", "logit_rms_err", "served_margin", "given_logit_err",
        "given_logit_rms_err"}
    assert "float8" in cell["check_tolerance_why"]
    assert cell["warmup_s"] >= 40 and cell["check_new_tokens"] == 32
    mix = manifest_lib.read_json(BENCH, "traffic", MIX)
    assert mix["arrival"] == {"mode": "closed", "clients": 8}
    assert mix["documents"]["asks"] == 4
    assert mix["documents"]["tokens"]["min"] == 16384
    assert mix["documents"]["tokens"]["max"] + 128 + 256 <= dep["arena_len"]
    assert mix["prompt_tokens"] == {"min": 32, "max": 128}
    assert mix["output_tokens"] == {"min": 64, "max": 256}
    assert (mix["block"], mix["shuffle"]) == (32, 8)
    why = by_name(BENCH["workloads"])[CELL]["why"]
    assert "16384-" in why and "64-256" in why and len(why) <= 200


def test_count_params_at_the_published_sizes_is_the_published_671_9b():
    """Shapes only (``jax.eval_shape``): the layer equations of ISSUE 61 at
    the catalog's sizes, no prediction block, add up to 671,877,944,064; the
    cut as the configuration file says to 4,635,518,208."""
    import jax
    import jax.numpy as jnp

    from perfbench.lib import configs
    from ray_tpu.models.transformer import count_params, init_params

    fam = manifest_lib.read_json_from_bench("families", "deepseek_v32")

    def count(hp):
        cfg = configs.build_program_config(
            *configs.program_overrides(hp, fam))
        return count_params(jax.eval_shape(
            lambda: init_params(cfg, jax.random.PRNGKey(0))))

    assert count(HP) == 4_635_518_208 == picked_work.model_params(HP)
    whole = dict(HP, num_hidden_layers=61, first_k_dense_replace=3,
                 n_routed_experts=256, vocab_size=129280)
    assert count(whole) == 671_877_944_064 == picked_work.model_params(whole)
    assert jnp.bfloat16 == jnp.dtype(HP["program"]["dtype"])


# ------------------------------------------------------------ the arithmetic


def test_the_work_of_the_kind_by_hand():
    assert picked_work.score_pair_flops(HP) == 2 * 64 * 128 == 16_384
    assert picked_work.index_key_bytes(HP) == 256
    assert latent_work.token_bytes(HP) == 1152
    # 2,176 operations a chosen (query, key, head): 242 a byte of the row
    assert latent_work.pair_flops(HP) == 128 * 2176 == 278_528
    assert 241 < latent_work.pair_flops(HP) / 1152 < 242
    assert picked_work.indexer_params(HP) == 13_959_424
    assert latent_work.attention_params(HP) == 187_107_328
    assert picked_work.layer_params(HP, 0) == 597_442_816
    assert picked_work.layer_params(HP, 1) == 951_599_616
    # of a token's 8 routes a sixteenth lands here: 0.5 pairs a token
    assert picked_work.layer_params(HP, 1, 0.5) == (
        246_956_544 + 0.5 * 44_040_192)


SIZES = {"vocab_size": 16160, "num_layers": 5, "embed_dim": 7168,
         "num_heads": 128, "num_kv_heads": 128, "head_dim": 192,
         "mlp_dim": 2048, "mlp": "moe", "max_seq_len": 163840}
# a window of 1000 turns, every one a chunk of 512 at a context of 40k with
# 6 live rows along at 40k each; five layers
CHUNK_PAIRS = 5 * (512 * 40_000 + 512 * 513 // 2)
STEP_PAIRS = 5 * 6 * 40_001
COUNTERS = {
    "decode_steps": 1000, "prefill_chunks": 1000, "fused_turns": 1000,
    "turns": 1000, "prefill_tokens": 512_000, "fused_step_rows": 6000,
    "picked_index_pairs": 1000 * (CHUNK_PAIRS + STEP_PAIRS),
    "picked_step_index_pairs": 1000 * STEP_PAIRS,
    "picked_chosen_pairs": 1000 * 5 * 2048 * 518,
    "picked_step_chosen_pairs": 1000 * 5 * 2048 * 6,
    "picked_latent_bytes": 1000 * 5 * 2048 * 518 * 1152,
    "picked_index_key_bytes": 1000 * 5 * (40_512 + 6 * 40_001) * 256,
    "moe_layer_calls": 8000, "moe_rows_routed": 1_040_000,
    "moe_routes_chosen": 16_576_000, "moe_max_expert_rows": 300_000}
PROGRAMS = {"jit_paged_prefill_chunk": {"count": 50, "sum_s": 3.0,
                                        "median_s": 0.060}}
OPS = {"index_score [custom-call]": {"count": 500, "sum_s": 0.9},
       "indexed_select [custom-call]": {"count": 500, "sum_s": 0.3},
       "picked_latent_chunk_attention [custom-call]": {"count": 2000,
                                                       "sum_s": 0.5},
       "picked_latent_step_attention [custom-call]": {"count": 250,
                                                      "sum_s": 0.01},
       "gather.12": {"count": 4000, "sum_s": 0.4},
       "fusion": {"count": 9000, "sum_s": 0.8},
       "copy-done": {"count": 400, "sum_s": 0.02}}


def ctx_of(delta, programs=PROGRAMS, ops=OPS):
    trace = (None if programs is None else {
        "programs": programs, "ops": ops, "busy_s": 2.95,
        "ops_by_program": {"jit_paged_prefill_chunk": ops}})
    return {"counters": {"delta": delta, "end": delta},
            "trace": trace, "config": HP, "sizes": SIZES, "device": V5E,
            "cell": manifest_lib.read_json(BENCH, "cells", CELL)}


def read(metric, ctx):
    return manifest_lib.metric_reader(metric)(ctx)


def test_the_readers_on_a_hand_made_window():
    ctx = ctx_of(COUNTERS)
    traced = 50 / 1000    # traced chunk runs over chunks; every one fused
    pair = max(1152 / 819e9, 278_528 / 197e12)
    least = traced * 1000 * 5 * 2048 * 518 * pair
    # the kernels' time AT MOST: their own columns and the ``-done`` ops
    assert read(NEW[0], ctx) == pytest.approx(
        100 * least / (0.5 + 0.02 + 0.01 + 0.02))
    score = traced * 1000 * (STEP_PAIRS * 260 / 819e9
                             + CHUNK_PAIRS * 16_384 / 197e12)
    assert read(NEW[1], ctx) == pytest.approx(100 * score / (0.9 + 0.02))
    assert read(NEW[2], ctx) == pytest.approx(
        100 * (0.9 + 0.3 + 0.5 + 0.01 + 0.4) / 2.95)
    assert read(NEW[3], ctx) == pytest.approx(
        100 * 5 * 2048 * 518 / (CHUNK_PAIRS + STEP_PAIRS))
    weights = 2 * (4_635_518_208 - 16160 * 7168)
    moved = (weights + 5 * (40_512 + 6 * 40_001) * 256
             + 5 * 2048 * 518 * 1152)
    flops = (2 * 518 * (597_442_816 + 4 * (246_956_544 + 0.5 * 44_040_192))
             + 2 * 7 * (16160 * 7168 + 7168)
             + (CHUNK_PAIRS + STEP_PAIRS) * 16_384
             + 5 * 2048 * 518 * 278_528)
    assert flops / 197e12 > moved / 819e9      # a full turn is the peak's
    assert read(NEW[4], ctx) == pytest.approx(100 * flops / 197e12 / 0.060)
    assert read("moe.held_route_share", ctx) == pytest.approx(
        100 * 1_040_000 / 16_576_000)
    for name in NEW:
        assert 0 < read(name, ctx) < 100, name


@pytest.mark.parametrize("metric", NEW)
def test_a_reader_that_finds_nothing_returns_nothing(metric):
    """Another model's program, or the parent's under these readers: no
    counters and no kernel of that name. Nothing, never 0, and nothing is
    raised."""
    other = {"decode_steps": 40, "prefill_chunks": 9, "tokens_generated": 7,
             "latent_tokens_context": 16, "latent_chunk_pairs": 90,
             "indexed_tokens_context": 128, "indexed_tokens_scored": 64}
    no_kernel = {"fusion": OPS["fusion"],
                 "index_score [custom-call]": OPS[
                     "index_score [custom-call]"]}
    assert read(metric, ctx_of(other, PROGRAMS, no_kernel)) is None
    assert read(metric, ctx_of({}, None)) is None
    assert read(metric, {"counters": {}, "trace": None}) is None
    assert read(metric, {}) is None
    if metric != "attn.picked_share":  # which reads counters alone
        assert read(metric, ctx_of(COUNTERS, None)) is None
    if metric in NEW[:3]:  # counters, no such kernel
        assert read(metric, ctx_of(COUNTERS, PROGRAMS, {
            "fusion": OPS["fusion"]})) is None
    zeros = {k: 0 for k in COUNTERS}
    assert read(metric, ctx_of(zeros, PROGRAMS, no_kernel)) is None


def test_a_line_of_the_cell_is_accepted_with_its_metrics_and_not_without():
    for traced in (False, True):
        mine = manifest_lib.metrics_for(BENCH, CELL, traced)
        names = {m["name"] for m in mine}
        assert (set(NEW) | set(JOINED)) <= names if (
            traced) else names == {"gap_p95_ms", "setup_s"}
        assert not names & set(NOT_JOINED)
        device = dict(V5E, memory_peak_bytes=14_000_000_000)
        if traced:
            device.update(window_s=3.0, busy_s=2.9)
        line = contract.build_line(
            correct=True, attempted=40, failed=0, device=device,
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
    assert len(old) == 11
    for cell in old:
        for traced in (False, True):
            names = {m["name"] for m in manifest_lib.metrics_for(
                BENCH, cell, traced)}
            assert not names & set(NEW), (cell, names & set(NEW))


# ---------------------------------------------------------------- rehearsal

TINY_CELL = "tiny_longdocs_picked"
TINY_JOINED = ["client.tokens_per_s", "client.ttft_p50_ms.gap",
               "sched.occupancy.gap", "sched.prefix_hit_share.gap",
               "paging.peak_pages_in_use.gap", "device.idle_share.gap",
               "step.prefill_share.gap", "sched.prefill_turn_share.gap",
               "sched.fused_turn_share.gap", "step.turn_ms.gap"]
# the readers of this PR that find something on a CPU: the kernels run
# interpreted there and leave no event of their names
TINY_NEW = ["attn.picked_share", "moe.held_route_share"]


def tiny_manifest(tmp_path):
    """The committed tiny manifest plus a toy DeepSeek-V3.2, its cell, the
    expert model's balance reader and this PR's readers that read no
    kernel."""
    with open(os.path.join(TINY_DIR, "BENCHMARK_turns.json")) as f:
        tiny = json.load(f)
    tiny["paths"] = [TINY_DIR]
    for config in tiny["configs"]:
        config["file"] = os.path.join(TINY_DIR, config["file"])
    tiny["configs"].append({
        "name": "tiny_deepseek", "source": "tests only",
        "file": os.path.join(TINY_DIR, "configs", "tiny_deepseek.json"),
        "reduced": [], "why": "a toy of DeepSeek-V3.2-Exp"})
    tiny["workloads"].append({
        "name": TINY_CELL, "config": "tiny_deepseek",
        "traffic": "tiny_longdocs", "chips": 1,
        "why": "the cell of a model that picks latents under the prefix "
               "cache and holds a share of its experts, at a toy size"})
    by_name(tiny["end_to_end"])["gap_p95_ms"]["workloads"].append(TINY_CELL)
    for name in TINY_JOINED:
        by_name(tiny["per_layer"])[name]["workloads"].append(TINY_CELL)
    for name in ["moe.max_expert_load"] + TINY_NEW:
        tiny["per_layer"].append(dict(by_name(BENCH["per_layer"])[name],
                                      workloads=[TINY_CELL]))
    path = tmp_path / "BENCHMARK_deepseek.json"
    path.write_text(json.dumps(tiny))
    return str(path)


def rehearse(manifest_path, trace, cache_dir, seed=2**31 + 61):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    env.pop("BENCH_RUN", None)
    script = (
        "import sys; sys.path.insert(0, {root!r}); "
        "from tests.perfbench import rehearse; "
        "sys.exit(rehearse.main({path!r}, 'rehearse_deepseek'))").format(
            root=ROOT, path=manifest_path)
    return subprocess.run(
        [sys.executable, "-c", script, "--workload", TINY_CELL,
         "--seed", str(seed), "--seconds", "2", "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)


def test_rehearsal_of_the_cell_that_picks_latents_under_the_cache(tmp_path):
    """The toy model through ``serve.run``, the scheduler, the radix cache
    and the two paged programs, checked against ``reference/deepseek_v32.py``
    by the harness — without choices, and GIVEN the routes; the kind's
    counters and the share's in the run's ``delta`` note; a traced line with
    the joined readers and the two of this PR that find something on a CPU
    (the one run is the traced one: it reports what the other would, and
    more)."""
    path = tiny_manifest(tmp_path)
    proc = rehearse(path, 1, tmp_path / "cache")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = contract.last_line(proc.stdout)
    assert contract.check_line(last, manifest_lib.load(path), TINY_CELL,
                               True) == []
    line = json.loads(last)
    assert line["correct"] is True and line["failed"] == 0
    checks = [json.loads(ln) for ln in proc.stdout.splitlines()
              if ln.startswith('{"note": "checks"')][0]
    delta = checks["delta"]
    assert checks["reference_check"]["logit_err"] < 1e-4
    assert checks["reference_check"]["prompt_tokens"] == 77  # past topk 24
    assert checks["reference_check"]["given_choices"] == "routes"
    assert checks["checks"]["reference_logits_given_choices"] is True
    assert 0 < line["compared"]["given_logit_err"]["value"] < 1e-4
    assert checks["scheduler"]["compiled_programs"] == 2
    assert delta["prefix_hit_tokens"] > 0     # index keys under the cache
    # three layers; a query attends min(t + 1, 24) of its t + 1
    assert 0 < delta["picked_chosen_pairs"] < delta["picked_index_pairs"]
    assert 0 < delta["picked_step_chosen_pairs"] <= (
        delta["picked_step_index_pairs"])
    assert delta["picked_latent_bytes"] == (
        delta["picked_chosen_pairs"] * 4 * (32 + 8))
    assert delta["picked_index_key_bytes"] > 0
    assert "latent_tokens_context" not in delta   # the dense kind's
    assert delta["moe_routes_chosen"] == 2 * 3 * delta["moe_live_rows"]
    assert 0 < delta["moe_rows_routed"] < delta["moe_routes_chosen"]
    assert delta["moe_shared_rows"] == 2 * delta["moe_live_rows"]
    assert 0 < line["metrics"]["attn.picked_share"]["value"] < 100
    assert 0 < line["metrics"]["moe.held_route_share"]["value"] < 100
    assert line["metrics"]["moe.max_expert_load"]["value"] >= 100
    assert "left_running" in proc.stdout
