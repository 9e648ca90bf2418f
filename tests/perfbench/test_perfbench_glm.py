"""The GLM-4.7-Flash configuration and its cell ``glm47_flash_longdocs``
(ISSUE 55): what ``BENCHMARK.json`` lists for them, held by NAME and cut at
this PR's first entry (``pr55_entries``: never ``[-1]``, a total or a whole
``workloads`` list, so the next PR can add behind them); the arithmetic of
``perfbench/lib/latent_work.py`` against counts by hand; the three readers on
hand-made ``ctx``s; and a CPU rehearsal of the cell at a toy size in both
kinds of run, over a manifest BUILT here from the committed tiny one plus
this PR's entries. Counts and structure only: no number here is a device
number.

This PR is no ``benchmark`` PR, so its hold lives in this file, which it
adds: ``tests/perfbench/held.py`` is a file the benchmark already has. A
later ``benchmark`` PR moves ``pr55_entries`` into ``held.CHECKS``.
"""

import copy
import json
import os
import subprocess
import sys

import pytest

from perfbench.lib import contract, latent_work
from perfbench.lib import manifest as manifest_lib
from tests.perfbench import held
from tests.perfbench.test_perfbench_additions import add_a_prs_entries

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY_DIR = os.path.join(HERE, "tiny")
BENCH = manifest_lib.load()
CONFIG, CELL, MIX = "glm47_flash_l6", "glm47_flash_longdocs", "longdocs"
BEFORE = "keye_longctx"   # the cell that was the last of every list joined
HP = manifest_lib.config(BENCH, CONFIG)
NEW = ["kernel.latent_attn_roofline", "latent.turn_roofline",
       "step.latent_share"]
ROW = {  # unit, better, source, layer
    "kernel.latent_attn_roofline": ("%", "higher", "device_trace",
                                    "kernels"),
    "latent.turn_roofline": ("%", "higher", "device_trace", "jitted step"),
    "step.latent_share": ("%", "higher", "device_trace", "jitted step")}
JOINED = ["client.tokens_per_s", "client.ttft_p50_ms.gap",
          "client.ttft_p95_ms.gap", "sched.occupancy.gap",
          "sched.prefix_hit_share.gap", "paging.peak_pages_in_use.gap",
          "device.idle_share.gap", "step.prefill_share.gap",
          "step.turn_ms.gap", "sched.queue_wait_ms.gap",
          "sched.host_share.gap", "sched.stall_share.gap",
          "replica.stream_lag_ms.gap", "sched.prefill_turn_ms.gap",
          "sched.prefill_turn_share.gap", "sched.fused_turn_share.gap",
          "moe.max_expert_load"]
# readers whose arithmetic is another model's, and the two that read a PLAIN
# step, which this traffic rarely runs (ROADMAP R0.11)
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
              "indexed.turn_roofline", "step.decode_ms.gap",
              "sched.decode_turn_ms.gap", "sched.prefix_hit_share",
              "serve_tokens_per_s"]
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def by_name(rows):
    return {r["name"]: r for r in rows}


def pr55_entries(manifest):
    """This PR's entries as it wrote them, found by name; whatever a later
    PR put behind them is free."""
    config = by_name(manifest["configs"])[CONFIG]
    assert config["file"] == f"perfbench/configs/{CONFIG}.json"
    assert config["reduced"] == ["num_hidden_layers",
                                 "num_nextn_predict_layers"]
    assert config["source"] == ("https://huggingface.co/zai-org/"
                                "GLM-4.7-Flash/blob/main/config.json")
    cell = by_name(manifest["workloads"])[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    names = [w["name"] for w in manifest["workloads"]]
    assert names.index(CELL) > names.index(BEFORE)
    gap = by_name(manifest["end_to_end"])["gap_p95_ms"]["workloads"]
    assert gap[gap.index(CELL) - 1] == BEFORE
    rows = by_name({**by_name(manifest["per_layer"]),
                    **by_name(manifest["end_to_end"])}.values())
    for name in JOINED:
        cells = rows[name]["workloads"]
        assert cells[cells.index(CELL) - 1] == BEFORE, name
        assert rows[name]["moves"] == "gap_p95_ms"
    for name in NOT_JOINED:
        assert CELL not in rows[name]["workloads"], name
    order = [m["name"] for m in manifest["per_layer"]]
    at = order.index(NEW[0])
    assert order[at:at + len(NEW)] == NEW       # together, in this order
    assert at > order.index("indexed.turn_roofline")  # behind PR 51's
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
    ``workloads`` list at this PR's cell), so that the comparison below
    still holds once later PRs have added behind it."""
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
    pr55_entries(BENCH)
    parent = without_this_pr(BENCH)
    assert CELL not in json.dumps(parent) and CONFIG not in json.dumps(parent)
    held.only_added(parent, BENCH)
    held.static_rules(BENCH)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(BENCH["workloads"]) >= 10


@pytest.mark.parametrize("tags", [("later",), ("later", "and_later")],
                         ids=["one_pr_behind_it", "two_prs_behind_it"])
def test_its_hold_accepts_what_later_prs_add(tags):
    later = copy.deepcopy({k: v for k, v in BENCH.items() if k != "_dir"})
    for tag in tags:
        add_a_prs_entries(later, tag)
    pr55_entries(later)
    held.only_added(BENCH, later)
    held.static_rules(later)
    # and what this PR found is still found under them
    held.only_added(without_this_pr(later), later)


def row_of(manifest, name):
    return by_name(manifest["per_layer"])[name]


@pytest.mark.parametrize("edit", [
    lambda m: m["workloads"].insert(0, m["workloads"].pop(
        [w["name"] for w in m["workloads"]].index(CELL))),
    lambda m: m["per_layer"].remove(row_of(m, NEW[1])),
    lambda m: row_of(m, "kernel.paged_attn_roofline")["workloads"].append(
        CELL),
    lambda m: row_of(m, "step.decode_ms.gap")["workloads"].append(CELL),
    lambda m: by_name(m["end_to_end"])["gap_p95_ms"]["workloads"].remove(
        CELL),
    lambda m: by_name(m["configs"])[CONFIG]["reduced"].append(
        "kv_lora_rank"),
    lambda m: row_of(m, NEW[0])["workloads"].insert(0, "olmoe_reason"),
    lambda m: row_of(m, NEW[2])["workloads"].append("mistral7b_docs"),
    lambda m: by_name(m["workloads"])[CELL].update(chips=4),
], ids=["the_cell_moved_to_the_front", "a_reader_taken_away",
        "the_cell_in_the_paged_kernels_list",
        "the_cell_on_a_plain_steps_list",
        "the_cell_out_of_gap_p95_ms", "a_width_listed_as_reduced",
        "another_cell_before_it_in_its_metric",
        "a_cell_the_benchmark_had_on_a_new_metric",
        "four_chips_for_one_chips_work"])
def test_its_hold_refuses_an_edit_of_its_entries(edit):
    edited = copy.deepcopy({k: v for k, v in BENCH.items() if k != "_dir"})
    edit(edited)
    with pytest.raises((AssertionError, KeyError, ValueError)):
        pr55_entries(edited)


PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 768,
    "kv_lora_rank": 512, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64,
    "v_head_dim": 256, "vocab_size": 154880}


def test_the_configuration_is_the_published_one_cut_in_depth_only():
    """Every key of the catalog's entry under its own name; what differs is
    the depth and the prediction block. No width is touched, the vocabulary
    and all 64 experts and the shared one are whole."""
    differs = {k for k, v in PUBLISHED.items() if HP.get(k, "absent") != v}
    assert differs == {"num_hidden_layers",
                       "num_nextn_predict_layers"} == set(HP["reduced"])
    assert HP["num_hidden_layers"] in (5, 6)
    assert HP["num_nextn_predict_layers"] == 0
    assert "pipeline" in HP["stands_for"] and "layout" in HP
    assert HP["program"]["dtype"] == "bfloat16"
    said = " ".join(HP["assumed"])
    for what in ("HALF-SPLIT", "n_group 1", "ties", "1e-20",
                 "e_score_correction_bias", "shared expert",
                 "multi-token-prediction", "640 LANES"):
        assert what in said, what
    # attention 21.76M, an expert 9.437M, an expert layer 635.3M, the dense
    # layer 84.68M; with embedding and head 2 x 317.2M
    assert latent_work.attention_params(HP) == 21_759_232
    assert latent_work.expert_params(HP) == 9_437_184
    assert latent_work.layer_params(HP, 0) == 84_677_888
    assert latent_work.layer_params(HP, 1) == 635_311_424
    assert latent_work.model_params(HP) == {
        6: 3_895_625_536, 5: 3_260_314_112}[HP["num_hidden_layers"]]
    fam = manifest_lib.read_json_from_bench("families", "glm4_moe_lite")
    assert fam["preset"] == "glm_moe_lite_debug"
    assert fam["reference"] == "glm_moe_lite"
    assert fam["keys"]["kv_lora_rank"] == "latent_kv_rank"
    assert fam["keys"]["first_k_dense_replace"] == "moe_dense_layers"
    assert fam["constants"]["moe_scoring"] == "sigmoid"
    # every key the family maps is one the configuration states
    assert set(fam["keys"]) <= set(HP)


def test_the_cell_and_its_traffic_are_what_the_issue_names():
    cell = manifest_lib.read_json(BENCH, "cells", CELL)
    dep = cell["deployment"]
    assert dep == {"slots": 8, "prefill_chunk": 512, "arena_len": 66048,
                   "page_tokens": 16, "kv_pages": 32769,
                   "prefix_cache": True}   # and no option was added
    assert (dep["kv_pages"] - 1) * dep["page_tokens"] >= 524288
    assert dep["arena_len"] % dep["prefill_chunk"] == 0
    assert 4096 <= cell["check_prompt_tokens"] <= 4608
    assert cell["check_prompt_tokens"] % dep["page_tokens"] == 1
    assert cell["check_new_tokens"] == 32
    tol = cell["check_tolerance"]
    assert set(tol) == {"logit_err", "logit_rms_err", "served_margin",
                        "given_logit_err", "given_logit_rms_err"}
    assert "float8" in cell["check_tolerance_why"]
    keye = manifest_lib.read_json(BENCH, "cells", "keye_longctx")
    for key in ("warmup_s", "trace_s", "grace_s"):
        assert cell[key] == keye[key]
    mix = manifest_lib.read_json(BENCH, "traffic", MIX)
    assert mix["arrival"] == {"mode": "closed", "clients": 8}
    assert mix["documents"] == {"tokens": {"min": 16384, "max": 65536},
                                "asks": 4}
    assert mix["prompt_tokens"] == {"min": 32, "max": 128}
    assert mix["output_tokens"] == {"min": 64, "max": 256}
    assert (mix["block"], mix["shuffle"]) == (32, 8)
    assert "order_seed" in mix and mix["order_why"]
    assert (mix["documents"]["tokens"]["max"] + mix["prompt_tokens"]["max"]
            + mix["output_tokens"]["max"]) <= dep["arena_len"]
    why = by_name(BENCH["workloads"])[CELL]["why"]
    assert "16384-65536" in why and "64-256" in why and len(why) <= 200


# ------------------------------------------------------------ the arithmetic


def test_the_work_of_the_kind_by_hand():
    # a (query, key) pair: 20 heads, a score over 576 and a product over 512
    assert latent_work.pair_flops(HP) == 20 * (2 * 576 + 2 * 512) == 43_520
    assert latent_work.token_bytes(HP) == 1152
    # 37.8 operations a byte: a step is the memory's
    assert 37.7 < latent_work.pair_flops(HP) / 1152 < 37.8 < 197e12 / 819e9
    # a 512 chunk: 22.3M operations a context token, absorbed
    assert 512 * latent_work.pair_flops(HP) == 22_282_240


SIZES = {"vocab_size": 154880, "num_layers": 6, "embed_dim": 2048,
         "num_heads": 20, "num_kv_heads": 20, "head_dim": 256,
         "mlp_dim": 1536, "mlp": "moe", "max_seq_len": 202752}
L = HP["num_hidden_layers"]
# a window of 1000 turns, every one a chunk of 500 real tokens whose last
# query is at a context of 30000, with 6 live rows at 25000 along
PAIRS = sum(range(29501, 30001))
COUNTERS = {
    "decode_steps": 1000, "prefill_chunks": 1000, "fused_turns": 1000,
    "turns": 1000, "prefill_tokens": 500_000, "fused_step_rows": 6000,
    "latent_tokens_context": L * 1000 * (PAIRS + 6 * 25000),
    "latent_step_tokens_context": L * 1000 * 6 * 25000,
    "latent_chunk_pairs": L * 1000 * PAIRS,
    "moe_layer_calls": 2 * (L - 1) * 1000}
PROGRAMS = {"jit_paged_prefill_chunk": {"count": 50, "sum_s": 2.5,
                                        "median_s": 0.050}}
OPS = {"latent_chunk_attention [custom-call]": {"count": 300, "sum_s": 1.2},
       "latent_step_attention [custom-call]": {"count": 300, "sum_s": 0.1},
       "fusion": {"count": 9000, "sum_s": 0.9}}


def ctx_of(delta, programs=PROGRAMS, ops=OPS):
    trace = (None if programs is None
             else {"programs": programs, "ops": ops, "busy_s": 2.45})
    return {"counters": {"delta": delta, "end": delta}, "trace": trace,
            "config": HP, "sizes": SIZES, "device": V5E,
            "cell": manifest_lib.read_json(BENCH, "cells", CELL)}


def read(metric, ctx):
    return manifest_lib.metric_reader(metric)(ctx)


def test_the_readers_on_a_hand_made_window():
    ctx = ctx_of(COUNTERS)
    share = 50 / 1000   # a twentieth of the window's turns, all fused
    least = share * L * 1000 * (6 * 25000 * 1152 / 819e9
                                + PAIRS * 43520 / 197e12)
    assert read("kernel.latent_attn_roofline", ctx) == pytest.approx(
        100 * least / 1.3)
    assert read("step.latent_share", ctx) == pytest.approx(100 * 1.3 / 2.45)
    weights = sum(latent_work.layer_params(HP, i) for i in range(L))
    active = sum(latent_work.layer_params(HP, i, 4) for i in range(L))
    head = 2048 * 154880 + 2048
    moved = 2 * (weights + head) + L * 6 * 25000 * 1152
    flops = (2 * 506 * active + 2 * 7 * head
             + L * (PAIRS + 6 * 25000) * 43520)
    turn = max(moved / 819e9, flops / 197e12)
    assert turn == flops / 197e12   # at 30k the chunk's pairs bound the turn
    assert read("latent.turn_roofline", ctx) == pytest.approx(
        100 * turn / 0.050)
    for name in NEW:
        assert 0 < read(name, ctx) < 100


@pytest.mark.parametrize("metric", NEW)
def test_a_reader_that_finds_nothing_returns_nothing(metric):
    """Another model's program, or the parent's under these readers: no
    counters and no kernel of that name. Nothing, never 0, and nothing is
    raised."""
    other = {"decode_steps": 40, "prefill_chunks": 9, "tokens_generated": 7,
             "moe_layer_calls": 16, "moe_experts_hit": 90}
    no_kernel = {"fusion": OPS["fusion"]}
    if metric != "step.latent_share":  # which reads the kernels alone
        assert read(metric, ctx_of(other)) is None
    assert read(metric, ctx_of(other, PROGRAMS, no_kernel)) is None
    assert read(metric, ctx_of({}, None)) is None
    assert read(metric, {"counters": {}, "trace": None}) is None
    if metric.startswith(("kernel.", "step.")):  # counters, no such kernel
        assert read(metric, ctx_of(COUNTERS, PROGRAMS, no_kernel)) is None
    assert read(metric, ctx_of(COUNTERS, None)) is None
    # the counters present and nothing counted: still nothing, not 0
    zeros = {k: 0 for k in COUNTERS}
    assert read(metric, ctx_of(zeros, PROGRAMS, no_kernel)) is None
    # a window without a live decode row: the chunk's kernel alone is read
    chunks_only = {k: v for k, v in OPS.items() if "step" not in k}
    assert read(metric, ctx_of(COUNTERS, PROGRAMS, chunks_only)) > 0


def test_a_line_of_the_cell_is_accepted_with_its_metrics_and_not_without():
    for traced in (False, True):
        mine = manifest_lib.metrics_for(BENCH, CELL, traced)
        names = {m["name"] for m in mine}
        assert (set(NEW) | set(JOINED)) <= names if traced else (
            names == {"gap_p95_ms", "setup_s"})
        assert not names & set(NOT_JOINED)
        device = dict(V5E, memory_peak_bytes=13_000_000_000)
        if traced:
            device.update(window_s=3.0, busy_s=2.9)
        line = contract.build_line(
            correct=True, attempted=30, failed=0, device=device,
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
    assert len(old) == 9
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
# the reader of this PR that finds something on a CPU: the kernels run as
# plain ``jax.numpy`` there and leave no event of their names
TINY_NEW = ["latent.turn_roofline"]


def tiny_manifest(tmp_path):
    """The committed tiny manifest plus a toy GLM, its cell, the expert
    model's balance reader and this PR's reader that reads no kernel."""
    with open(os.path.join(TINY_DIR, "BENCHMARK_turns.json")) as f:
        tiny = json.load(f)
    tiny["paths"] = [TINY_DIR]
    for config in tiny["configs"]:
        config["file"] = os.path.join(TINY_DIR, config["file"])
    tiny["configs"].append({
        "name": "tiny_glm", "source": "tests only",
        "file": os.path.join(TINY_DIR, "configs", "tiny_glm.json"),
        "reduced": [], "why": "a toy of GLM-4.7-Flash"})
    tiny["workloads"].append({
        "name": "tiny_longdocs", "config": "tiny_glm",
        "traffic": "tiny_longdocs", "chips": 1,
        "why": "the cell of a model with latent attention, at a toy size"})
    by_name(tiny["end_to_end"])["gap_p95_ms"]["workloads"].append(
        "tiny_longdocs")
    for name in TINY_JOINED:
        by_name(tiny["per_layer"])[name]["workloads"].append("tiny_longdocs")
    for name in ["moe.max_expert_load"] + TINY_NEW:
        tiny["per_layer"].append(dict(by_name(BENCH["per_layer"])[name],
                                      workloads=["tiny_longdocs"]))
    path = tmp_path / "BENCHMARK_glm.json"
    path.write_text(json.dumps(tiny))
    return str(path)


def rehearse(manifest_path, trace, cache_dir, seed=2**31 + 55):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    env.pop("BENCH_RUN", None)
    script = (
        "import sys; sys.path.insert(0, {root!r}); "
        "from tests.perfbench import rehearse; "
        "sys.exit(rehearse.main({path!r}, 'rehearse_glm'))").format(
            root=ROOT, path=manifest_path)
    return subprocess.run(
        [sys.executable, "-c", script, "--workload", "tiny_longdocs",
         "--seed", str(seed), "--seconds", "2", "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("trace", [0, 1], ids=["end_to_end", "traced"])
def test_rehearsal_of_the_cell_with_latent_pages(tmp_path, trace):
    """The toy model through ``serve.run``, the scheduler with its radix
    prefix cache and the two paged programs, checked against
    ``reference/glm_moe_lite.py`` by the harness — without choices, and GIVEN
    the routes; documents asked four times hit the cache; the kind's
    counters in the run's ``delta`` note; in the traced run a line with the
    joined readers and the one of this PR that finds something on a CPU."""
    path = tiny_manifest(tmp_path)
    proc = rehearse(path, trace, tmp_path / "cache")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = contract.last_line(proc.stdout)
    assert contract.check_line(last, manifest_lib.load(path),
                               "tiny_longdocs", bool(trace)) == []
    line = json.loads(last)
    assert line["correct"] is True and line["failed"] == 0
    checks = [json.loads(ln) for ln in proc.stdout.splitlines()
              if ln.startswith('{"note": "checks"')][0]
    delta = checks["delta"]
    assert checks["reference_check"]["logit_err"] < 1e-4
    assert checks["reference_check"]["prompt_tokens"] == 77
    assert checks["reference_check"]["given_choices"] == "routes"
    assert checks["checks"]["reference_logits_given_choices"] is True
    assert 0 < line["compared"]["given_logit_err"]["value"] < 1e-4
    assert checks["scheduler"]["compiled_programs"] == 2
    assert 0 < delta["latent_step_tokens_context"] < delta[
        "latent_tokens_context"]
    assert 0 < delta["latent_chunk_pairs"] < delta["latent_tokens_context"]
    assert delta["latent_bytes_moved"] > 0
    assert delta["moe_rows_routed"] == 3 * 2 * delta["moe_live_rows"]
    assert delta["moe_shared_rows"] == 2 * delta["moe_live_rows"]
    assert delta["prefix_hit_tokens"] > 0   # the documents' later asks
    if trace:
        assert line["metrics"]["latent.turn_roofline"]["value"] > 0
        assert line["metrics"]["moe.max_expert_load"]["value"] >= 100
        assert line["metrics"]["sched.prefix_hit_share.gap"]["value"] > 0
    else:
        assert set(line["metrics"]) == {"gap_p95_ms", "setup_s"}
    assert "left_running" in proc.stdout
