"""The Keye-VL-2.0-30B-A3B configuration and its cell ``keye_longctx`` (ISSUE
51): what ``BENCHMARK.json`` lists for them, held by NAME and cut at this
PR's first entry (``pr51_entries``: never ``[-1]``, a total or a whole
``workloads`` list, so the next PR can add behind them); the arithmetic of
``perfbench/lib/indexed_work.py`` against counts by hand; the five readers on
hand-made ``ctx``s; and a CPU rehearsal of the cell at a toy size in both
kinds of run, over a manifest BUILT here from the committed tiny one plus
this PR's entries. Counts and structure only: no number here is a device
number.

This PR is no ``benchmark`` PR, so its hold lives in this file, which it
adds: ``tests/perfbench/held.py`` is a file the benchmark already has. A
later ``benchmark`` PR moves ``pr51_entries`` into ``held.CHECKS``.
"""

import copy
import json
import os
import subprocess
import sys

import pytest

from perfbench.lib import contract, indexed_work, window_work
from perfbench.lib import manifest as manifest_lib
from tests.perfbench import held
from tests.perfbench.test_perfbench_additions import add_a_prs_entries

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY_DIR = os.path.join(HERE, "tiny")
BENCH = manifest_lib.load()
CONFIG, CELL, MIX = "keye_vl2_30b_a3b_l5", "keye_longctx", "longctx"
HP = manifest_lib.config(BENCH, CONFIG)
NEW = ["kernel.index_score_roofline", "kernel.indexed_attn_roofline",
       "step.indexer_share", "attn.indexed_share", "indexed.turn_roofline"]
ROW = {  # unit, better, source, layer
    "kernel.index_score_roofline": ("%", "higher", "device_trace", "kernels"),
    "kernel.indexed_attn_roofline": ("%", "higher", "device_trace",
                                     "kernels"),
    "step.indexer_share": ("%", "higher", "device_trace", "jitted step"),
    "attn.indexed_share": ("%", "lower", "program_counter", "kernels"),
    "indexed.turn_roofline": ("%", "higher", "device_trace", "jitted step")}
# the lists the cell joined, each behind the cell that was its last
JOINED = {
    "client.tokens_per_s": "mellum2_shortlong",
    "client.ttft_p50_ms.gap": "mellum2_shortlong",
    "client.ttft_p95_ms.gap": "mellum2_shortlong",
    "sched.occupancy.gap": "mellum2_shortlong",
    "sched.prefix_hit_share.gap": "mellum2_shortlong",
    "paging.peak_pages_in_use.gap": "mellum2_shortlong",
    "device.idle_share.gap": "mellum2_shortlong",
    "step.prefill_share.gap": "mellum2_shortlong",
    "sched.queue_wait_ms.gap": "mellum2_shortlong",
    "sched.host_share.gap": "mellum2_shortlong",
    "sched.stall_share.gap": "mellum2_shortlong",
    "replica.stream_lag_ms.gap": "mellum2_shortlong",
    "moe.max_expert_load": "mellum2_shortlong",
    "sched.prefill_turn_ms.gap": "mellum2_shortlong",
    "sched.prefill_turn_share.gap": "mellum2_shortlong",
    "sched.fused_turn_share.gap": "mellum2_shortlong",
    "step.turn_ms.gap": "mellum2_shortlong"}
# readers whose arithmetic is another model's (a dense paged kernel, an
# expert of ``intermediate_size``, other mixers), and the two that read a
# PLAIN step: 8 slots of 58 chunks to 277 steps leave no turn without a chunk
NOT_JOINED = ["kernel.paged_attn_roofline", "moe.decode_step_roofline",
              "step.mixer_share", "kernel.linear_attn_roofline",
              "kernel.sparse_attn_roofline", "attn.selected_share",
              "kernel.retention_step_roofline",
              "kernel.retention_chunk_roofline",
              "retention.decode_step_roofline", "step.retention_share",
              "kernel.window_attn_roofline", "kernel.global_attn_roofline",
              "window.decode_step_roofline", "paging.window_held_share",
              "step.decode_ms.gap", "sched.decode_turn_ms.gap"]
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def by_name(rows):
    return {r["name"]: r for r in rows}


def pr51_entries(manifest):
    """This PR's entries as it wrote them, found by name; whatever a later
    PR put behind them is free."""
    config = by_name(manifest["configs"])[CONFIG]
    assert config["file"] == f"perfbench/configs/{CONFIG}.json"
    assert config["reduced"] == ["num_hidden_layers"]
    assert "Kwai-Keye/Keye-VL-2.0-30B-A3B" in config["source"]
    cell = by_name(manifest["workloads"])[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    names = [w["name"] for w in manifest["workloads"]]
    assert names.index(CELL) > names.index("mellum2_shortlong")
    gap = by_name(manifest["end_to_end"])["gap_p95_ms"]["workloads"]
    assert gap[gap.index(CELL) - 1] == "mellum2_shortlong"
    rows = by_name(manifest["per_layer"])
    for name, before in JOINED.items():
        cells = rows[name]["workloads"]
        assert cells[cells.index(CELL) - 1] == before, name
        assert rows[name]["moves"] == "gap_p95_ms"
    for name in NOT_JOINED:
        assert CELL not in rows[name]["workloads"], name
    order = [m["name"] for m in manifest["per_layer"]]
    at = order.index(NEW[0])
    assert order[at:at + len(NEW)] == NEW       # together, in this order
    assert at > order.index("paging.window_held_share")  # behind PR 46's
    for name in NEW:
        row = rows[name]
        # the rule PR 45 paid for: a new entry lists the PR's own cell first
        # and no cell the benchmark had (whose parent has no such counter)
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
    pr51_entries(BENCH)
    for check in held.CHECKS + held.FOUND:   # every earlier PR's hold
        check(BENCH)
    parent = without_this_pr(BENCH)
    assert CELL not in json.dumps(parent) and CONFIG not in json.dumps(parent)
    held.only_added(parent, BENCH)
    held.static_rules(BENCH)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(BENCH["workloads"]) >= 9


@pytest.mark.parametrize("tags", [("later",), ("later", "and_later")],
                         ids=["one_pr_behind_it", "two_prs_behind_it"])
def test_its_hold_accepts_what_later_prs_add(tags):
    later = copy.deepcopy({k: v for k, v in BENCH.items() if k != "_dir"})
    for tag in tags:
        add_a_prs_entries(later, tag)
    pr51_entries(later)
    held.only_added(BENCH, later)
    held.static_rules(later)
    # and what this PR found is still found under them
    held.only_added(without_this_pr(later), later)


def row_of(manifest, name):
    return by_name(manifest["per_layer"])[name]


@pytest.mark.parametrize("edit", [
    lambda m: m["workloads"].insert(0, m["workloads"].pop(
        [w["name"] for w in m["workloads"]].index(CELL))),
    lambda m: m["per_layer"].remove(row_of(m, NEW[2])),
    lambda m: row_of(m, "kernel.paged_attn_roofline")["workloads"].append(
        CELL),
    lambda m: row_of(m, "step.decode_ms.gap")["workloads"].append(CELL),
    lambda m: by_name(m["end_to_end"])["gap_p95_ms"]["workloads"].remove(
        CELL),
    lambda m: by_name(m["configs"])[CONFIG]["reduced"].append("head_dim"),
    lambda m: row_of(m, NEW[0])["workloads"].insert(0, "olmoe_reason"),
    lambda m: row_of(m, NEW[3])["workloads"].append("minicpm_sala_longdoc"),
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
        pr51_entries(edited)


PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 262144, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "KeyeVL2",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4,
    "num_local_experts": 128, "rms_norm_eps": 1e-06,
    "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                     "type": "default"},
    "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                  "q_chunk_size": 512, "topk": 2048},
    "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def test_the_configuration_is_the_published_one_cut_in_depth_only():
    """Every key of the catalog's entry under its own name; what differs is
    the depth. No width is touched, the vocabulary and all 128 experts are
    whole, ``sa_config`` and ``rope_scaling`` are copied whole."""
    differs = {k for k, v in PUBLISHED.items() if HP.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"} == set(HP["reduced"])
    assert HP["num_hidden_layers"] in (5, 6)
    assert "pipeline" in HP["stands_for"] and "layout" in HP
    assert HP["program"]["dtype"] == "bfloat16"
    said = " ".join(HP["assumed"])
    for what in ("RMSNorm over each head", "normed input", "LayerNorm",
                 "Hadamard", "FP8", "TOKEN", "multi-token-prediction",
                 "ties", "VISION TOWER"):
        assert what in said, what
    # a layer 625.4M parameters; with embedding and head 2 x 311.2M
    layer = (window_work.layer_dense_bytes(HP) + indexed_work.indexer_bytes(HP)
             + 128 * window_work.expert_bytes(HP)) // 2
    assert layer == 625_381_504
    total = HP["num_hidden_layers"] * layer + 2 * 151936 * 2048 + 2048
    assert total == {5: 3_749_239_424, 6: 4_374_620_928}[
        HP["num_hidden_layers"]]
    fam = manifest_lib.read_json_from_bench("families", "KeyeVL2")
    assert fam["preset"] == "keye_debug" and fam["reference"] == "keye"
    assert fam["keys"]["sa_config"] == "sa_config"
    assert fam["keys"]["rope_scaling"] == "rope_scaling"
    assert fam["constants"]["head_qk_norm"] is True


def test_the_cell_and_its_traffic_are_what_the_issue_names():
    cell = manifest_lib.read_json(BENCH, "cells", CELL)
    dep = cell["deployment"]
    assert dep == {"slots": 8, "prefill_chunk": 512, "arena_len": 49664,
                   "page_tokens": 16, "kv_pages": 8 * 3104 + 1,
                   "prefix_cache": False}   # and no option was added
    topk = HP["sa_config"]["topk"]
    assert cell["check_prompt_tokens"] == 8704 > 4 * topk
    assert cell["check_new_tokens"] == 32
    tol = cell["check_tolerance"]
    assert set(tol) == {"logit_err", "logit_rms_err", "served_margin",
                        "given_logit_err", "given_logit_rms_err"}
    # between the sound runs' 0.06-0.08 and the float8 control's 0.36-0.41
    assert 0.1 < min(tol["given_logit_err"], tol["given_logit_rms_err"])
    assert max(tol["given_logit_err"], tol["given_logit_rms_err"]) < 0.25
    assert "float8" in cell["check_tolerance_why"]
    sala = manifest_lib.read_json(BENCH, "cells", "minicpm_sala_longdoc")
    for key in ("warmup_s", "trace_s", "grace_s"):
        assert cell[key] == sala[key]
    mix = manifest_lib.read_json(BENCH, "traffic", MIX)
    assert mix["arrival"] == {"mode": "closed", "clients": 10}
    assert mix["prompt_tokens"] == {"min": 16384, "max": 49152}
    assert mix["output_tokens"] == {"min": 128, "max": 512}
    # the order is this mix's own (7, not the other mixes' 23): under 23 the
    # first requests' race for the slots decided gap_p95_ms (order_why)
    assert (mix["block"], mix["shuffle"], mix["order_seed"]) == (32, 8, 7)
    assert "race" in mix["order_why"]
    assert mix["prompt_tokens"]["min"] == 8 * topk
    assert (mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
            == dep["arena_len"])
    why = by_name(BENCH["workloads"])[CELL]["why"]
    assert "16384-49152" in why and "128-512" in why


# ------------------------------------------------------------ the arithmetic


def test_the_work_of_the_kind_by_hand():
    # a (query, token) pair: 16 dot products of 64 values
    assert indexed_work.score_pair_flops(HP) == 2 * 16 * 64
    assert indexed_work.index_key_bytes(HP) == 128
    # a (query, attended token) pair: score and value, 32 heads of 128
    assert indexed_work.attended_pair_flops(HP) == 4 * 32 * 128
    assert window_work.kv_bytes_per_token(HP) == 2048
    assert indexed_work.indexer_bytes(HP) == 2 * (
        2048 * (1024 + 64 + 16) + 2 * 64)
    assert window_work.expert_bytes(HP) == 3 * 2048 * 768 * 2


SIZES = {"vocab_size": 151936, "num_layers": 5, "embed_dim": 2048,
         "num_heads": 32, "num_kv_heads": 4, "head_dim": 128,
         "mlp_dim": 768, "mlp": "moe", "max_seq_len": 262144}
L = HP["num_hidden_layers"]
# a window of 1000 turns, every one a chunk of 500 real tokens at a context
# of 30000 with 6 live rows at 25000 along
COUNTERS = {
    "decode_steps": 1000, "prefill_chunks": 1000, "fused_turns": 1000,
    "turns": 1000, "prefill_tokens": 500_000, "fused_step_rows": 6000,
    "indexed_tokens_scored": L * 1000 * (500 * 30000 + 6 * 25000),
    "indexed_tokens_context": L * 1000 * (500 * 30000 + 6 * 25000),
    "indexed_tokens_attended": L * 1000 * 506 * 2048,
    "indexed_step_tokens_context": L * 1000 * 6 * 25000,
    "indexed_step_tokens_attended": L * 1000 * 6 * 2048,
    "moe_layer_calls": 2 * L * 1000, "moe_experts_hit": L * 1000 * 160}
PROGRAMS = {"jit_paged_prefill_chunk": {"count": 50, "sum_s": 2.5,
                                        "median_s": 0.050}}
OPS = {"index_score [custom-call]": {"count": 500, "sum_s": 0.30},
       "indexed_select [custom-call]": {"count": 500, "sum_s": 0.25},
       "indexed_chunk_attention [custom-call]": {"count": 250, "sum_s": 0.9},
       "indexed_step_attention [custom-call]": {"count": 250, "sum_s": 0.05},
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
    score = share * L * 1000 * (6 * 25000 * (128 + 4) / 819e9
                                + 500 * 30000 * 2048 / 197e12)
    assert read("kernel.index_score_roofline", ctx) == pytest.approx(
        100 * score / 0.30)
    attend = share * L * 1000 * (6 * 2048 * 2048 / 819e9
                                 + 500 * 2048 * 16384 / 197e12)
    assert read("kernel.indexed_attn_roofline", ctx) == pytest.approx(
        100 * attend / 0.95)
    assert read("step.indexer_share", ctx) == pytest.approx(
        100 * 1.5 / 2.45)
    assert read("attn.indexed_share", ctx) == pytest.approx(
        100 * 506 * 2048 / (500 * 30000 + 6 * 25000))
    dense = window_work.layer_dense_bytes(HP) + indexed_work.indexer_bytes(HP)
    expert = window_work.expert_bytes(HP)
    moved = (L * (dense + 128 * expert) + window_work.head_bytes(HP)
             + L * 6 * (25000 * 128 + 2048 * 2048))
    flops = (506 * L * (dense + 8 * expert)
             + L * 500 * (30000 * 2048 + 2048 * 16384))
    least = max(moved / 819e9, flops / 197e12)
    assert least == moved / 819e9   # the experts' bytes bound the turn
    assert read("indexed.turn_roofline", ctx) == pytest.approx(
        100 * least / 0.050)
    for name in NEW:
        assert 0 < read(name, ctx) < 100


@pytest.mark.parametrize("metric", NEW)
def test_a_reader_that_finds_nothing_returns_nothing(metric):
    """Another model's program, or the parent's under these readers: no
    counters and no kernel of that name. Nothing, never 0, and nothing is
    raised."""
    other = {"decode_steps": 40, "prefill_chunks": 9, "tokens_generated": 7,
             "moe_layer_calls": 16, "moe_experts_hit": 90}
    if metric != "step.indexer_share":  # which reads the kernels alone
        assert read(metric, ctx_of(other)) is None
    assert read(metric, ctx_of(other, PROGRAMS,
                               {"fusion": OPS["fusion"]})) is None
    assert read(metric, ctx_of({}, None)) is None
    assert read(metric, {"counters": {}, "trace": None}) is None
    if metric.startswith(("kernel.", "step.")):  # counters, no such kernel
        assert read(metric, ctx_of(COUNTERS, PROGRAMS,
                                   {"fusion": OPS["fusion"]})) is None
    if metric != "attn.indexed_share":  # which needs no trace
        assert read(metric, ctx_of(COUNTERS, None)) is None
    # the counters present and nothing counted: still nothing, not 0
    zeros = {k: 0 for k in COUNTERS}
    assert read(metric, ctx_of(zeros, PROGRAMS,
                               {"fusion": OPS["fusion"]})) is None


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
    """What PR 45 was refused for: the parent's program runs the OLD cells
    under this PR's benchmark files and reports none of the new counters,
    so no old cell may be listed for a reader that needs them."""
    old = [w["name"] for w in without_this_pr(BENCH)["workloads"]]
    assert len(old) == 8
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
# the readers of this PR that find something on a CPU: the kernels are
# INTERPRETED there and leave no event of their names
TINY_NEW = ["attn.indexed_share", "indexed.turn_roofline"]


def tiny_manifest(tmp_path):
    """The committed tiny manifest plus a toy Keye, its cell, the expert
    model's balance reader and this PR's two readers that read no kernel."""
    with open(os.path.join(TINY_DIR, "BENCHMARK_turns.json")) as f:
        tiny = json.load(f)
    tiny["paths"] = [TINY_DIR]
    for config in tiny["configs"]:
        config["file"] = os.path.join(TINY_DIR, config["file"])
    tiny["configs"].append({
        "name": "tiny_keye", "source": "tests only",
        "file": os.path.join(TINY_DIR, "configs", "tiny_keye.json"),
        "reduced": [], "why": "a toy of Keye-VL-2.0's language model"})
    tiny["workloads"].append({
        "name": "tiny_longctx", "config": "tiny_keye",
        "traffic": "tiny_longctx", "chips": 1,
        "why": "the cell of a model with a learned indexer, at a toy size"})
    by_name(tiny["end_to_end"])["gap_p95_ms"]["workloads"].append(
        "tiny_longctx")
    for name in TINY_JOINED:
        by_name(tiny["per_layer"])[name]["workloads"].append("tiny_longctx")
    for name in ["moe.max_expert_load"] + TINY_NEW:
        tiny["per_layer"].append(dict(by_name(BENCH["per_layer"])[name],
                                      workloads=["tiny_longctx"]))
    path = tmp_path / "BENCHMARK_keye.json"
    path.write_text(json.dumps(tiny))
    return str(path)


def rehearse(manifest_path, trace, cache_dir, seed=2**31 + 51):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    env.pop("BENCH_RUN", None)
    script = (
        "import sys; sys.path.insert(0, {root!r}); "
        "from tests.perfbench import rehearse; "
        "sys.exit(rehearse.main({path!r}, 'rehearse_keye'))").format(
            root=ROOT, path=manifest_path)
    return subprocess.run(
        [sys.executable, "-c", script, "--workload", "tiny_longctx",
         "--seed", str(seed), "--seconds", "2", "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("trace", [0, 1], ids=["end_to_end", "traced"])
def test_rehearsal_of_the_cell_with_an_indexer(tmp_path, trace):
    """The toy model through ``serve.run``, the scheduler and the two paged
    programs, checked against ``reference/keye.py`` by the harness on a
    prompt past ``topk`` — without choices, and GIVEN the routes (the first
    keyword of the reference the program can fill); the kind's counters in
    the run's ``delta`` note; in the traced run a line with the joined
    readers and the two of this PR that find something on a CPU."""
    path = tiny_manifest(tmp_path)
    proc = rehearse(path, trace, tmp_path / "cache")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = contract.last_line(proc.stdout)
    assert contract.check_line(last, manifest_lib.load(path),
                               "tiny_longctx", bool(trace)) == []
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
    assert 0 < delta["indexed_tokens_attended"] < delta[
        "indexed_tokens_context"] == delta["indexed_tokens_scored"]
    assert 0 < delta["indexed_step_tokens_attended"] < delta[
        "indexed_tokens_attended"]
    assert delta["moe_rows_routed"] > 0 and delta.get(
        "prefix_hit_tokens", 0) == 0
    if trace:
        value = {n: line["metrics"][n]["value"] for n in TINY_NEW}
        assert 0 < value["attn.indexed_share"] < 100
        assert value["indexed.turn_roofline"] > 0
        assert line["metrics"]["moe.max_expert_load"]["value"] >= 100
    else:
        assert set(line["metrics"]) == {"gap_p95_ms", "setup_s"}
    assert "left_running" in proc.stdout
