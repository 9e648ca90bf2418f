"""The Mellum2-12B-A2.5B configuration and its cell ``mellum2_shortlong``
(ISSUE 46): what ``BENCHMARK.json`` lists for them, held by NAME and as a
PREFIX (``pr46_entries``: never ``[-1]``, a total or a whole ``workloads``
list, so the next PR can add behind them); the arithmetic of
``perfbench/lib/window_work.py`` against counts by hand; the four readers on
hand-made ``ctx``s; and a CPU rehearsal of the cell at a toy size in both
kinds of run, over a manifest BUILT here from the committed tiny one plus
this PR's entries. Counts and structure only: no number here is a device
number.

This PR is no ``benchmark`` PR, so its hold lives in this file, which it
adds: ``tests/perfbench/held.py`` is a file the benchmark already has. A
later ``benchmark`` PR moves ``pr46_entries`` into ``held.CHECKS``.
"""

import copy
import json
import os
import subprocess
import sys

import pytest

from perfbench.lib import contract, window_work
from perfbench.lib import manifest as manifest_lib
from tests.perfbench import held
from tests.perfbench.test_perfbench_additions import add_a_prs_entries

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY_DIR = os.path.join(HERE, "tiny")
BENCH = manifest_lib.load()
CONFIG, CELL, MIX = "mellum2_12b_l8", "mellum2_shortlong", "shortlong"
HP = manifest_lib.config(BENCH, CONFIG)
NEW = ["kernel.window_attn_roofline", "kernel.global_attn_roofline",
       "window.decode_step_roofline", "paging.window_held_share"]
ROW = {  # unit, better, source, layer
    "kernel.window_attn_roofline": ("%", "higher", "device_trace", "kernels"),
    "kernel.global_attn_roofline": ("%", "higher", "device_trace", "kernels"),
    "window.decode_step_roofline": ("%", "higher", "device_trace",
                                    "jitted step"),
    "paging.window_held_share": ("%", "lower", "program_counter", "paging")}
# the lists the cell joined, each behind the cell that was its last
JOINED = {
    "client.tokens_per_s": "brumby_longgen",
    "client.ttft_p50_ms.gap": "brumby_longgen",
    "client.ttft_p95_ms.gap": "brumby_longgen",
    "sched.occupancy.gap": "brumby_longgen",
    "sched.prefix_hit_share.gap": "brumby_longgen",
    "paging.peak_pages_in_use.gap": "minicpm_sala_longdoc",
    "device.idle_share.gap": "brumby_longgen",
    "step.prefill_share.gap": "brumby_longgen",
    "sched.queue_wait_ms.gap": "brumby_longgen",
    "sched.host_share.gap": "brumby_longgen",
    "sched.stall_share.gap": "brumby_longgen",
    "replica.stream_lag_ms.gap": "brumby_longgen",
    "moe.max_expert_load": "olmoe_reason",
    "sched.prefill_turn_ms.gap": "brumby_longgen",
    "sched.prefill_turn_share.gap": "brumby_longgen",
    "sched.fused_turn_share.gap": "brumby_longgen",
    "step.turn_ms.gap": "brumby_longgen",
    # the two that read a PLAIN step: one turn in ten of this traffic
    # carries no chunk, and every traced window read held 45-46 of them
    "step.decode_ms.gap": "olmoe_reason",
    "sched.decode_turn_ms.gap": "brumby_longgen"}
# readers whose arithmetic is another model's (all layers' K/V a token, an
# expert of ``intermediate_size``, a head of hidden / heads; other mixers)
NOT_JOINED = ["kernel.paged_attn_roofline", "moe.decode_step_roofline",
              "step.mixer_share", "kernel.linear_attn_roofline",
              "kernel.sparse_attn_roofline", "attn.selected_share",
              "kernel.retention_step_roofline",
              "kernel.retention_chunk_roofline",
              "retention.decode_step_roofline", "step.retention_share"]
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def by_name(rows):
    return {r["name"]: r for r in rows}


def pr46_entries(manifest):
    """This PR's entries as it wrote them, found by name; whatever a later
    PR put behind them is free."""
    config = by_name(manifest["configs"])[CONFIG]
    assert config["file"] == f"perfbench/configs/{CONFIG}.json"
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "mlp_layer_types"]
    assert "JetBrains/Mellum2-12B-A2.5B-Instruct" in config["source"]
    cell = by_name(manifest["workloads"])[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    names = [w["name"] for w in manifest["workloads"]]
    assert names.index(CELL) > names.index("brumby_longgen")
    gap = by_name(manifest["end_to_end"])["gap_p95_ms"]["workloads"]
    assert gap[gap.index(CELL) - 1] == "brumby_longgen"
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
    assert at > order.index("step.retention_share")  # behind what PR 43 left
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
    pr46_entries(BENCH)
    for check in held.CHECKS + held.FOUND:   # every earlier PR's hold
        check(BENCH)
    parent = without_this_pr(BENCH)
    assert CELL not in json.dumps(parent) and CONFIG not in json.dumps(parent)
    held.only_added(parent, BENCH)
    held.static_rules(BENCH)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(BENCH["workloads"]) >= 8


@pytest.mark.parametrize("tags", [("later",), ("later", "and_later")],
                         ids=["one_pr_behind_it", "two_prs_behind_it"])
def test_its_hold_accepts_what_later_prs_add(tags):
    later = copy.deepcopy({k: v for k, v in BENCH.items() if k != "_dir"})
    for tag in tags:
        add_a_prs_entries(later, tag)
    pr46_entries(later)
    held.only_added(BENCH, later)
    held.static_rules(later)
    # and what this PR found is still found under them (PR 43's own test of
    # this kind compares against its entries taken out of the MIDDLE, and is
    # red since this PR's entries stand behind them: PERF.md 7)
    held.only_added(without_this_pr(later), later)


def row_of(manifest, name):
    return by_name(manifest["per_layer"])[name]


@pytest.mark.parametrize("edit", [
    lambda m: m["workloads"].insert(0, m["workloads"].pop(
        [w["name"] for w in m["workloads"]].index(CELL))),
    lambda m: m["per_layer"].remove(row_of(m, NEW[2])),
    lambda m: row_of(m, "kernel.paged_attn_roofline")["workloads"].append(
        CELL),
    lambda m: row_of(m, "moe.decode_step_roofline")["workloads"].append(CELL),
    lambda m: by_name(m["end_to_end"])["gap_p95_ms"]["workloads"].remove(
        CELL),
    lambda m: by_name(m["configs"])[CONFIG]["reduced"].append("head_dim"),
    lambda m: row_of(m, NEW[0])["workloads"].insert(0, "olmoe_reason"),
    lambda m: row_of(m, NEW[3])["workloads"].append("mistral7b_chat"),
    lambda m: by_name(m["workloads"])[CELL].update(chips=4),
], ids=["the_cell_moved_to_the_front", "a_reader_taken_away",
        "the_cell_in_the_paged_kernels_list",
        "the_cell_on_the_other_expert_models_list",
        "the_cell_out_of_gap_p95_ms", "a_width_listed_as_reduced",
        "another_cell_before_it_in_its_metric",
        "a_cell_the_benchmark_had_on_a_new_metric",
        "four_chips_for_one_chips_work"])
def test_its_hold_refuses_an_edit_of_its_entries(edit):
    edited = copy.deepcopy({k: v for k, v in BENCH.items() if k != "_dir"})
    edit(edited)
    with pytest.raises((AssertionError, KeyError, ValueError)):
        pr46_entries(edited)


PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "max_position_embeddings": 131072, "max_window_layers": 0,
    "model_type": "mellum", "moe_intermediate_size": 896,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 28,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"] * 7,
    "mlp_layer_types": ["sparse"] * 28}


def test_the_configuration_is_the_published_one_cut_in_depth_only():
    """Every number of the catalog's entry under its own key; what differs
    is the depth and, with it, the two lists a layer. No width is touched,
    the vocabulary is whole, ``rope_parameters`` is copied whole."""
    differs = {k for k, v in PUBLISHED.items() if HP.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "layer_types", "mlp_layer_types"}
    assert HP["num_hidden_layers"] == 8
    assert HP["layer_types"] == PUBLISHED["layer_types"][:8]
    assert HP["mlp_layer_types"] == ["sparse"] * 8
    assert set(HP["reduced"]) == differs
    assert len(HP["assumed"]) >= 4 and "MTP" not in HP["stands_for"]
    assert any("multi-token-prediction" in a for a in HP["assumed"])
    assert "stands_for" in HP and HP["program"]["dtype"] == "bfloat16"
    # 3.795B parameters, 7.59 GB of bf16 (ISSUE 46's arithmetic)
    assert window_work.model_bytes(HP) == 7_589_933_568
    fam = manifest_lib.read_json_from_bench("families", "mellum")
    assert fam["preset"] == "mellum_debug" and fam["reference"] == "mellum"
    assert fam["keys"]["layer_types"] == "layer_kinds"
    assert fam["keys"]["head_dim"] == "head_dim"
    assert fam["keys"]["moe_intermediate_size"] == "mlp_dim"


def test_the_cell_and_its_traffic_are_what_the_issue_names():
    cell = manifest_lib.read_json(BENCH, "cells", CELL)
    dep = cell["deployment"]
    assert dep["prefill_chunk"] == 512 and dep["page_tokens"] == 16
    assert dep["arena_len"] == 33792 and dep["prefix_cache"] is False
    # every slot's worst case in the full layers, and the garbage page
    assert dep["kv_pages"] == dep["slots"] * dep["arena_len"] // 16 + 1
    assert dep["slots"] in (32, 24)
    assert set(dep) == {"slots", "prefill_chunk", "arena_len", "page_tokens",
                        "kv_pages", "prefix_cache"}   # no option was added
    assert cell["max_ongoing_requests"] == 256
    assert cell["check_prompt_tokens"] == 4352 > HP["sliding_window"] + 6 * 512
    # 32 checked positions: one flipped position no longer carries the
    # root mean square (REVIEW 46); and the reference GIVEN the routes
    assert cell["check_prompt_tokens"] % 512 and cell["check_new_tokens"] == 32
    tol = cell["check_tolerance"]
    assert set(tol) == {"logit_err", "logit_rms_err", "served_margin",
                        "given_logit_err", "given_logit_rms_err"}
    assert max(tol["given_logit_err"], tol["given_logit_rms_err"]) < 0.1
    assert "float8" in cell["check_tolerance_why"]
    mix = manifest_lib.read_json(BENCH, "traffic", MIX)
    assert mix["arrival"]["mode"] == "closed"
    assert mix["arrival"]["clients"] == dep["slots"] * 5 // 4
    assert mix["prompt_tokens"] == {"min": 256, "max": 32768}
    assert mix["output_tokens"] == {"min": 128, "max": 1024}
    assert (mix["block"], mix["shuffle"], mix["order_seed"]) == (32, 8, 23)
    assert (mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
            <= dep["arena_len"])
    why = by_name(BENCH["workloads"])[CELL]["why"]
    assert "256-32768" in why and "128-1024" in why


# ------------------------------------------------------------ the arithmetic


def test_the_work_of_the_two_kinds_by_hand():
    assert window_work.layers_of(HP, "window") == 6
    assert window_work.layers_of(HP, "full") == 2
    # K/V of a token and layer: 2 x 4 heads x 128 x bf16 = 2 KB
    assert window_work.kv_bytes_per_token(HP) == 2048
    # a pair: score and value, 2 x 128 each, 32 query heads
    assert window_work.pair_flops(HP) == 4 * 32 * 128
    assert window_work.expert_bytes(HP) == 3 * 2304 * 896 * 2 == 12_386_304
    # q 2304 x 4096, k and v 2304 x 512, o 4096 x 2304, router 2304 x 64,
    # two norms
    assert window_work.layer_dense_bytes(HP) == 2 * (
        2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304 + 2304 * 64 + 2 * 2304)
    assert window_work.head_bytes(HP) == 2 * (2304 * 98304 + 2304)


SIZES = {"vocab_size": 98304, "num_layers": 8, "embed_dim": 2304,
         "num_heads": 32, "num_kv_heads": 4, "head_dim": 128,
         "mlp_dim": 896, "mlp": "moe", "max_seq_len": 131072}
# a window of 1000 turns, every one a chunk of 500 real tokens with 30 live
# rows along: contexts of 6000 in the full layers, 1024 in the window layers
COUNTERS = {
    "decode_steps": 1000, "prefill_chunks": 1000, "fused_turns": 1000,
    "turns": 1000,
    "full_attn_step_keys": 2 * 1000 * 30 * 6000,
    "window_attn_step_keys": 6 * 1000 * 30 * 1024,
    "full_attn_chunk_pairs": 2 * 1000 * 500 * 6000,
    "window_attn_chunk_pairs": 6 * 1000 * 500 * 1024,
    "moe_layer_calls": 16000, "moe_experts_hit": 16000 * 60,
    "window_tokens_held": 1000 * 32 * 1400,
    "window_tokens_unreleased": 1000 * 32 * 7000}
PROGRAMS = {"jit_paged_prefill_chunk": {"count": 50, "sum_s": 2.0,
                                        "median_s": 0.040}}
OPS = {"window_attention [custom-call]": {"count": 600, "sum_s": 0.20},
       "paged_attention [custom-call]": {"count": 200, "sum_s": 0.25},
       "fusion": {"count": 9000, "sum_s": 1.5}}


def ctx_of(delta, programs=PROGRAMS, ops=OPS):
    trace = (None if programs is None
             else {"programs": programs, "ops": ops, "busy_s": 2.9})
    return {"counters": {"delta": delta, "end": delta}, "trace": trace,
            "config": HP, "sizes": SIZES, "device": V5E,
            "cell": manifest_lib.read_json(BENCH, "cells", CELL)}


def read(metric, ctx):
    return manifest_lib.metric_reader(metric)(ctx)


def test_the_readers_on_a_hand_made_window():
    ctx = ctx_of(COUNTERS)
    # a twentieth of the window's turns are in the trace, all of them fused
    share = 50 / 1000
    window = share * (6 * 1000 * 30 * 1024 * 2048 / 819e9
                      + 6 * 1000 * 500 * 1024 * 16384 / 197e12)
    assert read("kernel.window_attn_roofline", ctx) == pytest.approx(
        100 * window / 0.20)
    full = share * (2 * 1000 * 30 * 6000 * 2048 / 819e9
                    + 2 * 1000 * 500 * 6000 * 16384 / 197e12)
    assert read("kernel.global_attn_roofline", ctx) == pytest.approx(
        100 * full / 0.25)
    keys = 2 * 30 * 6000 + 6 * 30 * 1024
    least = (8 * (window_work.layer_dense_bytes(HP) + 60 * 12_386_304)
             + window_work.head_bytes(HP) + keys * 2048) / 819e9
    # no plain step in the trace: over the chunk program's median
    assert read("window.decode_step_roofline", ctx) == pytest.approx(
        100 * least / 0.040)
    assert read("paging.window_held_share", ctx) == pytest.approx(20.0)
    for name in NEW:
        assert 0 < read(name, ctx) < 100
    # with a plain step in the trace, over ITS median
    mixed = dict(PROGRAMS, jit_paged_decode_step={
        "count": 5, "sum_s": 0.1, "median_s": 0.02})
    assert read("window.decode_step_roofline",
                ctx_of(COUNTERS, mixed)) == pytest.approx(100 * least / 0.02)


@pytest.mark.parametrize("metric", NEW)
def test_a_reader_that_finds_nothing_returns_nothing(metric):
    """Another model's program, or the parent's under these readers: no
    counters and no kernel of that name. Nothing, never 0, and nothing is
    raised."""
    other = {"decode_steps": 40, "prefill_chunks": 9, "tokens_generated": 7,
             "moe_layer_calls": 16, "moe_experts_hit": 90}
    assert read(metric, ctx_of(other)) is None
    assert read(metric, ctx_of(other, PROGRAMS,
                               {"fusion": OPS["fusion"]})) is None
    assert read(metric, ctx_of({}, None)) is None
    if metric.startswith("kernel."):   # the counters, and no such kernel
        assert read(metric, ctx_of(COUNTERS, PROGRAMS,
                                   {"fusion": OPS["fusion"]})) is None
    if metric != "paging.window_held_share":  # which needs no trace
        assert read(metric, ctx_of(COUNTERS, None)) is None
    # the counters present and nothing counted: still nothing, not 0
    zeros = {k: 0 for k in COUNTERS}
    assert read(metric, ctx_of(zeros)) is None


def test_a_line_of_the_cell_is_accepted_with_its_metrics_and_not_without():
    for traced in (False, True):
        mine = manifest_lib.metrics_for(BENCH, CELL, traced)
        names = {m["name"] for m in mine}
        assert (set(NEW) | set(JOINED)) <= names if traced else (
            names == {"gap_p95_ms", "setup_s"})
        assert not names & set(NOT_JOINED)
        device = dict(V5E, memory_peak_bytes=14_000_000_000)
        if traced:
            device.update(window_s=3.0, busy_s=2.9)
        line = contract.build_line(
            correct=True, attempted=150, failed=0, device=device,
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
# the readers of this PR that find something on a CPU: the two kernels'
# device time has no events there (the reference lane serves off a TPU)
TINY_NEW = ["window.decode_step_roofline", "paging.window_held_share"]


def tiny_manifest(tmp_path):
    """The committed tiny manifest plus a toy Mellum, its cell, the expert
    model's balance reader and this PR's two readers that read no kernel."""
    with open(os.path.join(TINY_DIR, "BENCHMARK_turns.json")) as f:
        tiny = json.load(f)
    tiny["paths"] = [TINY_DIR]
    for config in tiny["configs"]:
        config["file"] = os.path.join(TINY_DIR, config["file"])
    tiny["configs"].append({
        "name": "tiny_mellum", "source": "tests only",
        "file": os.path.join(TINY_DIR, "configs", "tiny_mellum.json"),
        "reduced": [], "why": "a toy of Mellum-2's shape"})
    tiny["workloads"].append({
        "name": "tiny_shortlong", "config": "tiny_mellum",
        "traffic": "tiny_shortlong", "chips": 1,
        "why": "the cell of a model with a page pool a kind, at a toy size"})
    by_name(tiny["end_to_end"])["gap_p95_ms"]["workloads"].append(
        "tiny_shortlong")
    for name in TINY_JOINED:
        by_name(tiny["per_layer"])[name]["workloads"].append("tiny_shortlong")
    for name in ["moe.max_expert_load"] + TINY_NEW:
        tiny["per_layer"].append(dict(by_name(BENCH["per_layer"])[name],
                                      workloads=["tiny_shortlong"]))
    path = tmp_path / "BENCHMARK_mellum.json"
    path.write_text(json.dumps(tiny))
    return str(path)


def rehearse(manifest_path, trace, cache_dir, seed=2**31 + 46):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    env.pop("BENCH_RUN", None)
    script = (
        "import sys; sys.path.insert(0, {root!r}); "
        "from tests.perfbench import rehearse; "
        "sys.exit(rehearse.main({path!r}, 'rehearse_mellum'))").format(
            root=ROOT, path=manifest_path)
    return subprocess.run(
        [sys.executable, "-c", script, "--workload", "tiny_shortlong",
         "--seed", str(seed), "--seconds", "2", "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=400)


@pytest.mark.parametrize("trace", [0, 1], ids=["end_to_end", "traced"])
def test_rehearsal_of_the_cell_with_a_pool_a_kind(tmp_path, trace):
    """The toy model through ``serve.run``, the scheduler and the two paged
    programs with both pools, checked against ``reference/mellum.py`` by the
    harness on a prompt past the window; the window's counters in the run's
    ``delta`` note; in the traced run a line with the joined readers and
    the two of this PR that find something on a CPU."""
    path = tiny_manifest(tmp_path)
    proc = rehearse(path, trace, tmp_path / "cache")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = contract.last_line(proc.stdout)
    assert contract.check_line(last, manifest_lib.load(path),
                               "tiny_shortlong", bool(trace)) == []
    line = json.loads(last)
    assert line["correct"] is True and line["failed"] == 0
    checks = [json.loads(ln) for ln in proc.stdout.splitlines()
              if ln.startswith('{"note": "checks"')][0]
    delta = checks["delta"]
    assert checks["reference_check"]["logit_err"] < 1e-4
    assert checks["reference_check"]["prompt_tokens"] == 77
    # the toy cell states limits GIVEN the routes, as the cell does: the
    # second pass runs the uncached forward's window layers too
    assert checks["reference_check"]["given_choices"] == "routes"
    assert checks["checks"]["reference_logits_given_choices"] is True
    assert 0 < line["compared"]["given_logit_err"]["value"] < 1e-4
    assert checks["scheduler"]["compiled_programs"] == 2
    assert delta["window_pages_released"] > 0
    assert 0 < delta["window_tokens_held"] < delta["window_tokens_unreleased"]
    assert 0 < delta["window_attn_step_keys"]
    assert delta["window_attn_chunk_pairs"] < 3 * delta[
        "full_attn_chunk_pairs"]
    assert delta["moe_rows_routed"] > 0 and delta.get(
        "prefix_hit_tokens", 0) == 0
    if trace:
        value = {n: line["metrics"][n]["value"] for n in TINY_NEW}
        assert value["window.decode_step_roofline"] > 0
        assert 0 < value["paging.window_held_share"] < 100
        assert line["metrics"]["moe.max_expert_load"]["value"] >= 100
    else:
        assert set(line["metrics"]) == {"gap_p95_ms", "setup_s"}
    assert "left_running" in proc.stdout
