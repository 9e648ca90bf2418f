"""The Brumby-14B configuration and its cell ``brumby_longgen`` (ISSUE 43):
what ``BENCHMARK.json`` lists for them, held by NAME and as a PREFIX
(``pr43_entries``: never ``[-1]``, a total or a whole ``workloads`` list, so
the next PR can add behind them); the arithmetic of
``perfbench/lib/retention_work.py`` against a count by hand; the four readers
on hand-made ``ctx``s; and one CPU rehearsal of the cell at a toy size, over
a manifest BUILT here from the committed tiny one plus this PR's entries (no
copied manifest). Counts and structure only: no number here is a device
number.

This PR is no ``benchmark`` PR, so its hold lives in this file, which it
adds: ``tests/perfbench/held.py`` is a file the benchmark already has. A
later ``benchmark`` PR moves ``pr43_entries`` into ``held.CHECKS``.
"""

import copy
import json
import os
import subprocess
import sys

import pytest

from perfbench.lib import contract, retention_work
from perfbench.lib import manifest as manifest_lib
from tests.perfbench import held
from tests.perfbench.test_perfbench_additions import add_a_prs_entries

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY_DIR = os.path.join(HERE, "tiny")
BENCH = manifest_lib.load()
HP = manifest_lib.config(BENCH, "brumby_14b_l8")
CONFIG, CELL, MIX = "brumby_14b_l8", "brumby_longgen", "longgen"
NEW = ["kernel.retention_step_roofline", "kernel.retention_chunk_roofline",
       "retention.decode_step_roofline", "step.retention_share"]
LAYER = {"kernel.retention_step_roofline": "kernels",
         "kernel.retention_chunk_roofline": "kernels",
         "retention.decode_step_roofline": "jitted step",
         "step.retention_share": "jitted step"}
# the lists the cell joined, each behind the cell that was its last
JOINED = {
    "client.tokens_per_s": "minicpm_sala_longdoc",
    "client.ttft_p50_ms.gap": "minicpm_sala_longdoc",
    "client.ttft_p95_ms.gap": "minicpm_sala_longdoc",
    "sched.occupancy.gap": "minicpm_sala_longdoc",
    "sched.prefix_hit_share.gap": "minicpm_sala_longdoc",
    "device.idle_share.gap": "minicpm_sala_longdoc",
    "step.prefill_share.gap": "minicpm_sala_longdoc",
    "sched.queue_wait_ms.gap": "minicpm_sala_longdoc",
    "sched.host_share.gap": "minicpm_sala_longdoc",
    "sched.stall_share.gap": "minicpm_sala_longdoc",
    "replica.stream_lag_ms.gap": "minicpm_sala_longdoc",
    "sched.decode_turn_ms.gap": "olmoe_reason",
    "sched.prefill_turn_ms.gap": "minicpm_sala_longdoc",
    "sched.prefill_turn_share.gap": "minicpm_sala_longdoc",
    "sched.fused_turn_share.gap": "minicpm_sala_longdoc",
    "step.turn_ms.gap": "minicpm_sala_longdoc"}
# pages, the paged kernel, another model's mixers and experts; and the plain
# step's median by name, which the PR that fuses this turn could not leave
NOT_JOINED = ["paging.peak_pages_in_use.gap", "kernel.paged_attn_roofline",
              "step.decode_ms.gap", "step.mixer_share",
              "kernel.linear_attn_roofline", "kernel.sparse_attn_roofline",
              "attn.selected_share", "moe.decode_step_roofline",
              "moe.max_expert_load"]
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def by_name(rows):
    return {r["name"]: r for r in rows}


def pr43_entries(manifest):
    """This PR's entries as it wrote them, found by name; whatever a later
    PR put behind them is free."""
    config = by_name(manifest["configs"])[CONFIG]
    assert config["file"] == f"perfbench/configs/{CONFIG}.json"
    assert config["reduced"] == ["num_hidden_layers"]
    assert "manifestai/Brumby-14B-Base" in config["source"]
    cell = by_name(manifest["workloads"])[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    names = [w["name"] for w in manifest["workloads"]]
    assert names.index(CELL) > names.index("minicpm_sala_longdoc")
    gap = by_name(manifest["end_to_end"])["gap_p95_ms"]["workloads"]
    assert gap[gap.index(CELL) - 1] == "minicpm_sala_longdoc"
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
    assert at > order.index("step.turn_ms.gap")  # behind what PR 41 left
    for name in NEW:
        row = rows[name]
        assert row["workloads"][:1] == [CELL]
        assert (row["unit"], row["better"], row["moves"], row["source"],
                row["layer"]) == ("%", "higher", "gap_p95_ms",
                                  "device_trace", LAYER[name])


def without_this_pr(manifest):
    """The manifest this PR found: its entries taken out again."""
    out = copy.deepcopy({k: v for k, v in manifest.items() if k != "_dir"})
    out["configs"] = [c for c in out["configs"] if c["name"] != CONFIG]
    out["workloads"] = [w for w in out["workloads"] if w["name"] != CELL]
    out["per_layer"] = [m for m in out["per_layer"] if m["name"] not in NEW]
    for m in out["end_to_end"] + out["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].remove(CELL)
    return out


# ------------------------------------------------------ the manifest's part


def test_this_pr_added_by_adding_and_holds_its_own_entries():
    pr43_entries(BENCH)
    for check in held.CHECKS + held.FOUND:   # every earlier PR's hold
        check(BENCH)
    parent = without_this_pr(BENCH)
    assert CELL not in json.dumps(parent) and CONFIG not in json.dumps(parent)
    held.only_added(parent, BENCH)
    held.static_rules(BENCH)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(BENCH["workloads"]) >= 7


@pytest.mark.parametrize("tags", [("later",), ("later", "and_later")],
                         ids=["one_pr_behind_it", "two_prs_behind_it"])
def test_its_hold_accepts_what_later_prs_add(tags):
    later = copy.deepcopy({k: v for k, v in BENCH.items() if k != "_dir"})
    for tag in tags:
        add_a_prs_entries(later, tag)
    pr43_entries(later)
    held.only_added(BENCH, later)
    held.static_rules(later)


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
    lambda m: by_name(m["configs"])[CONFIG]["reduced"].append("hidden_size"),
    lambda m: row_of(m, NEW[0])["workloads"].insert(0, "mistral7b_chat"),
    lambda m: by_name(m["workloads"])[CELL].update(chips=4),
], ids=["the_cell_moved_to_the_front", "a_reader_taken_away",
        "the_cell_in_the_paged_kernels_list",
        "the_cell_on_the_plain_steps_list", "the_cell_out_of_gap_p95_ms",
        "a_width_listed_as_reduced", "another_cell_before_it_in_its_metric",
        "four_chips_for_one_chips_work"])
def test_its_hold_refuses_an_edit_of_its_entries(edit):
    edited = copy.deepcopy({k: v for k, v in BENCH.items() if k != "_dir"})
    edit(edited)
    with pytest.raises((AssertionError, KeyError, ValueError)):
        pr43_entries(edited)


def test_the_configuration_is_the_published_one_cut_in_depth_only():
    """Every number of the catalog's entry under its own key; what differs
    is the depth. No width is touched, the vocabulary is whole."""
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 5120, "intermediate_size": 17408,
        "max_position_embeddings": 32768, "max_window_layers": 40,
        "model_type": "brumby", "num_attention_heads": 40,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    differs = {k for k, v in published.items() if HP.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"} and HP["num_hidden_layers"] == 8
    assert set(HP["reduced"]) == {"num_hidden_layers"}
    assert HP["published_num_hidden_layers"] == 40
    assert HP["retention_degree"] == 2 and len(HP["assumed"]) >= 6
    assert "stands_for" in HP and HP["program"]["dtype"] == "bfloat16"
    cell = manifest_lib.read_json(BENCH, "cells", CELL)
    dep = cell["deployment"]
    assert dep == {"slots": 16, "prefill_chunk": 512, "arena_len": 17408,
                   "prefix_cache": False}      # no page size, no pool
    mix = manifest_lib.read_json(BENCH, "traffic", MIX)
    assert mix["arrival"] == {"mode": "closed", "clients": 18}
    assert (mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
            <= dep["arena_len"])
    # every prompt past the length at which a state is smaller than the
    # bf16 keys and values it replaces
    kv_token = 2 * 8 * 128 * 2
    assert mix["prompt_tokens"]["min"] > retention_work.state_bytes(HP) \
        / kv_token
    assert cell["check_prompt_tokens"] >= mix["prompt_tokens"]["min"]
    tol = cell["check_tolerance"]
    assert set(tol) == {"logit_err", "logit_rms_err", "served_margin"}
    assert "float8" in cell["check_tolerance_why"]
    fam = manifest_lib.read_json_from_bench("families", "brumby")
    assert fam["preset"] == "brumby_debug" and fam["reference"] == "brumby"


# ------------------------------------------------------------ the arithmetic


def test_the_work_of_the_mixer_by_hand():
    assert retention_work.half_rows(HP) == 8256
    # 8 K/V heads x (8256 x 128 + 8256) float32: 34.08 MB a row and layer
    assert retention_work.state_bytes(HP) == 8 * 8256 * 129 * 4 == 34080768
    # a token of a chunk, a layer: 48 heads' products with the state, and
    # the causal half of its block's scores and products for 40 heads
    assert retention_work.chunk_flops_per_token(HP) == (
        48 * 2 * 8256 * 128 + 40 * 2 * 128 * 128)
    assert 512 * retention_work.chunk_flops_per_token(HP) == pytest.approx(
        52.6e9, rel=0.01)


SIZES = {"vocab_size": 151936, "num_layers": 8, "embed_dim": 5120,
         "num_heads": 40, "num_kv_heads": 8, "head_dim": 128,
         "mlp_dim": 17408, "mlp": "swiglu", "max_seq_len": 32768}
# a window of 1000 steps over 14 live rows and 700 chunks of 500 real tokens
COUNTERS = {"decode_steps": 1000, "prefill_chunks": 700, "turns": 1000,
            "retention_step_rows": 8 * 14000,
            "retention_chunk_calls": 8 * 700,
            "retention_chunk_tokens": 8 * 350000}
PROGRAMS = {"jit_paged_decode_step": {"count": 50, "sum_s": 1.3,
                                      "median_s": 0.026},
            "jit_paged_prefill_chunk": {"count": 35, "sum_s": 1.0,
                                        "median_s": 0.028}}
OPS = {"power_retention_step [custom-call]": {"count": 400, "sum_s": 0.7},
       "power_retention_chunk [custom-call]": {"count": 280, "sum_s": 0.4},
       "fusion": {"count": 9000, "sum_s": 1.5}}


def ctx_of(delta, programs=PROGRAMS, ops=OPS):
    trace = (None if programs is None
             else {"programs": programs, "ops": ops, "busy_s": 3.0})
    return {"counters": {"delta": delta, "end": delta}, "trace": trace,
            "config": HP, "sizes": SIZES, "device": V5E,
            "cell": manifest_lib.read_json(BENCH, "cells", CELL)}


def read(metric, ctx):
    return manifest_lib.metric_reader(metric)(ctx)


def test_the_readers_on_a_hand_made_window():
    ctx = ctx_of(COUNTERS)
    # a twentieth of the window's steps and of its chunks are in the trace
    states = 8 * 14000 / 20 * 2 * 34080768 / 819e9
    assert read("kernel.retention_step_roofline", ctx) == pytest.approx(
        100 * states / 0.7)
    chunk = (8 * 350000 * (48 * 2 * 8256 * 128 + 40 * 2 * 128 * 128) / 197e12
             + 8 * 700 * 2 * 34080768 / 819e9) / 20
    assert read("kernel.retention_chunk_roofline", ctx) == pytest.approx(
        100 * chunk / 0.4)
    # a layer: q, o 2 x 26.2M, k, v 2 x 5.2M, gate 0.04M, SwiGLU 267.4M;
    # the head 777.9M; bf16
    weights = 2 * (8 * (2 * 5120 * 40 * 128 + 2 * 5120 * 8 * 128 + 5120 * 8
                        + 3 * 5120 * 17408) + 5120 * 151936)
    assert retention_work.weight_bytes(ctx) == weights
    least = (weights + 14 * 8 * 2 * 34080768) / 819e9
    assert read("retention.decode_step_roofline", ctx) == pytest.approx(
        100 * least / 0.026)
    assert read("step.retention_share", ctx) == pytest.approx(100 * 1.1 / 3)
    for name in NEW:
        assert 0 < read(name, ctx) < 100


def test_a_fused_turn_keeps_the_steps_half():
    """Once the chunk's program carries the rows (ROADMAP S3.1) a window of
    chunk runs alone still reads the step kernel's share and the step's
    least time, over the program that ran them."""
    fused = dict(COUNTERS, fused_turns=700)
    only_chunks = {"jit_paged_prefill_chunk": PROGRAMS[
        "jit_paged_prefill_chunk"]}
    ctx = ctx_of(fused, only_chunks)
    assert read("kernel.retention_step_roofline", ctx) == pytest.approx(
        100 * (8 * 14000 * 35 / 1000 * 2 * 34080768 / 819e9) / 0.7)
    assert 0 < read("retention.decode_step_roofline", ctx) < 100
    # two programs a turn, and the trace held no plain step: nothing
    assert read("retention.decode_step_roofline",
                ctx_of(COUNTERS, only_chunks)) is None


@pytest.mark.parametrize("metric", NEW)
def test_a_reader_that_finds_nothing_returns_nothing(metric):
    """Another model's program, or the parent's under these readers: no
    counters and no kernels. Nothing, never 0, and nothing is raised."""
    other = {"decode_steps": 40, "prefill_chunks": 9, "tokens_generated": 7}
    if metric != "step.retention_share":  # which reads the trace alone
        assert read(metric, ctx_of(other)) is None
    if not metric.startswith("retention."):  # which reads the programs
        assert read(metric, ctx_of(COUNTERS, PROGRAMS,
                                   {"fusion": OPS["fusion"]})) is None
    assert read(metric, ctx_of(other, PROGRAMS, {"fusion": OPS["fusion"]})) \
        is None
    assert read(metric, ctx_of({}, None)) is None
    assert read(metric, ctx_of(COUNTERS, None)) is None


def test_a_line_of_the_cell_is_accepted_with_its_metrics_and_not_without():
    for traced in (False, True):
        mine = manifest_lib.metrics_for(BENCH, CELL, traced)
        names = {m["name"] for m in mine}
        assert (set(NEW) | set(JOINED)) <= names if traced else (
            names == {"gap_p95_ms", "setup_s"})
        assert not names & set(NOT_JOINED)
        device = dict(V5E, memory_peak_bytes=13_400_000_000)
        if traced:
            device.update(window_s=3.0, busy_s=2.99)
        line = contract.build_line(
            correct=True, attempted=27, failed=0, device=device,
            metrics={m["name"]: {"value": 12.5, "unit": m["unit"]}
                     for m in mine},
            breakdown={"device_ops": [], "idle_gaps": []} if traced else None)
        assert contract.check_line(line, BENCH, CELL, traced) == []
        line["metrics"].pop(NEW[0] if traced else "gap_p95_ms")
        assert contract.check_line(line, BENCH, CELL, traced)


# ---------------------------------------------------------------- rehearsal

TINY_JOINED = ["client.tokens_per_s", "client.ttft_p50_ms.gap",
               "sched.occupancy.gap", "sched.prefix_hit_share.gap",
               "device.idle_share.gap", "step.prefill_share.gap",
               "sched.prefill_turn_share.gap", "sched.fused_turn_share.gap",
               "step.turn_ms.gap"]


def tiny_manifest(tmp_path):
    """The committed tiny manifest plus a toy Brumby, its cell and the one
    retention reader that reads program names (the kernels are interpreted
    on the CPU, so the readers of their device time have nothing to read):
    written beside nothing, with the tiny tree named by its path."""
    with open(os.path.join(TINY_DIR, "BENCHMARK_turns.json")) as f:
        tiny = json.load(f)
    tiny["paths"] = [TINY_DIR]
    for config in tiny["configs"]:
        config["file"] = os.path.join(TINY_DIR, config["file"])
    tiny["configs"].append({
        "name": "tiny_brumby", "source": "tests only",
        "file": os.path.join(TINY_DIR, "configs", "tiny_brumby.json"),
        "reduced": [], "why": "a toy of Brumby's shape"})
    tiny["workloads"].append({
        "name": "tiny_longgen", "config": "tiny_brumby",
        "traffic": "tiny_longgen", "chips": 1,
        "why": "the cell of a model without pages, at a toy size"})
    by_name(tiny["end_to_end"])["gap_p95_ms"]["workloads"].append(
        "tiny_longgen")
    for name in TINY_JOINED:
        by_name(tiny["per_layer"])[name]["workloads"].append("tiny_longgen")
    tiny["per_layer"].append(dict(
        by_name(BENCH["per_layer"])["retention.decode_step_roofline"],
        workloads=["tiny_longgen"]))
    path = tmp_path / "BENCHMARK_brumby.json"
    path.write_text(json.dumps(tiny))
    return str(path)


def rehearse(manifest_path, trace, cache_dir, seed=2**31 + 43):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    env.pop("BENCH_RUN", None)
    script = (
        "import sys; sys.path.insert(0, {root!r}); "
        "from tests.perfbench import rehearse; "
        "sys.exit(rehearse.main({path!r}, 'rehearse_brumby'))").format(
            root=ROOT, path=manifest_path)
    return subprocess.run(
        [sys.executable, "-c", script, "--workload", "tiny_longgen",
         "--seed", str(seed), "--seconds", "2", "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=400)


def test_rehearsal_of_a_cell_without_pages(tmp_path):
    """The toy model through ``serve.run``, the scheduler and the two paged
    programs with no page anywhere, checked against
    ``reference/brumby.py`` by the harness; the mixer's counters in the
    run's ``delta`` note; a traced line with the joined readers and the one
    retention reader that finds something on a CPU."""
    path = tiny_manifest(tmp_path)
    proc = rehearse(path, 1, tmp_path / "cache")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = contract.last_line(proc.stdout)
    assert contract.check_line(last, manifest_lib.load(path), "tiny_longgen",
                               True) == []
    line = json.loads(last)
    assert line["correct"] is True and line["failed"] == 0
    checks = [json.loads(ln) for ln in proc.stdout.splitlines()
              if ln.startswith('{"note": "checks"')][0]
    delta = checks["delta"]
    assert checks["reference_check"]["logit_err"] < 1e-4
    assert checks["reference_check"]["prompt_tokens"] == 100
    assert checks["scheduler"]["usable_pages"] == 0
    assert checks["scheduler"]["peak_pages_in_use"] == 0
    assert checks["scheduler"]["compiled_programs"] == 2
    # two layers; a snapshot taken while the scheduler runs may hold one
    # chunk's count on one side and not yet on the other (the identities
    # are exact at rest: tests/test_brumby.py)
    assert abs(delta["retention_chunk_calls"]
               - 2 * delta["prefill_chunks"]) <= 2
    assert abs(delta["retention_chunk_tokens"]
               - 2 * delta["prefill_tokens"]) <= 2 * 32
    assert 0 < delta["retention_step_rows"] <= 2 * 4 * delta["decode_steps"]
    for key in ("attn_tokens_attended", "attn_bytes_moved", "fused_turns",
                "prefix_hit_tokens", "pages_allocated_total"):
        assert delta.get(key, 0) == 0, key
    value = {n: line["metrics"][n]["value"] for n in (
        "retention.decode_step_roofline", "sched.fused_turn_share.gap",
        "sched.prefix_hit_share.gap")}
    assert value["retention.decode_step_roofline"] > 0
    assert value["sched.fused_turn_share.gap"] == 0   # two programs a turn
    assert value["sched.prefix_hit_share.gap"] == 0
    assert "jit_paged_decode_step" in " ".join(checks["program_runs"])
    assert "left_running" in proc.stdout
