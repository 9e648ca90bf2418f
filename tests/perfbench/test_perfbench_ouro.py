"""The Ouro-2.6B configuration and its cell ``ouro_reason`` (ISSUE 65): what
``BENCHMARK.json`` lists for them, held by NAME and cut at this PR's first
entry (``pr65_entries``: never ``[-1]``, a total or a whole ``workloads``
list, so the next PR can add behind them), applied to the committed manifest
and to a synthetic later addition; the configuration against the catalog's
row, NOTHING cut; the arithmetic of ``perfbench/lib/loop_work.py`` against
counts by hand, at the published sizes and on the tiny tree's toy
(``tiny/configs/tiny_ouro.json``, the cell ``tiny_reason_loop``); the three
readers on hand-made ``ctx``s, and without their kernel, trace or passes
NOTHING. Counts and structure only: no number here is a device number. No
rehearsal of the toy through the served path: a process of its own is most
of the 60 s this PR's tests are held to, and a script no test runs is
nobody's (review of PR 65); the served path is ``tests/test_ouro_paged.py``'s
scheduler case and, at the published sizes, the chip's.

This PR is no ``benchmark`` PR, so its hold lives in this file, which it
adds: ``tests/perfbench/held.py`` is a file the benchmark already has. A
later ``benchmark`` PR moves ``pr65_entries`` into ``held.CHECKS``.
"""

import copy
import json
import os

import pytest

from perfbench.lib import loop_work
from perfbench.lib import manifest as manifest_lib
from tests.perfbench import held
from tests.perfbench.test_perfbench_additions import add_a_prs_entries

HERE = os.path.dirname(os.path.abspath(__file__))
TINY_DIR = os.path.join(HERE, "tiny")
BENCH = manifest_lib.load()
CONFIG, CELL, MIX = "ouro_2_6b", "ouro_reason", "reason_short"
BEFORE = "deepseek_v32_longdocs"  # the last cell of most lists joined
HP = manifest_lib.config(BENCH, CONFIG)
NEW = ["loop.decode_step_roofline", "kernel.paged_attn_roofline.loop",
       "step.loop_attn_share"]
ROW = {  # unit, better, source, layer
    NEW[0]: ("%", "higher", "device_trace", "jitted step"),
    NEW[1]: ("%", "higher", "device_trace", "kernels"),
    NEW[2]: ("%", "lower", "device_trace", "jitted step")}
JOINED = ["client.tokens_per_s", "client.ttft_p50_ms.gap",
          "client.ttft_p95_ms.gap", "sched.occupancy.gap",
          "sched.prefix_hit_share.gap", "paging.peak_pages_in_use.gap",
          "device.idle_share.gap", "step.prefill_share.gap",
          "step.turn_ms.gap", "sched.queue_wait_ms.gap",
          "sched.host_share.gap", "sched.stall_share.gap",
          "replica.stream_lag_ms.gap", "sched.prefill_turn_ms.gap",
          "sched.prefill_turn_share.gap", "sched.fused_turn_share.gap",
          "setup.jit_trace_lower_s", "setup.jit_compile_s",
          "setup.jit_cache_hit_share", "setup.jit_compile_events",
          "setup.weights_s", "setup.scheduler_build_s"]
# the two that read a PLAIN step: every traced run of the builder's held one
# (PERF.md 6, PR 65), so the cell is on them, behind another neighbour
PLAIN_STEP = {"step.decode_ms.gap": "nemotron3_nano_reason",
              "sched.decode_turn_ms.gap": "nemotron3_nano_reason"}
# readers whose bytes or counters are another model's
NOT_JOINED = ["kernel.paged_attn_roofline", "moe.decode_step_roofline",
              "moe.max_expert_load", "moe.held_route_share",
              "step.mixer_share", "kernel.latent_attn_roofline",
              "kernel.window_attn_roofline", "window.decode_step_roofline",
              "ssm.decode_step_roofline", "retention.decode_step_roofline",
              "sched.prefix_hit_share", "serve_tokens_per_s"]
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def by_name(rows):
    return {r["name"]: r for r in rows}


def pr65_entries(manifest):
    """This PR's entries as it wrote them, found by name; whatever a later
    PR put behind them is free."""
    config = by_name(manifest["configs"])[CONFIG]
    assert config["file"] == f"perfbench/configs/{CONFIG}.json"
    assert config["reduced"] == []  # nothing is cut
    assert config["source"] == ("https://huggingface.co/ByteDance/Ouro-2.6B/"
                                "blob/main/config.json")
    cell = by_name(manifest["workloads"])[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, MIX, 1)
    names = [w["name"] for w in manifest["workloads"]]
    assert names.index(CELL) > names.index(BEFORE)
    rows = by_name({**by_name(manifest["per_layer"]),
                    **by_name(manifest["end_to_end"])}.values())
    for name, before in {**dict.fromkeys(JOINED + ["gap_p95_ms"], BEFORE),
                         **PLAIN_STEP}.items():
        cells = rows[name]["workloads"]
        assert cells[cells.index(CELL) - 1] == before, name
        assert rows[name].get("moves", "gap_p95_ms") in ("gap_p95_ms",
                                                         "setup_s")
    for name in NOT_JOINED:
        assert CELL not in rows[name]["workloads"], name
    order = [m["name"] for m in manifest["per_layer"]]
    at = order.index(NEW[0])
    assert order[at:at + len(NEW)] == NEW       # together, in this order
    assert at > order.index("picked.turn_roofline")  # behind PR 61's
    for name in NEW:
        row = rows[name]
        # a new entry lists the PR's own cell first and no cell the
        # benchmark had (whose parent has no such loop)
        assert row["workloads"][:1] == [CELL]
        assert not set(row["workloads"]) & set(names[:names.index(CELL)])
        assert (row["unit"], row["better"], row["source"],
                row["layer"]) == ROW[name]
        assert row["moves"] == "gap_p95_ms"


def without_this_pr(manifest):
    """The manifest this PR found: its entries AND WHATEVER FOLLOWED THEM
    taken out again."""
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


def test_this_pr_added_by_adding_and_holds_its_own_entries():
    pr65_entries(BENCH)
    parent = without_this_pr(BENCH)
    assert CELL not in json.dumps(parent) and CONFIG not in json.dumps(parent)
    held.only_added(parent, BENCH)
    held.static_rules(BENCH)
    held.every_cell_reports(BENCH)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(BENCH["workloads"]) >= 13 and len(BENCH["configs"]) >= 12


@pytest.mark.parametrize("tags", [("later",), ("later", "and_later")],
                         ids=["one_pr_behind_it", "two_prs_behind_it"])
def test_its_hold_accepts_what_later_prs_add(tags):
    later = copy.deepcopy({k: v for k, v in BENCH.items() if k != "_dir"})
    for tag in tags:
        add_a_prs_entries(later, tag)
    pr65_entries(later)
    held.only_added(BENCH, later)
    held.static_rules(later)
    held.only_added(without_this_pr(later), later)


def row_of(manifest, name):
    return by_name(manifest["per_layer"])[name]


@pytest.mark.parametrize("edit", [
    lambda m: m["workloads"].insert(0, m["workloads"].pop(
        [w["name"] for w in m["workloads"]].index(CELL))),
    lambda m: m["per_layer"].remove(row_of(m, NEW[1])),
    lambda m: row_of(m, "kernel.paged_attn_roofline")["workloads"].append(
        CELL),
    lambda m: by_name(m["end_to_end"])["gap_p95_ms"]["workloads"].remove(
        CELL),
    lambda m: by_name(m["configs"])[CONFIG]["reduced"].append(
        "num_hidden_layers"),
    lambda m: row_of(m, NEW[0])["workloads"].append("mistral7b_chat"),
    lambda m: by_name(m["workloads"])[CELL].update(chips=4),
], ids=["the_cell_moved_to_the_front", "a_reader_taken_away",
        "the_cell_on_the_one_pass_kernels_roofline",
        "the_cell_out_of_gap_p95_ms", "the_depth_listed_as_reduced",
        "a_cell_the_benchmark_had_on_a_new_metric",
        "four_chips_for_one_chips_work"])
def test_its_hold_refuses_an_edit_of_its_entries(edit):
    edited = copy.deepcopy({k: v for k, v in BENCH.items() if k != "_dir"})
    edit(edited)
    with pytest.raises((AssertionError, KeyError, ValueError)):
        pr65_entries(edited)


def test_the_configuration_is_the_published_one_uncut():
    """Every key of the catalog's row as it stands, ``reduced`` empty, what
    no key says under ``assumed``; the cell's deployment and its mix as
    ISSUE 65 states them; a token's cache and the weights as it reckons."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(path):
        with open(path) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Ouro-2.6B")
        assert {k: HP[k] for k in row["config"]} == row["config"]
        assert HP["source"] == row["source_url"]
    assert HP["reduced"] == {} and len(HP["assumed"]) >= 8
    assert (HP["num_hidden_layers"], HP["total_ut_steps"],
            HP["early_exit_threshold"]) == (48, 4, 1)
    assert loop_work.layer_params(HP) == 51_388_416
    assert loop_work.token_bytes(HP) == 1_572_864 == 192 * 8192
    weights = (48 * loop_work.layer_params(HP) + 2 * loop_work.head_params(HP)
               + 2048 + 2049)
    assert weights == 2_667_974_657
    cell = manifest_lib.read_json(BENCH, "cells", CELL)
    dep = cell["deployment"]
    # ISSUE 65's first choice: every slot's worst case, 84% of the chip
    assert (dep["slots"], dep["prefill_chunk"], dep["arena_len"],
            dep["page_tokens"], dep["kv_pages"], dep["prefix_cache"]) == (
                8, 256, 704, 16, 353, True)
    assert dep["slots"] * dep["arena_len"] // 16 == dep["kv_pages"] - 1
    pool = (dep["kv_pages"] - 1) * 16 * loop_work.token_bytes(HP)
    assert 8.85e9 < pool < 8.87e9 and 14.1e9 < pool + 2 * weights < 14.3e9
    assert set(cell["check_tolerance"]) == {"logit_err", "logit_rms_err",
                                            "served_margin"}
    mix = manifest_lib.read_json(BENCH, "traffic", MIX)
    assert mix["arrival"] == {"mode": "closed", "clients": 10}
    sizes = [(mix[k]["min"], mix[k]["max"], mix[k]["tail_share"],
              mix[k]["tail_alpha"]) for k in ("prompt_tokens",
                                              "output_tokens")]
    assert sizes == [(64, 256, 0.1, 1.5), (128, 448, 0.1, 1.5)]
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] <= \
        dep["arena_len"]
    fam = manifest_lib.read_json_from_bench("families", "ouro")
    assert (fam["preset"], fam["reference"]) == ("ouro", "ouro")
    assert fam["keys"]["total_ut_steps"] == "loop_passes"
    assert fam["keys"]["early_exit_threshold"] == "exit_threshold"


def ctx_of(hp, *, step_s=0.040, kernel_s=0.5, busy_s=2.9, steps=70,
           context=250_000, chunks=((256, 256), (100, 356)), slots=8):
    """A traced run's context as ``run.metrics_of`` hands it to a reader."""
    return {
        "config": hp, "device": V5E,
        "cell": {"deployment": {"slots": slots}},
        "trace": {"busy_s": busy_s, "ops": {
            "paged_attention.1": {"count": 192 * steps, "sum_s": kernel_s,
                                  "median_s": kernel_s / (192 * steps)}},
            "programs": {"jit_paged_decode_step": {
                "count": steps, "sum_s": steps * step_s,
                "median_s": step_s}}},
        "counters": {"delta": {}, "trace_window": {
            "decode_context_tokens": context,
            "prefill_chunks": [list(c) for c in chunks]}}}


def test_the_three_readers_by_hand_at_the_published_sizes():
    """70 plain steps of 40 ms whose delivered tokens' contexts add up to
    250,000: a step reads the 4.93 GB stack FOUR times, the head and 3,571
    tokens x 1.5 MB."""
    ctx = ctx_of(HP)
    read = {name: manifest_lib.metric_reader(name)(ctx) for name in NEW}
    stack = 48 * 51_388_416
    moved = 2 * (4 * (stack + 2048) + 2049 + 2048 * 49152) \
        + 250_000 / 70 * 1_572_864
    assert read[NEW[0]] == pytest.approx(100 * moved / 819e9 / 0.040)
    assert 75 < read[NEW[0]] < 85  # 31.3 ms of bytes under a 40 ms step
    # the kernel: every delivered token's context in all 192 pools, and two
    # chunks: the first bound by its operations, the second by its bytes
    pair = 4.0 * 16 * 128 * 48 * 4
    least = 250_000 * 1_572_864 / 819e9 + max(
        256 * 256 * pair / 197e12, 256 * 1_572_864 / 819e9) + max(
        100 * 356 * pair / 197e12, 356 * 1_572_864 / 819e9)
    assert read[NEW[1]] == pytest.approx(100 * least / 0.5)
    assert 256 * 256 * pair / 197e12 > 256 * 1_572_864 / 819e9
    assert 100 * 356 * pair / 197e12 < 356 * 1_572_864 / 819e9
    assert read[NEW[2]] == pytest.approx(100 * 0.5 / 2.9)
    assert all(0 < v < 100 for v in read.values())
    # every traced turn carried a chunk: the chunk program's median, if its
    # runs carried decode rows
    fused = copy.deepcopy(ctx)
    fused["trace"]["programs"] = {"jit_paged_prefill_chunk": {
        "count": 10, "sum_s": 0.9, "median_s": 0.090}}
    fused["counters"]["delta"] = {"prefill_chunks": 40, "fused_turns": 40}
    assert manifest_lib.metric_reader(NEW[0])(fused) == pytest.approx(
        100 * loop_work.step_least_seconds(HP, 8, 25_000, V5E["kind"])
        / 0.090)


def test_the_arithmetic_on_the_tiny_trees_toy():
    """``tiny/configs/tiny_ouro.json`` (three layers of width 64 gone
    through four times), the cell ``tiny_reason_loop``: counts by hand."""
    tiny = {"_dir": TINY_DIR, "paths": ["."], "configs": [
        {"name": "tiny_ouro", "file": "configs/tiny_ouro.json"}]}
    hp = manifest_lib.config(tiny, "tiny_ouro")
    cell = manifest_lib.read_json(tiny, "cells", "tiny_reason_loop")
    assert cell["deployment"]["prefix_cache"] is True
    assert loop_work.passes(hp) == 4
    assert loop_work.layer_params(hp) == 4 * 64 * 64 + 3 * 64 * 128 + 4 * 64
    assert loop_work.token_bytes(hp, 4) == 2 * 4 * 16 * 4 * 3 * 4
    assert loop_work.pair_flops(hp) == 4.0 * 4 * 16 * 3 * 4
    slots = cell["deployment"]["slots"]
    least = loop_work.step_least_seconds(hp, slots, 100, V5E["kind"], 4)
    stack = 3 * loop_work.layer_params(hp)
    assert least == pytest.approx((4 * (4 * (stack + 64) + 65 + 64 * 256)
                                   + 100 * loop_work.token_bytes(hp, 4))
                                  / 819e9)
    read = manifest_lib.metric_reader(NEW[0])(ctx_of(hp, slots=slots))
    assert 0 < read < 1  # a toy's bytes under a 40 ms step


@pytest.mark.parametrize("name", NEW)
def test_a_reader_without_its_kernel_trace_or_passes_reads_nothing(name):
    """On the parent's side, on an untraced run, for another model: None,
    never an exception (the line then leaves the metric out)."""
    read = manifest_lib.metric_reader(name)
    assert read({**ctx_of(HP), "trace": None}) is None
    bare = ctx_of(HP)
    bare["trace"]["ops"], bare["trace"]["programs"] = {}, {}
    assert read(bare) is None
    other = manifest_lib.config(BENCH, "mistral7b_v03_l16")
    assert read(ctx_of(other)) is None
    empty = ctx_of(HP)
    empty["counters"] = {"delta": {}}
    assert read(empty) is None or name == NEW[2]


def test_no_cell_the_benchmark_had_reports_a_new_metric():
    old = [w["name"] for w in without_this_pr(BENCH)["workloads"]]
    assert len(old) == 12
    for cell in old:
        for traced in (False, True):
            names = {m["name"] for m in manifest_lib.metrics_for(
                BENCH, cell, traced)}
            assert not names & set(NEW), (cell, names & set(NEW))
    mine = {m["name"] for m in manifest_lib.metrics_for(BENCH, CELL, True)}
    assert set(NEW + JOINED + list(PLAIN_STEP)) <= mine
    assert {m["name"] for m in manifest_lib.metrics_for(
        BENCH, CELL, False)} == {"gap_p95_ms", "setup_s"}
