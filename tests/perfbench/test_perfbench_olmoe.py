"""The OLMoE configuration and its cell (ISSUE 26): the manifest's entries,
the arithmetic of ``perfbench/lib/moe_work.py`` against a count by hand, the
readers of the expert counters on hand-made ``ctx``s, and the CPU rehearsal
of the cell at a toy size over the third tiny manifest
(``tiny/BENCHMARK_olmoe.json``). Counts and structure only: no number here
is a device number.

What ``BENCHMARK.json`` lists for the configuration and the cell is held in
``held.py`` (``pr26_config``, ``pr26_cell``, and since PR 31 ``pr31_entries``:
the ``moe.*`` readers and the six lists of PR 24's the cell joined), by name
and index, so that whatever a later PR appends passes.
"""

import dataclasses
import json
import os
import subprocess
import sys

import pytest

from perfbench.lib import configs, contract, moe_work, peaks
from perfbench.lib import manifest as manifest_lib
from tests.perfbench import held

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = manifest_lib.load()
LAYERS = manifest_lib.load(os.path.join(HERE, "tiny",
                                        "BENCHMARK_layers.json"))
OLMOE = manifest_lib.load(os.path.join(HERE, "tiny", "BENCHMARK_olmoe.json"))
HP = manifest_lib.config(BENCH, "olmoe_1b_7b_l8")
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
SIZES = {"num_layers": 8, "num_kv_heads": 16, "head_dim": 128}  # as run
NEW = held.MOE
JOINED = tuple(n + ".gap" for n in held.SERVE)  # PR 24's six, since PR 31


def read(metric, ctx):
    return manifest_lib.metric_reader(metric)(ctx)


# ------------------------------------------------------ the manifest's part


def test_the_configuration_is_the_published_one_cut_in_depth_only():
    published = {  # the catalog's entry (config.json of the release)
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 1024,
        "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304}
    differs = {k for k, v in published.items() if HP.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"} and HP["num_hidden_layers"] == 8
    held.pr26_config(BENCH)  # fourth, cut in depth only, nothing moved


def test_the_family_names_only_what_the_program_had_before_this_pr():
    """The family file maps onto a preset and fields the PARENT's
    ``TransformerConfig`` already had, so the parent builds a replica from
    it (and fails in the reference check, within minutes) and does not
    raise in its constructor."""
    from ray_tpu.models import presets
    from ray_tpu.models.transformer import TransformerConfig

    fam = manifest_lib.read_json_from_bench("families", "olmoe")
    assert fam["preset"] == "moe_debug" and fam["reference"] == "olmoe"
    since_pr26 = {"qk_norm", "moe_renormalize"}
    fields = {f.name for f in dataclasses.fields(TransformerConfig)}
    named = set(fam["keys"].values()) | set(fam["constants"])
    assert named <= fields - since_pr26
    preset, overrides = configs.program_overrides(HP, fam)
    cfg = getattr(presets, preset)(**{
        k: v for k, v in overrides.items() if "dtype" not in k})
    assert (cfg.qk_norm, cfg.moe_renormalize) == (True, False)
    assert (cfg.embed_dim, cfg.num_heads, cfg.kv_heads, cfg.head_dim,
            cfg.hidden_dim, cfg.moe_num_experts, cfg.moe_top_k,
            cfg.num_layers, cfg.vocab_size, cfg.max_seq_len) == (
        2048, 16, 16, 128, 1024, 64, 8, 8, 50304, 4096)
    assert cfg.rope_theta == 10000 and not cfg.tie_embeddings


def test_the_cell_and_its_traffic_are_the_issues():
    held.pr26_cell(BENCH)  # fifth, one chip, in the lists chat is in
    held.pr31_entries(BENCH)  # and, since PR 31, in PR 24's six and moe.*
    chat = manifest_lib.read_json(BENCH, "cells", "mistral7b_chat")
    mine = manifest_lib.read_json(BENCH, "cells", "olmoe_reason")
    for key in ("kind", "max_ongoing_requests", "deployment", "warmup_s",
                "trace_s", "grace_s"):
        assert mine[key] == chat[key], key  # the same deployment
    mix = manifest_lib.read_json(BENCH, "traffic", "reason")
    assert mix["arrival"] == {"mode": "closed", "clients": 40}
    assert mix["prompt_tokens"] == {"min": 64, "max": 512, "body_max": 256,
                                    "tail_share": 0.1, "tail_alpha": 1.5}
    assert mix["output_tokens"] == {"min": 256, "max": 1024, "body_max": 768,
                                    "tail_share": 0.1, "tail_alpha": 1.5}
    assert (mix["block"], mix["shuffle"], mix["order_seed"]) == (32, 8, 23)
    per_layer = {m["name"] for m in manifest_lib.metrics_for(
        BENCH, "olmoe_reason", True)}
    assert per_layer >= set(held.SHARED) | set(JOINED) | set(NEW)


def test_the_third_tiny_manifest_is_the_second_plus_the_expert_cell():
    """As ``BENCHMARK.json`` took the cell: by appending, to the lists the
    chat cell is in (PR 24's six among them) and behind everything."""
    held.only_added(LAYERS, OLMOE)
    cells = len(LAYERS["workloads"])
    assert OLMOE["workloads"][cells]["name"] == "tiny_reason"
    assert OLMOE["configs"][len(LAYERS["configs"])]["name"] == "tiny_olmoe"
    n = len(LAYERS["per_layer"])
    for mine, theirs in zip(OLMOE["per_layer"], LAYERS["per_layer"]):
        if "tiny_chat" in theirs.get("workloads", ()):
            k = len(theirs["workloads"])  # joined right behind what was there
            assert mine["workloads"][k:k + 1] == ["tiny_reason"], mine["name"]
    assert set(JOINED) <= {m["name"] for m in manifest_lib.metrics_for(
        OLMOE, "tiny_reason", True)}
    assert [m["name"] for m in OLMOE["per_layer"][n:n + 2]] == list(NEW)
    for m in OLMOE["per_layer"][n:n + 2]:
        assert m["workloads"][:1] == ["tiny_reason"]
        assert (m["moves"], m["layer"]) == ("gap_p95_ms", "experts")
        assert callable(manifest_lib.metric_reader(m["name"]))


# --------------------------------------------------------------- arithmetic


def test_bytes_equal_the_count_by_hand():
    assert moe_work.expert_bytes(HP) == 3 * 2048 * 1024 * 2 == 12_582_912
    # a layer: 4 x 2048 x 2048 + 64 x 3 x 2048 x 1024 + 2048 x 64 + norms
    layer = (4 * 2048 * 2048 + 64 * 3 * 2048 * 1024 + 2048 * 64
             + 2 * 2048 + 2 * 2048)
    assert round(layer / 1e6, 1) == 419.6
    assert moe_work.layer_dense_bytes(HP) + 64 * moe_work.expert_bytes(HP) \
        == 2 * layer
    total = 8 * layer + 2 * 2048 * 50304 + 2048
    assert round(total / 1e9, 3) == 3.563
    assert moe_work.model_bytes(HP) == 2 * total
    assert round(moe_work.model_bytes(HP) / 1e9, 2) == 7.13
    assert peaks.kv_bytes_per_token(SIZES) == 2 * 8 * 16 * 128 * 2 == 65536
    full = dict(HP, num_hidden_layers=16)
    assert round(moe_work.model_bytes(full) / 1e9, 1) == 13.8


def test_the_least_step_reads_every_hit_expert_once():
    # all 64 experts hit, no context: the whole model but the embedding
    least = moe_work.decode_step_least_seconds(HP, 64, 0, "TPU v5 lite")
    want = (moe_work.model_bytes(HP) - 2 * 2048 * 50304) / 819e9
    assert least == pytest.approx(want)
    assert 8.4e-3 < least < 8.7e-3
    fewer = moe_work.decode_step_least_seconds(HP, 32, 0, "TPU v5 lite")
    assert least - fewer == pytest.approx(8 * 32 * 12_582_912 / 819e9)
    with_kv = moe_work.decode_step_least_seconds(HP, 64, 32 * 600 * 65536,
                                                 "TPU v5 lite")
    assert with_kv - least == pytest.approx(32 * 600 * 65536 / 819e9)


def moe_ctx(delta, programs=None, trace_window=None):
    trace = None
    if programs is not None:
        trace = {"programs": programs, "ops": {}, "devices": 1}
    return {"trace": trace, "device": V5E, "sizes": SIZES, "cell": {},
            "config": HP,
            "counters": {"delta": delta, "end": {"phase_park_s": 1.0},
                         "trace_window": trace_window or {}}}


COUNTERS = {"moe_layer_calls": 8 * 1000, "moe_live_rows": 32 * 1000,
            "moe_rows_routed": 32 * 1000 * 8 * 8,
            "moe_experts_hit": 8 * 1000 * 62, "moe_max_expert_rows":
            8 * 1000 * 10}


def test_decode_step_roofline_is_least_time_over_the_programs_median():
    programs = {"jit_paged_decode_step": {"count": 100, "sum_s": 1.8,
                                          "median_s": 0.018}}
    window = {"decode_context_tokens": 100 * 32 * 500, "prefill_chunks": []}
    got = read("moe.decode_step_roofline",
               moe_ctx(COUNTERS, programs, window))
    least = moe_work.decode_step_least_seconds(HP, 62, 32 * 500 * 65536,
                                               "TPU v5 lite")
    assert got == pytest.approx(100 * least / 0.018)
    assert 40 < got < 60


def test_max_expert_load_is_the_fullest_over_the_mean():
    # 10 rows in the fullest of 64 experts, 4 a expert on average
    assert read("moe.max_expert_load", moe_ctx(COUNTERS)) == \
        pytest.approx(250.0)


@pytest.mark.parametrize("metric", ["moe.decode_step_roofline",
                                    "moe.max_expert_load"])
def test_without_the_counters_a_reader_reads_zero_not_nothing(metric):
    """A dense model, or a program from before the counters (the parent of
    PR 26 under these readers): 0, and the line is accepted."""
    programs = {"jit_paged_decode_step": {"count": 9, "sum_s": 0.9,
                                          "median_s": 0.1}}
    assert read(metric, moe_ctx({}, programs)) == 0.0
    assert read(metric, moe_ctx({"decode_steps": 40}, None)) == 0.0
    assert read("moe.decode_step_roofline", moe_ctx(COUNTERS, None)) == 0.0
    assert read("moe.decode_step_roofline", moe_ctx(COUNTERS, {})) == 0.0


# ---------------------------------------------------------------- rehearsal


def rehearse(trace, cache_dir, seed=2**31 + 11):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    env.pop("BENCH_RUN", None)
    return subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_olmoe.py"),
         "--workload", "tiny_reason", "--seed", str(seed), "--seconds", "2",
         "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=400)


@pytest.mark.parametrize("trace", [0, 1], ids=["untraced", "traced"])
def test_rehearsal_of_the_expert_cell(trace, tmp_path):
    """The toy OLMoE through ``serve.run``, the scheduler and the paged
    programs, checked against ``reference/olmoe.py`` by the harness; the
    dropless witness in the run's ``delta`` note; every new reader finite."""
    proc = rehearse(trace, tmp_path / "cache")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = contract.last_line(proc.stdout)
    assert contract.check_line(last, OLMOE, "tiny_reason", bool(trace)) == []
    line = json.loads(last)
    assert line["correct"] is True and line["failed"] == 0
    checks = [json.loads(ln) for ln in proc.stdout.splitlines()
              if ln.startswith('{"note": "checks"')][0]
    delta = checks["delta"]
    hp = manifest_lib.config(OLMOE, "tiny_olmoe")
    assert delta["moe_live_rows"] > 0
    assert delta["moe_rows_routed"] == (
        delta["moe_live_rows"] * hp["num_experts_per_tok"]
        * hp["num_hidden_layers"])
    # counted when fetched, a chunk or two after they were dispatched
    runs = delta["moe_layer_calls"] / hp["num_hidden_layers"]
    assert abs(runs - delta["decode_steps"] - delta["prefill_chunks"]) <= 4
    assert checks["reference_check"]["logit_err"] < 1e-4
    if trace:
        value = {n: line["metrics"][n]["value"] for n in NEW + JOINED}
        assert value["step.decode_ms.gap"] > 0
        assert 0 < value["step.prefill_share.gap"] < 100
        assert 0 < value["sched.host_share.gap"] <= 100
        assert value["sched.queue_wait_ms.gap"] > 0
        assert value["replica.stream_lag_ms.gap"] > 0
        assert value["sched.stall_share.gap"] == 0
        assert value["moe.decode_step_roofline"] > 0
        assert 100 <= value["moe.max_expert_load"] <= 800
        assert "jit_paged_decode_step" in checks["program_runs"]
