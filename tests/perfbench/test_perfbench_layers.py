"""The per-layer metrics that read what ISSUE 24 put into the program: the
named serving programs, the scheduler's phase clock and request counters,
the replica's hand-off counter and the named flash kernel. Arithmetic on
hand-made ``ctx``s, the manifests' entries, and the CPU rehearsal of the
serving and training cells over the second tiny manifest
(``tiny/BENCHMARK_layers.json``), which lists the thirteen new names.
Counts and structure only: no number here is a device number."""

import json
import os
import subprocess
import sys

import pytest

from perfbench.lib import contract, layers
from perfbench.lib import manifest as manifest_lib
from tests.perfbench import held

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = manifest_lib.load(os.path.join(HERE, "tiny", "BENCHMARK.json"))
LAYERS = manifest_lib.load(os.path.join(HERE, "tiny",
                                        "BENCHMARK_layers.json"))
SERVE, FLASH = held.SERVE, held.FLASH
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}


def read(metric, ctx):
    return manifest_lib.metric_reader(metric)(ctx)


def serve_ctx(delta=None, programs=None, end=None):
    """A serving cell's ctx as ``perfbench/run.py`` builds it, cut to what
    the readers touch; ``end`` defaults to a program with the phase clock."""
    trace = None
    if programs is not None:
        trace = {"programs": programs, "ops": {}, "devices": 1}
    return {"trace": trace, "device": V5E, "sizes": {}, "cell": {},
            "counters": {"delta": delta or {},
                         "end": {"phase_park_s": 1.0} if end is None
                         else end}}


def run(count, sum_s, median_s):
    return {"count": count, "sum_s": sum_s, "median_s": median_s}


PHASES = {"phase_admit_s": 1.0, "phase_prefill_s": 1.0,
          "phase_prefill_wait_s": 2.0, "phase_decode_prepare_s": 1.0,
          "phase_decode_wait_s": 14.0, "phase_decode_fetch_s": 0.5,
          "phase_sample_s": 0.3, "phase_emit_s": 0.2, "phase_park_s": 30.0,
          "phase_verify_s": 0.0, "phase_migrate_s": 0.0}


# ------------------------------------------------------------- arithmetic


def test_decode_ms_is_the_named_programs_median():
    programs = {"jit_paged_decode_step": run(20, 2.4, 0.1203),
                "jit_paged_prefill_chunk": run(3, 0.111, 0.037),
                "jit_scatter": run(5, 1e-4, 2e-5)}
    assert read("step.decode_ms", serve_ctx(programs=programs)) == \
        pytest.approx(120.3)
    assert read("step.prefill_share", serve_ctx(programs=programs)) == \
        pytest.approx(100 * 0.111 / (0.111 + 2.4))


def test_a_window_without_prefill_reads_zero_and_without_decode_nothing():
    only_decode = {"jit_paged_decode_step": run(9, 1.8, 0.2)}
    assert read("step.prefill_share", serve_ctx(programs=only_decode)) == 0
    only_prefill = {"jit_paged_prefill_chunk": run(3, 0.1, 0.03)}
    for metric in ("step.decode_ms", "step.prefill_share"):
        assert read(metric, serve_ctx(programs=only_prefill)) is None
        assert read(metric, serve_ctx(programs=None)) is None  # no trace


def test_queue_wait_and_stream_lag_are_means_over_the_windows_counts():
    ctx = serve_ctx(delta={"queue_wait_s": 12.0, "admitted": 4,
                           "stream_lag_s": 0.9, "stream_tokens": 300})
    assert read("sched.queue_wait_ms", ctx) == pytest.approx(3000.0)
    assert read("replica.stream_lag_ms", ctx) == pytest.approx(3.0)
    none = serve_ctx(delta={"queue_wait_s": 0.0, "admitted": 0,
                            "stream_lag_s": 0.0, "stream_tokens": 0})
    assert read("sched.queue_wait_ms", none) == 0.0
    assert read("replica.stream_lag_ms", none) == 0.0
    # the counters are there (phase clock) but these two are not: refused
    assert read("sched.queue_wait_ms", serve_ctx(delta={})) is None


def test_host_share_leaves_out_the_device_waits_and_park():
    ctx = serve_ctx(delta=dict(PHASES, stall_s=2.5))
    # working 20 s (all but park), of which 16 s wait for the device
    assert read("sched.host_share", ctx) == pytest.approx(100 * 4 / 20)
    # the loop stood still 2.5 s of the thread's 50 s
    assert read("sched.stall_share", ctx) == pytest.approx(5.0)
    quiet = serve_ctx(delta=dict(PHASES))
    assert read("sched.stall_share", quiet) == 0.0
    stood_still = serve_ctx(delta={k: 0.0 for k in PHASES})
    assert read("sched.host_share", stood_still) is None
    assert read("sched.stall_share", stood_still) is None


def test_a_program_from_before_the_phase_clock_reads_zero_not_nothing():
    """The driver runs the traced cells on the parent commit with these
    readers laid over it; the harness refuses a line that lacks a metric."""
    parent = serve_ctx(
        delta={"decode_steps": 300, "admitted": 60}, end={"slots": 32},
        programs={"jit__unknown": run(23, 2.5, 0.12)})
    for metric in SERVE:
        assert read(metric, parent) == 0.0, metric
        assert read(metric + ".gap", parent) == 0.0, metric


@pytest.mark.parametrize("metric", SERVE)
def test_the_gap_twin_reads_what_its_name_reads(metric):
    ctx = serve_ctx(
        delta=dict(PHASES, queue_wait_s=3.0, admitted=2, stall_s=0.5,
                   stream_lag_s=0.2, stream_tokens=100),
        programs={"jit_paged_decode_step": run(20, 2.4, 0.12),
                  "jit_paged_prefill_chunk": run(3, 0.1, 0.03)})
    assert read(metric + ".gap", ctx) == read(metric, ctx) is not None


def flash_ctx(ops, batch, seq, devices, sizes):
    return {"trace": {"ops": ops, "programs": {}, "devices": devices},
            "sizes": sizes, "cell": {"batch": batch},
            "traffic": {"seq_len": seq}, "counters": {},
            "device": dict(V5E, count=devices)}


def test_flash_roofline_counts_causal_matmuls_of_the_calls_it_sees():
    gpt2 = {"num_heads": 12, "head_dim": 64}
    # 4 steps x 12 layers: forward and its remat twin, dQ, dK/dV
    ops = {"flash_attention_fwd [custom-call]": {"count": 96, "sum_s": 1.2},
           "flash_attention_bwd_dq [custom-call]": {"count": 48,
                                                    "sum_s": 0.5},
           "flash_attention_bwd_dkv [custom-call]": {"count": 48,
                                                     "sum_s": 0.5},
           "fusion": {"count": 1000, "sum_s": 1.4}}
    one = 128 * 12 * 1024 * 1024 * 64  # B H S S D: one causal matmul
    flops = 96 * 2 * one + 48 * 3 * one + 48 * 4 * one
    want = 100 * flops / 197e12 / 2.2
    got = read(FLASH, flash_ctx(ops, 128, 1024, 1, gpt2))
    assert got == pytest.approx(want) and 5 < got < 105
    per_call = layers.flash_flops_per_call(gpt2, 128, 1024, 1)
    assert per_call == {"flash_attention_fwd": 2 * one,
                        "flash_attention_bwd_dq": 3 * one,
                        "flash_attention_bwd_dkv": 4 * one}


def test_flash_roofline_takes_the_devices_share_of_batch_and_heads():
    mistral = {"num_heads": 32, "head_dim": 128}
    ops = {"flash_attention_fwd [custom-call]": {"count": 48, "sum_s": 0.1}}
    one_chip = read(FLASH, flash_ctx(ops, 8, 4096, 1, mistral))
    four = read(FLASH, flash_ctx(ops, 8, 4096, 4, mistral))
    assert four == pytest.approx(one_chip / 4)
    one = 8 * 32 / 4 * 4096 * 4096 * 128
    assert four == pytest.approx(100 * 48 * 2 * one / 197e12 / 0.1)


def test_flash_roofline_reads_zero_where_no_kernel_has_the_name():
    ops = {"closed_call [custom-call]": {"count": 48, "sum_s": 0.6},
           "checkpoint [custom-call]": {"count": 48, "sum_s": 1.0}}
    ctx = flash_ctx(ops, 128, 1024, 1, {"num_heads": 12, "head_dim": 64})
    assert read(FLASH, ctx) == 0.0
    assert read(FLASH, dict(ctx, trace=None)) is None


# -------------------------------------------------------------- manifests


def test_the_benchmark_lists_the_thirteen_names_each_with_a_reader():
    held.pr24_entries(manifest_lib.load())


def test_the_second_tiny_manifest_is_the_first_plus_the_new_names():
    first = {k: v for k, v in TINY.items() if k != "per_layer"}
    assert {k: v for k, v in LAYERS.items() if k != "per_layer"} == first
    n = len(TINY["per_layer"])
    assert LAYERS["per_layer"][:n] == TINY["per_layer"]
    added = [m["name"] for m in LAYERS["per_layer"][n:]]
    assert added == held.THIRTEEN  # BENCHMARK.json's [18:31], held there


# -------------------------------------------------------------- rehearsal


def rehearse(workload, cache_dir, seed=2**31 + 7):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    env["XLA_FLAGS"] = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f)
    env.pop("BENCH_RUN", None)
    return subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_layers.py"),
         "--workload", workload, "--seed", str(seed), "--seconds", "2",
         "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=400)


@pytest.mark.parametrize("workload,suffix", [("tiny_docs", ""),
                                             ("tiny_chat", ".gap")])
def test_serving_rehearsal_reads_the_phase_clock_and_the_names(
        workload, suffix, tmp_path):
    proc = rehearse(workload, tmp_path / "cache")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = contract.last_line(proc.stdout)
    assert contract.check_line(last, LAYERS, workload, True) == []
    line = json.loads(last)
    value = {n: line["metrics"][n + suffix]["value"] for n in SERVE}
    assert value["step.decode_ms"] > 0
    assert 0 < value["step.prefill_share"] < 100  # both programs ran
    assert 0 < value["sched.host_share"] <= 100
    assert value["sched.stall_share"] == 0
    assert value["sched.queue_wait_ms"] > 0
    assert value["replica.stream_lag_ms"] > 0
    labels = [label for label, _ in line["breakdown"]["idle_gaps"]]
    assert not [l for l in labels if "jit__unknown" in l], labels
    assert [l for l in labels if "| host: serve." in l], labels
    # the phase seconds travel in the checks note of every run
    checks = [json.loads(ln) for ln in proc.stdout.splitlines()
              if ln.startswith('{"note": "checks"')][0]
    assert checks["delta"]["phase_decode_wait_s"] > 0
    assert not [p for p in checks["program_runs"] if "unknown" in p]


def test_training_rehearsal_reports_the_flash_share(tmp_path):
    """On the CPU the attention is the reference, not the kernel: the share
    reads 0, and is reported."""
    proc = rehearse("tiny_train", tmp_path / "cache")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = contract.last_line(proc.stdout)
    assert contract.check_line(last, LAYERS, "tiny_train", True) == []
    assert json.loads(last)["metrics"][FLASH] == {"value": 0.0, "unit": "%"}
