"""The harness end to end on the CPU: one train cell and the serve cells at
a toy size, through the test-only entry (``rehearse.py``), untraced and
traced. Each run is a fresh process, as on the chip, and has to end in a
last line the contract checker accepts. What is counted here is counts: no
number of these runs is a device number."""

import json
import os
import subprocess
import sys

import pytest

from perfbench.lib import contract
from perfbench.lib import manifest as manifest_lib

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TINY = manifest_lib.load(os.path.join(HERE, "tiny", "BENCHMARK.json"))


def rehearse(workload, trace, cache_dir, seed=2**31 + 5):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    # as many devices as the cell has chips (conftest asks for eight)
    chips = manifest_lib.workload(TINY, workload)["chips"]
    flags = [f for f in env.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    if chips > 1:
        flags.append(f"--xla_force_host_platform_device_count={chips}")
    env["XLA_FLAGS"] = " ".join(flags)
    env.pop("BENCH_RUN", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse.py"),
         "--workload", workload, "--seed", str(seed), "--seconds", "2",
         "--trace", str(trace)],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=400)
    return proc


@pytest.mark.parametrize("workload,trace", [
    ("tiny_train", 0), ("tiny_train", 1), ("tiny_chat", 0),
    ("tiny_chat", 1), ("tiny_docs", 1), ("tiny_train_4dev", 1)])
def test_rehearsal_ends_in_a_line_the_checker_accepts(workload, trace,
                                                      tmp_path):
    proc = rehearse(workload, trace, tmp_path / "cache")
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    last = contract.last_line(proc.stdout)
    assert contract.check_line(last, TINY, workload, bool(trace)) == []
    line = json.loads(last)
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    if trace:
        assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
        assert line["breakdown"]["device_ops"]
    if workload == "tiny_train_4dev":
        assert line["device"]["count"] == 4
        assert line["metrics"]["coll.exposed_share"]["value"] > 0
    if workload == "tiny_docs":
        hit = line["metrics"]["sched.prefix_hit_share"]["value"]
        assert 30 < hit <= 100  # three of four asks can hit the cache


def test_the_command_itself_refuses_a_machine_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "perfbench", "run.py"),
         "--workload", manifest_lib.load()["workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "TPU" in proc.stderr
    last = contract.last_line(proc.stdout)
    assert not last.startswith('{"correct"')
