"""A training cell: ``JaxTrainer`` with one chip worker that runs
``make_train_step`` for the window and reports what it measured.

``run`` is the command's side (never imports JAX); ``train_loop`` is handed
to ``JaxTrainer`` and runs inside the worker that holds the chips — the only
process that can time the device, trace it and read its memory.
"""

from __future__ import annotations

import math
import os
import time
from typing import Any, Dict


def train_loop(config: Dict[str, Any]) -> None:
    first_line = time.time()
    # where set-up's seconds go: one stamp after each phase, by name
    phases, t_phase = {}, time.perf_counter()

    def phase_done(name: str) -> None:
        nonlocal t_phase
        now = time.perf_counter()
        phases[name] = now - t_phase
        t_phase = now

    import jax
    import numpy as np
    phase_done("import_jax")

    from perfbench.lib import configs, trace, traffic, weights, worker
    from perfbench.reference import common
    from perfbench.lib import manifest as manifest_lib
    from ray_tpu import train
    from ray_tpu.models.training import (OptimizerConfig, init_train_state,
                                         make_train_step)
    from ray_tpu.models.transformer import loss_fn
    phase_done("import_program")

    compiles = worker.CompileCounter()
    stamp = worker.device_stamp(config["require_tpu"])
    phase_done("open_chip")
    cell, mix, seed = config["cell"], config["traffic"], config["seed"]
    cfg = configs.build_program_config(config["preset"], config["overrides"])
    sizes = configs.program_sizes(cfg)
    batch, seq = int(cell["batch"]), int(mix["seq_len"])

    mesh = None
    place = jax.device_put
    if cell.get("mesh"):
        from ray_tpu.parallel.mesh import (MeshSpec, build_mesh,
                                           data_sharding)

        mesh = build_mesh(MeshSpec.of(**cell["mesh"]))
        place = lambda x: jax.device_put(x, data_sharding(mesh))
    ocfg = OptimizerConfig(**cell.get("optimizer", {}))
    state, tx = init_train_state(cfg, ocfg, weights.key_for(seed), mesh)
    step = make_train_step(cfg, tx, mesh)
    jax.block_until_ready(state.params)
    phase_done("state_init")

    # ---- correct: the system's loss on a seeded sample against the plain
    # reference on the same weights (set-up, outside the window)
    ref = manifest_lib.load_module(config["reference_path"],
                                   "perfbench_reference")
    sample = next(traffic.token_batches(
        {**mix, "seq_len": int(cell["check_seq_len"])}, seed + 1,
        int(cell["check_batch"]), cfg.vocab_size))
    sample_dev = place(sample)

    def system_loss(params, tokens):
        if mesh is None:
            return loss_fn(cfg, params, {"tokens": tokens})[0]
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return loss_fn(cfg, params, {"tokens": tokens})[0]

    loss_sys = float(jax.jit(system_loss)(state.params, sample_dev))
    loss_ref = float(common.next_token_loss(
        ref.forward(state.params, sample_dev, config["config"]), sample_dev))
    check_err = abs(loss_sys - loss_ref) / abs(loss_ref)
    phase_done("reference_check")

    # ---- warm-up: compile the one shape the window uses, take two steps
    batches = traffic.token_batches(mix, seed, batch, cfg.vocab_size)
    first = {"tokens": place(next(batches))}
    t0 = time.perf_counter()
    compiled = step.lower(state, first).compile()
    compile_s = time.perf_counter() - t0
    if config["require_tpu"] and "tpu_custom_call" not in compiled.as_text():
        raise RuntimeError("the compiled step holds no Pallas kernel: "
                           "attention fell to the reference")
    for _ in range(2):
        state, metrics = compiled(state, {"tokens": place(next(batches))})
    jax.block_until_ready(metrics["loss"])
    phase_done("compile_and_warmup")

    # ---- the measured window: a new batch from the host every step, the
    # host one step ahead of the device and never further
    seconds = float(config["seconds"])
    traced = bool(config["trace"])
    trace_steps = int(cell.get("trace_steps", 4))
    trace_dir = config["trace_dir"]
    losses, summary = [], None
    traced_s, traced_n = 0.0, 0
    compiles_before = compiles.count
    window_start = time.time()
    t_start = time.perf_counter()
    n = 0
    pending = None
    while time.perf_counter() - t_start < seconds:
        if traced and summary is None and n >= 3 and (
                time.perf_counter() - t_start > 0.3 * seconds):
            # the traced sub-window: its steps and its time are kept out of
            # the rate, so the profiler's drag is not read as a slow step
            jax.block_until_ready(pending)
            t_tr = time.perf_counter()
            trace.start(trace_dir)
            for _ in range(trace_steps):
                state, metrics = compiled(
                    state, {"tokens": place(next(batches))})
                losses.append(metrics["loss"])
            jax.block_until_ready(metrics["loss"])
            path = trace.stop(trace_dir)
            if os.environ.get("PERFBENCH_DESCRIBE_TRACE"):
                with open(os.environ["PERFBENCH_DESCRIBE_TRACE"], "w") as f:
                    f.write(trace.describe(path, per_line=40))
            summary = trace.summarize(trace.load(path))
            traced_s += time.perf_counter() - t_tr
            traced_n += trace_steps
            pending = None
        state, metrics = compiled(state, {"tokens": place(next(batches))})
        losses.append(metrics["loss"])
        n += 1
        if pending is not None:
            jax.block_until_ready(pending)
        pending = metrics["loss"]
    jax.block_until_ready(pending)
    elapsed = time.perf_counter() - t_start
    losses = [float(x) for x in jax.device_get(losses)]
    quarter = max(len(losses) // 4, 1)
    train.report({
        "first_line": first_line, "window_start": window_start,
        "steps": n + traced_n, "steps_timed": n,
        "elapsed_s": elapsed, "timed_s": elapsed - traced_s,
        "tokens_per_step": batch * seq,
        "losses_head": losses[:3], "losses_tail": losses[-3:],
        "loss_first_quarter": float(np.mean(losses[:quarter])),
        "loss_last_quarter": float(np.mean(losses[-quarter:])),
        "losses_finite": all(math.isfinite(x) for x in losses),
        "loss_system": loss_sys, "loss_reference": loss_ref,
        "check_rel_err": check_err,
        "compile_s": compile_s, "setup_phases": phases,
        "compiles_in_window": compiles.count - compiles_before,
        "memory_analysis": str(compiled.memory_analysis()),
        "memory_stats": dict(jax.devices()[0].memory_stats() or {}),
        "device": {**stamp, "memory_peak_bytes": worker.memory_peak_bytes()},
        "sizes": sizes, "trace": summary,
    })


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    """Drive the cell through ``JaxTrainer`` and return the run's results
    in the harness's common form."""
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train import JaxTrainer

    cell, chips = ctx["cell"], ctx["workload"]["chips"]
    loop_config = {
        "seed": ctx["seed"], "seconds": ctx["seconds"], "trace": ctx["trace"],
        "cell": cell, "traffic": ctx["traffic"], "config": ctx["config"],
        "preset": ctx["preset"], "overrides": ctx["overrides"],
        "reference_path": ctx["reference_path"],
        "require_tpu": ctx["require_tpu"], "trace_dir": ctx["trace_dir"],
    }
    result = JaxTrainer(
        train_loop, train_loop_config=loop_config,
        scaling_config=ScalingConfig(
            num_workers=1, use_tpu=ctx["require_tpu"],
            tpus_per_worker=chips if ctx["require_tpu"] else 0),
    ).fit()
    if result.error is not None:
        raise RuntimeError(f"training failed: {result.error}")
    out = dict(result.metrics)
    tol = float(cell["check_rel_tolerance"])
    checks = {
        "reference": out["check_rel_err"] <= tol,
        "losses_finite": out["losses_finite"],
        "loss_falls": out["loss_last_quarter"] < out["loss_first_quarter"],
        "no_compile_in_window": out["compiles_in_window"] == 0,
    }
    rate = out["steps_timed"] * out["tokens_per_step"] / out["timed_s"]
    return {
        "correct": all(checks.values()), "checks": checks,
        "compared": {
            "loss_rel_err": {"value": out["check_rel_err"], "limit": tol},
            "compiles_in_window": {"value": out["compiles_in_window"],
                                   "limit": 0}},
        "attempted": out["steps"], "failed": 0,
        "e2e": {"train_tokens_per_s": rate,
                "setup_s": out["window_start"] - ctx["t_process_start"]},
        "device": out["device"], "trace": out["trace"],
        "clock": {"worker_start_s": out["first_line"] - ctx["t_init"]},
        "sizes": out["sizes"], "counters": {},
        "setup_phases": out["setup_phases"],
        "notes": {k: out[k] for k in (
            "steps", "steps_timed", "elapsed_s", "timed_s", "losses_head",
            "losses_tail", "loss_system", "loss_reference", "check_rel_err",
            "compile_s", "compiles_in_window", "memory_analysis",
            "memory_stats")},
    }
