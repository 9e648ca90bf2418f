"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two flagship paths once, through the entry points a user calls,
at GPT-2 small's published width (`presets.gpt2_small()` unmodified; random
weights from ``--seed``):

  train    ray_tpu.init() finds the chip; JaxTrainer(...).fit() takes 6 steps
           (0..5) at batch 16 x 1024 with the flash kernel in the program
  kernels  a @ray_tpu.remote(num_tpus=1) task checks flash attention (fwd,
           grad) and paged attention (K=1, 4 and 5; and at Mistral-7B's widths
           K=1 and K=512 over contexts 16 to 8192) against their jax.numpy
           references on the device, and prints the largest errors
  serve    serve.run(build_app(preset="gpt2_small")) answers 8 concurrent
           requests: six through the handle, one streamed, one over HTTP

``--chips 4`` runs instead, and only, what exists across chips: one worker
holding four chips (fsdp=2 x tp=2) against the same job on one device, and
four one-chip actors alive together.

This process never imports JAX: a chip belongs to one process at a time, and
each phase's worker has exited (or been killed) before the next one starts.
Every phase prints one JSON line; a phase that fails ends the script at once
with a non-zero code. The last line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
with the device as JAX reports it inside the worker. Without an accelerator
the script fails and prints no such line.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import random
import shutil
import threading
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
BATCH, SEQ = 16, 1024          # T1's training batch
NEW_TOKENS = 32                # per serve request
PROMPT_LENS = (16, 48, 96, 160, 256, 512)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


# --------------------------------------------------------------------------
# code that runs INSIDE the workers (the only places jax is imported)


def require_chip() -> None:
    """Fail unless this worker really drives a TPU with compiled kernels."""
    import jax

    from ray_tpu.ops._pallas import should_interpret

    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise RuntimeError(f"worker runs on {platform!r}, not on a TPU")
    if should_interpret():
        raise RuntimeError("Pallas interpretation is in force on the chip "
                           "path (RAY_TPU_PALLAS_INTERPRET?)")


def device_report() -> dict:
    import jax

    from ray_tpu._native.build import native_available

    devices = jax.devices()
    stats = [d.memory_stats() or {} for d in devices]
    return {
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices)},
        "peak_bytes_in_use": max(s.get("peak_bytes_in_use", 0)
                                 for s in stats),
        "bytes_in_use": [s.get("bytes_in_use", 0) for s in stats],
        "pinned_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "pid": os.getpid(),
        "native": {"allocator": native_available("allocator"),
                   "codec": native_available("codec")},
    }


def train_loop(config: dict) -> None:
    """JaxTrainer loop: GPT-2 small, one repeated batch, `steps` steps;
    reports losses, compile seconds and what the worker sees."""
    import jax

    from ray_tpu import train
    from ray_tpu.models import presets
    from ray_tpu.models.training import (OptimizerConfig, init_train_state,
                                         make_train_step)

    require_chip()
    cfg = presets.gpt2_small()
    ocfg = OptimizerConfig(warmup_steps=2, decay_steps=100)
    mesh = None
    if config["mesh"]:
        from ray_tpu.parallel.mesh import MeshSpec, build_mesh

        mesh = build_mesh(MeshSpec.of(**config["mesh"]))
    state, tx = init_train_state(
        cfg, ocfg, jax.random.PRNGKey(config["seed"]), mesh)
    step = make_train_step(cfg, tx, mesh)
    tokens = jax.random.randint(jax.random.PRNGKey(config["seed"] + 1),
                                (BATCH, SEQ), 0, cfg.vocab_size)
    if mesh is not None:
        from ray_tpu.parallel.mesh import data_sharding

        tokens = jax.device_put(tokens, data_sharding(mesh))
    batch = {"tokens": tokens}

    t0 = time.perf_counter()
    compiled = step.lower(state, batch).compile()
    compile_s = time.perf_counter() - t0
    if "tpu_custom_call" not in compiled.as_text():
        raise RuntimeError("the compiled step holds no Pallas kernel: "
                           "attention fell to the reference")
    losses, step_s = [], []
    for _ in range(config["steps"]):
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch)
        jax.block_until_ready(metrics["loss"])
        step_s.append(round(time.perf_counter() - t0, 4))
        losses.append(float(metrics["loss"]))
    train.report({"losses": losses, "step_s": step_s,
                  "compile_s": round(compile_s, 2),
                  "mesh": dict(mesh.shape) if mesh is not None else None,
                  **device_report()})


def kernels_task(seed: int) -> dict:
    """Flash and paged attention against their references: GPT-2s shapes,
    and the paged kernel at Mistral-7B's widths too."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.flash_attention import (flash_attention,
                                             reference_attention)
    from ray_tpu.ops.paged_attention import paged_attention

    require_chip()
    H, D, T, P = 12, 64, 16, SEQ // 16
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    compile_s = 0.0

    def first_call(fn, *args):
        nonlocal compile_s
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        compile_s += time.perf_counter() - t0
        return out

    def max_err(got, want):
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-6))

    # ---- flash attention, forward and gradients
    q, k, v, w = (jax.random.normal(next(keys), (4, SEQ, H, D), jnp.bfloat16)
                  for _ in range(4))

    def graded(attn):
        def loss(q, k, v):
            return jnp.sum(attn(q, k, v).astype(jnp.float32) * w)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

    flash = jax.jit(lambda q, k, v: flash_attention(q, k, v, None, True))
    ref = jax.jit(lambda q, k, v: reference_attention(q, k, v, None, True))
    errors = {"flash_fwd": max_err(first_call(flash, q, k, v),
                                   first_call(ref, q, k, v))}
    (_, got), (_, want) = (first_call(graded(flash), q, k, v),
                           first_call(graded(ref), q, k, v))
    errors["flash_grad"] = max(max_err(g, r) for g, r in zip(got, want))

    def paged_err(qk, k_pool, v_pool, tables, lengths):
        """The kernel against its reference lane, largest relative error."""
        return max_err(*(first_call(
            jax.jit(lambda *a, impl=impl: paged_attention(*a, impl=impl)),
            qk, k_pool, v_pool, jnp.asarray(tables), jnp.asarray(lengths))
            for impl in ("pallas", "reference")))

    # ---- paged attention through a page table: decode (K=1), verify (K=4,
    # and the scheduler's default window of serve_spec_k + 1 = 5)
    lengths = np.asarray([0, 15, 16, 100, 333, 511, 777, 1000], np.int32)
    S = len(lengths)
    tables = (1 + np.arange(S * P, dtype=np.int32)).reshape(S, P)
    pool_shape = (S * P + 1, T, H * D)
    k_pool, v_pool = (jax.random.normal(next(keys), pool_shape, jnp.bfloat16)
                      for _ in range(2))
    for K in (1, 4, 5):
        qk = jax.random.normal(next(keys), (S, K, H, D), jnp.bfloat16)
        errors[f"paged_k{K}"] = paged_err(qk, k_pool, v_pool, tables, lengths)

    # ---- the same at Mistral-7B's widths (32 heads over 8 kv heads of 128),
    # contexts 16 to 8192: the decode step's K=1 (with a row that holds no
    # sequence, length -1, among the others) and a prefill chunk's K=512
    H, Hkv, D = 32, 8, 128
    for K, lengths in ((1, [16, 100, 511, -1, 512, 513, 2000, 4096, 8192]),
                       (512, [16, 1000, 4096, 7680])):
        lengths = np.asarray(lengths, np.int32)
        need = -(-(lengths + K) // T)
        tables = np.zeros((len(lengths), int(need.max())), np.int32)
        for s, n in enumerate(need):   # the tail stays the garbage page 0
            tables[s, :n] = 1 + need[:s].sum() + np.arange(n)
        pool_shape = (int(need.sum()) + 1, T, Hkv * D)
        k_pool, v_pool = (jax.random.normal(next(keys), pool_shape,
                                            jnp.bfloat16) for _ in range(2))
        qk = jax.random.normal(next(keys), (len(lengths), K, H, D),
                               jnp.bfloat16)
        errors[f"paged_mistral_k{K}"] = paged_err(qk, k_pool, v_pool, tables,
                                                  lengths)

    bad = {name: e for name, e in errors.items()
           if not (math.isfinite(e) and e < 2e-2)}
    if bad:
        raise RuntimeError(f"kernel disagrees with its reference: {bad} "
                           f"(all: {errors})")
    return {"max_rel_err": errors, "compile_s": round(compile_s, 2),
            **device_report()}


class ChipProbe:
    """One-chip actor for the four-replica check: sees one device, works."""

    def report(self) -> dict:
        import jax
        import jax.numpy as jnp

        require_chip()
        x = jnp.ones((1024, 1024), jnp.bfloat16)
        checksum = float(jax.block_until_ready((x @ x).sum()))
        return {"checksum": checksum, **device_report()}


# --------------------------------------------------------------------------
# the driver's phases


def fit(name: str, seed: int, steps: int, mesh, chips: int) -> dict:
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train import JaxTrainer

    t0 = time.perf_counter()
    result = JaxTrainer(
        train_loop,
        train_loop_config={"seed": seed, "steps": steps, "mesh": mesh},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                     tpus_per_worker=chips),
    ).fit()
    if result.error is not None:
        raise RuntimeError(f"{name}: training failed: {result.error}")
    out = dict(result.metrics)
    losses = out["losses"]
    if not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"{name}: non-finite loss in {losses}")
    if abs(losses[0] - math.log(50257)) > 0.5:
        raise RuntimeError(f"{name}: step-0 loss {losses[0]} is not within "
                           f"0.5 of ln(50257) = {math.log(50257):.2f}")
    if not losses[-1] < losses[1]:
        raise RuntimeError(f"{name}: loss did not fall: {losses}")
    emit(name, seconds=round(time.perf_counter() - t0, 1), **out)
    return out


def kernels_phase(seed: int) -> None:
    import ray_tpu

    t0 = time.perf_counter()
    out = ray_tpu.get(
        ray_tpu.remote(num_tpus=1)(kernels_task).remote(seed), timeout=900)
    emit("kernels", seconds=round(time.perf_counter() - t0, 1), **out)


def serve_phase(seed: int) -> None:
    import ray_tpu
    import ray_tpu.serve as serve
    from ray_tpu._native.build import native_available
    from ray_tpu._private import api
    from ray_tpu.serve.llm import build_app

    t_phase = time.perf_counter()
    handle = serve.run(
        build_app(preset="gpt2_small", max_new_tokens=NEW_TOKENS,
                  temperature=0.0),
        name="llm", route_prefix="/llm", timeout_s=900)
    port = serve.start(http_port=0)
    rng = random.Random(seed)
    prompts = [[rng.randrange(50257) for _ in range(n)] for n in PROMPT_LENS]

    def request(i: int, **extra) -> dict:
        return {"prompt_ids": prompts[i], "max_new_tokens": NEW_TOKENS,
                "temperature": 0.0, **extra}

    # the first request compiles the scheduler's two programs
    t0 = time.perf_counter()
    handle.remote({"prompt_ids": prompts[0][:8], "max_new_tokens": 2,
                   "temperature": 0.0}).result(timeout=900)
    first_request_s = time.perf_counter() - t0

    answers: dict = {}

    def via_handle(i):
        answers[i] = handle.remote(request(i)).result(timeout=600)

    def streamed():  # twin of request 1, consumed chunk by chunk
        answers["stream"] = list(handle.options(stream=True).remote(
            request(1, stream=True)))

    def via_http():  # twin of request 2, through the proxy
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/llm",
            data=json.dumps(request(2)).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=600) as resp:
            answers["http"] = json.loads(resp.read())

    threads = [threading.Thread(target=via_handle, args=(i,))
               for i in range(len(prompts))]
    threads += [threading.Thread(target=streamed),
                threading.Thread(target=via_http)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    requests_s = time.perf_counter() - t0

    missing = [k for k in (*range(len(prompts)), "stream", "http")
               if k not in answers]
    if missing:
        raise RuntimeError(f"serve: requests {missing} did not answer")
    counts = {str(i): answers[i]["num_tokens"] for i in range(len(prompts))}
    counts["stream"] = len(answers["stream"])
    counts["http"] = answers["http"]["num_tokens"]
    if set(counts.values()) != {NEW_TOKENS}:
        raise RuntimeError(f"serve: token counts {counts}, expected "
                           f"{NEW_TOKENS} each")
    if answers["http"]["text"] != answers[2]["text"]:
        raise RuntimeError("serve: the same prompt at temperature 0 gave "
                           "different tokens over HTTP and the handle")

    stats = handle.scheduler_stats.remote().result(timeout=60)
    weights = handle.weights_info.remote().result(timeout=60)
    core = api._core
    store = core._run(core.clients.get(core.supervisor_addr).call(
        "store_stats", timeout=60))
    checks = {
        "platform": stats["platform"] == "tpu",
        "attn_lane": stats.get("attn_lane") == "pallas",
        "compiled_programs": stats["compiled_programs"] == 2,
        "retired": stats["retired"] == len(threads) + 1,
        "weights_in_arena": weights.get("mode") == "published",
        "no_spill": store["total_spills"] == 0,
    }
    if not all(checks.values()):
        raise RuntimeError(f"serve: {checks}; stats={stats} "
                           f"weights={weights} store={store}")
    serve.shutdown()
    emit("serve", seconds=round(time.perf_counter() - t_phase, 1),
         compile_s=round(first_request_s, 2),
         requests_s=round(requests_s, 2), tokens=counts,
         device={"platform": stats["platform"], "kind": stats["device_kind"],
                 "count": stats["device_count"]},
         peak_bytes_in_use=stats["peak_bytes_in_use"],
         attn_lane=stats["attn_lane"],
         compiled_programs=stats["compiled_programs"],
         decode_steps=stats["decode_steps"],
         prefill_chunks=stats["prefill_chunks"],
         prefix_hit_tokens=stats.get("prefix_hit_tokens"),
         weights={k: weights.get(k) for k in ("mode", "nbytes")},
         store_capacity=store["capacity"],
         native={"allocator": native_available("allocator"),
                 "codec": native_available("codec")})


def one_chip(seed: int) -> dict:
    out = fit("train", seed, steps=6, mesh=None, chips=1)
    kernels_phase(seed)
    serve_phase(seed)
    return out["device"]


def four_chips(seed: int) -> dict:
    import ray_tpu

    sharded = fit("train_fsdp2_tp2", seed, steps=3,
                  mesh={"fsdp": 2, "tp": 2}, chips=4)
    single = fit("train_one_device", seed, steps=3, mesh={"dp": 1}, chips=1)
    rel = [abs(a - b) / abs(b)
           for a, b in zip(sharded["losses"], single["losses"])]
    idle = [i for i, b in enumerate(sharded["bytes_in_use"]) if b <= 0]
    if sharded["device"]["count"] != 4 or idle:
        raise RuntimeError(f"sharded step: devices {idle} hold no bytes "
                           f"({sharded['bytes_in_use']})")
    if max(rel) > 1e-2:
        raise RuntimeError(f"sharded and one-device losses differ: {rel}")
    emit("loss_parity", max_rel_diff=max(rel), rel_diff=rel)

    t0 = time.perf_counter()
    probes = [ray_tpu.remote(num_tpus=1)(ChipProbe).remote()
              for _ in range(4)]
    reports = ray_tpu.get([p.report.remote() for p in probes], timeout=600)
    for p in probes:
        ray_tpu.kill(p)
    chips = sorted(r["pinned_chips"] for r in reports)
    if ([r["device"]["count"] for r in reports] != [1] * 4
            or len(set(chips)) != 4
            or len({r["pid"] for r in reports}) != 4):
        raise RuntimeError(f"four one-chip actors did not get four "
                           f"different chips: {reports}")
    emit("four_replicas", seconds=round(time.perf_counter() - t0, 1),
         chips=chips, reports=reports)
    return sharded["device"]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: the sharded step and four one-chip replicas "
                         "only (needs four chips)")
    args = ap.parse_args()

    import ray_tpu
    from ray_tpu._private import compile_cache

    cache_dir = compile_cache.enable()  # workers inherit the variable
    emit("cache", dir=cache_dir, entries=compile_cache.entries(cache_dir))
    info = ray_tpu.init(log_to_driver=False)  # detection has to find chips
    try:
        found = int(ray_tpu.cluster_resources().get("TPU", 0))
        if found < args.chips:
            raise SystemExit(f"chip_smoke: needs {args.chips} TPU chip(s), "
                             f"ray_tpu.init() found {found}")
        device = (four_chips if args.chips == 4 else one_chip)(args.seed)
    finally:
        ray_tpu.shutdown()
        logs = os.path.join(info["session_dir"], "logs")
        shutil.copytree(logs, os.path.join(HERE, "chiprun_out",
                                           "chip_smoke_logs"),
                        dirs_exist_ok=True)
    emit("cache", dir=cache_dir, entries=compile_cache.entries(cache_dir))
    if device["platform"] != "tpu" or device["count"] != args.chips:
        raise SystemExit(f"chip_smoke: worker saw {device}")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
