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
  olmoe    a @ray_tpu.remote(num_tpus=1) task runs the benchmark's
           OLMoE-1B-7B configuration (published widths, 8 layers, bf16): one
           expert layer; the experts' kernel (moe_grouped_matmul) against
           jax.lax.ragged_dot on the stacked weights at a decode step's 256
           pairs and a chunk's 4096 (largest absolute difference printed);
           then two prompts through the paged prefill chunks and
           decode steps, against perfbench/reference/olmoe.py on the experts
           the system chose (routed error), with the share of (token, layer)
           pairs whose set of experts differs from the reference's own and
           how far those were from it (routing margin); expert counts equal
           live rows x 8; then a short mixed batch (12 requests, 8 slots)
           through ContinuousScheduler at those widths: the run-ahead share
           of its decode steps, and the served tokens against decode_step
           on a sequential cache (share equal; each within 3% of its best)
  sala     a @ray_tpu.remote(num_tpus=1) task runs the benchmark's
           MiniCPM-SALA configuration (published widths, 16 layers of two
           kinds, bf16): a prompt of 9300 tokens (past the selection's
           dense_len) through the paged prefill chunks, then 6 decode steps,
           against perfbench/reference/minicpm_sala.py on the blocks the
           system chose (error given the choice, inside the dense cells' 8% /
           6%), with the share of (query, K/V group, block) choices that
           differ from the reference's own and how far below the reference's
           cut the system's blocks scored; a slot without a sequence keeps a
           zero state; then a turn with a chunk as ONE program (a new
           prompt's chunk with the decoding row along) against the chunk
           alone and then the step, inside the cell's limits, and once with
           every row idle (the other slots' states bitwise as they were)
  brumby   a @ray_tpu.remote(num_tpus=1) task runs the benchmark's
           Brumby-14B configuration (published widths, 8 layers, every
           mixer power retention, bf16, no page anywhere): the two kernels
           in float32 at the published head shape (40 query heads on 8
           states) against the attention form token against token (tight:
           1e-3 of the largest output); the chunk kernel ALONE at the
           cell's shape (one row, 512 tokens, bf16, a warm state), timed:
           ``retention_chunk_ms``; then a prompt of 2177 tokens through
           the paged prefill chunks (the last one padded) and 6 decode steps
           beside a second live row, against perfbench/reference/brumby.py
           (inside the cell's limits); a slot without a sequence keeps a
           zero state; then the turn as one program against the two, and
           with every row idle, as in sala
  mellum   a @ray_tpu.remote(num_tpus=1) task runs the benchmark's
           Mellum2-12B-A2.5B configuration (published widths: hidden 2304,
           32 query heads over 4 K/V heads of 128, 64 experts of 896 top-8
           renormalized; 8 layers, six with a window of 1024 and a plain
           RoPE, two full with YaRN; bf16) through the paged programs with a
           page pool a kind: a prompt of 2300 tokens (past the window, its
           last chunk padded) and one of 1020 (below it) whose two chunks
           take the first's decode row along, then 6 decode steps of both
           (the second crosses the window), against
           perfbench/reference/mellum.py GIVEN the system's own routes
           (inside the dense cells' 8% / 6%); every window-pool page wholly
           behind a row's window is off its table and FILLED WITH NaN before
           the program that follows, so whatever read it would show; the
           window pool holds at most window + chunk tokens and a page a slot;
           then the benchmark's own comparison (reference_check with the
           routes given, under the limits of cells/mellum2_shortlong.json) on
           the float8 CONTROL (the reference's forward with every weight
           rounded to float8 e4m3), which has to come out NOT correct
  keye     a @ray_tpu.remote(num_tpus=1) task runs the benchmark's
           Keye-VL-2.0-30B-A3B configuration (published widths: hidden 2048,
           32 query heads over 4 K/V heads of 128 with q/k norm a head, an
           indexer of 16 heads of 64 that picks 2048 tokens a query, 128
           experts of 768 top-8 renormalized; 5 layers, bf16) through the
           paged programs: a prompt of 4113 tokens and one of 2065 (each
           ends on a page's FIRST token, both past topk) whose chunks take
           the first's decode row along, then 6 decode steps of both,
           against perfbench/reference/keye.py GIVEN the system's own routes
           AND the tokens its queries attended (inside the dense cells' 8% /
           6%; every query attends min(t + 1, 2048) tokens; the share of
           choices on which the float32 reference's own indexer parts from
           the bf16 one is reported); every page no table names is FILLED
           WITH NaN in K, V and the index keys before the first program and
           still is after the last; then indexed_select alone on a 512
           chunk's rows at 2k, 16k, 38k and 49k of a 49,664-lane table:
           its milliseconds, the passes a tile ran, its choice a sort's;
           then index_score and indexed_chunk_attention alone at 8k, 20k
           and 49k of the cell's table, pages in order and permuted over
           the pool (indexed_timing);
           then the benchmark's own comparison (reference_check with the
           routes given, under the limits of cells/keye_longctx.json) on
           the float8 CONTROL, which has to come out NOT correct
  glm      a @ray_tpu.remote(num_tpus=1) task runs the benchmark's
           GLM-4.7-Flash configuration (published widths: hidden 2048, 20
           heads of 192 + 64 q/k and 256 v values over a latent of 512 and
           one shared rotated key, a dense layer then 64 experts of 1536
           top-4 sigmoid chosen by score + bias beside a shared one; 1 + 5
           layers, bf16) through the paged programs, which attend the
           latents ABSORBED: a prompt of 4113 tokens and one of 2065 (each
           ends on a page's FIRST token) whose chunks take the first's
           decode row along, a third sequence that SPLICES the first's
           leading 2048 tokens (read table only, as the radix cache hands
           them) and goes on alone, then 6 decode steps of all three,
           against perfbench/reference/glm_moe_lite.py (UNABSORBED) GIVEN
           the system's own routes (inside the dense cells' 8% / 6%); every
           page no table names is FILLED WITH NaN before the first program
           and still is after the last; then a 512 chunk's attention alone
           at 8k, 20k and 55k, absorbed against expanded, and the step's
           kernel; then the benchmark's own comparison (reference_check
           with the routes given, under the limits of
           cells/glm47_flash_longdocs.json) on the float8 CONTROL, which
           has to come out NOT correct
  ouro     a @ray_tpu.remote(num_tpus=1) task runs the benchmark's
           Ouro-2.6B configuration WHOLE (published widths, all 48 layers
           gone through 4 times, bf16) under its cell's deployment (8 slots,
           353 pages x 192 pools: 14.2 GB held): over five seeds a prompt of
           320 tokens and one of 200 whose chunk takes the first's decode
           row along, then 4 steps of both, through the paged programs — ONE
           layer's body under a loop, the kernel told which pool — against
           perfbench/reference/ouro.py on logits; every exit pass the
           reference's (the last); every page no table names NaN in all 192
           pools before and after; and on each of the first THREE seeds' own
           weights and check prompt the float8 CONTROL through the
           benchmark's own comparison under cells/ouro_reason.json's limits,
           which has to come out NOT correct every time
  serve    serve.run(build_app(preset="gpt2_small")) answers 8 concurrent
           requests: six through the handle, one streamed, one over HTTP

``--chips 4`` runs instead, and only, what exists across chips: one worker
holding four chips (fsdp=2 x tp=2) against the same job on one device, the
benchmark's four-chip training step with the compiler options that hide its
``tp`` reduces against the same step without them (loss and gradient norm of
two steps), and four one-chip actors alive together.

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
    from ray_tpu._private import compile_cache

    train.report({"losses": losses, "step_s": step_s,
                  "compile_s": round(compile_s, 2),
                  "mesh": dict(mesh.shape) if mesh is not None else None,
                  # what this worker traced, lowered and compiled or loaded
                  "compiles": compile_cache.summary(),
                  **device_report()})


OVERLAP_CELL = "mistral7b_train_4chip"


def overlap_loop(config: dict) -> None:
    """JaxTrainer loop on four chips: the step of the benchmark's four-chip
    training cell (its configuration, mesh, optimizer, batch and traffic) as
    ``make_train_step`` compiles it, with ``training.OVERLAP_REDUCES``, and
    the same step without them, two steps each from the same seeded state
    on the same batches, one after the other (two states do not fit). The
    options are the chip compiler's private ones and a sibling of theirs
    compiles another gradient (PERF.md 6, PR 54): this is what would see
    it. Reports each step's loss and gradient norm, and how many of the
    loop's ``tp`` reduces each compiled text holds and hides."""
    from unittest import mock

    import jax

    from perfbench.lib import configs, traffic
    from perfbench.lib import manifest as manifest_lib
    from ray_tpu import train
    from ray_tpu.models import training
    from ray_tpu.parallel.mesh import (MeshSpec, build_mesh,
                                       collective_census, data_sharding)

    require_chip()
    manifest = manifest_lib.load()
    entry = manifest_lib.workload(manifest, OVERLAP_CELL)
    cell = manifest_lib.read_json(manifest, "cells", OVERLAP_CELL)
    mix = manifest_lib.read_json(manifest, "traffic", entry["traffic"])
    hp = manifest_lib.config(manifest, entry["config"])
    cfg = configs.build_program_config(*configs.program_overrides(
        hp, manifest_lib.read_json_from_bench("families", hp["model_type"])))
    mesh = build_mesh(MeshSpec.of(**cell["mesh"]))
    ocfg = training.OptimizerConfig(**cell["optimizer"])
    batches = [{"tokens": jax.device_put(tokens, data_sharding(mesh))}
               for tokens, _ in zip(traffic.token_batches(
                   mix, config["seed"], int(cell["batch"]), cfg.vocab_size),
                   range(2))]

    def two_steps(options: bool) -> dict:
        state, tx = training.init_train_state(
            cfg, ocfg, jax.random.PRNGKey(config["seed"]), mesh)
        t0 = time.perf_counter()
        with mock.patch.dict(training.OVERLAP_REDUCES,
                             clear=not options):
            compiled = training.make_train_step(cfg, tx, mesh).lower(
                state, batches[0]).compile()
        out = {"compile_s": round(time.perf_counter() - t0, 1),
               "loss": [], "grad_norm": []}
        for batch in batches:
            state, metrics = compiled(state, batch)
            out["loss"].append(float(metrics["loss"]))
            out["grad_norm"].append(float(metrics["grad_norm"]))
        out["tp_reduces"] = collective_census(compiled.as_text(), mesh)[
            ("loop", "all-reduce", ("tp",))]
        return out

    train.report({"with": two_steps(True), "without": two_steps(False),
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


def olmoe_task(seed: int) -> dict:
    """The OLMoE-width checks (ISSUE 26): the system in bf16 against the
    plain float32 reference given the system's own routes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.lib import configs, weights
    from perfbench.lib import manifest as manifest_lib
    from perfbench.reference import olmoe as ref
    from ray_tpu.models.decode import (StepRows, init_paged_caches,
                                       paged_decode_step,
                                       paged_prefill_into_slot)
    from ray_tpu.ops.moe import expert_mlp, moe_layer, tile_sizes

    require_chip()
    manifest = manifest_lib.load()
    hp = manifest_lib.config(manifest, "olmoe_1b_7b_l8")
    cfg = configs.build_program_config(*configs.program_overrides(
        hp, manifest_lib.read_json_from_bench("families", hp["model_type"])))
    L, E, k = cfg.num_layers, cfg.moe_num_experts, cfg.moe_top_k
    params = weights.make_params(cfg, seed)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 8))
    out = {}

    def rel(got, want):
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        return {"max": float(np.abs(got - want).max() / np.abs(want).max()),
                "rms": float(np.sqrt(((got - want) ** 2).mean()
                                     / (want ** 2).mean()))}

    # ---- one expert layer (layer 0's weights, as they lie: in the stack
    # or apart), a chunk's rows of which the last 112 are padding
    stacked = "mlp" in params["blocks"]
    mlp = params["blocks"]["mlp" if stacked else "0"]
    mlp = mlp if stacked else mlp["mlp"]
    x = jax.random.normal(next(keys), (1, 512, cfg.embed_dim), cfg.dtype)
    valid = jnp.arange(512)[None] < 400
    y, _, counts, routes = jax.jit(lambda p, x, v: moe_layer(
        p, x, num_experts=E, top_k=k, renormalize=cfg.moe_renormalize,
        dtype=cfg.dtype, valid=v, layer=0 if stacked else None))(
        mlp, x, valid)
    with jax.default_matmul_precision("highest"):
        h = x.astype(jnp.float32)
        router = mlp["w_router"][0] if stacked else mlp["w_router"]
        probs = jax.nn.softmax(h @ router.astype(jnp.float32), -1)
        w = ref.token_weights(probs, routes, k, cfg.moe_renormalize)
        want = jnp.zeros_like(h)
        add = jax.jit(ref.add_expert)
        for e in range(E):
            want = add(want, h, w, mlp, (0, e) if stacked else (e,))
    flips, margin = ref.routing_margin(probs[None, :, :400],
                                       routes[None, :, :400])
    out["layer"] = {"routed_err": rel(y[:, :400], want[:, :400]),
                    "padding_is_zero": bool((y[:, 400:] == 0).all()),
                    "rows_routed": int(counts.sum()), "live_rows_x_k": 400 * k,
                    "flip_share": flips, "margin": margin}

    # ---- the experts' kernel (moe_grouped_matmul) against the compiler's
    # ragged_dot, on the model's own experts as they lie (the LAST layer of
    # the stack, by its offset), at the pairs a decode step of 32 slots with
    # 22 live and a full 512-token chunk bring
    out["kernel"] = {}
    if stacked:
        first = (L - 1) * E
        w = [mlp[name].reshape(-1, *mlp[name].shape[2:]).astype(cfg.dtype)
             for name in ("w_gate", "w_up", "w_down")]
        draw = np.random.default_rng(seed + 1)
        # the stack is an ARGUMENT: closed over it would be a constant
        kernel = jax.jit(lambda xs, wg, wu, wd, g: expert_mlp(
            xs, wg, wu, wd, g, first))

        def plain(xs, wg, wu, wd, g):
            sl = [a[first:first + E] for a in (wg, wu, wd)]
            hidden = jax.nn.silu(jax.lax.ragged_dot(xs, sl[0], g)) * (
                jax.lax.ragged_dot(xs, sl[1], g))
            return jax.lax.ragged_dot(hidden, sl[2], g)

        for name, slots, live in (("decode", 32, 22), ("chunk", 512, 512)):
            groups = draw.multinomial(live * k,
                                      draw.dirichlet(np.full(E, 2.0)))
            xs = jax.random.normal(next(keys), (slots * k, cfg.embed_dim),
                                   cfg.dtype)
            g = jnp.asarray(groups, jnp.int32)
            if "moe_grouped_matmul" not in kernel.lower(xs, *w, g).as_text():
                raise RuntimeError("expert_mlp is not the kernel on the chip")
            got_k, want_k = (np.asarray(a[:live * k], np.float32) for a in (
                kernel(xs, *w, g), jax.jit(plain)(xs, *w, g)))
            out["kernel"][name] = {
                "pairs": slots * k, "experts_hit": int((groups > 0).sum()),
                "tiles": list(tile_sizes(slots * k, E, cfg.embed_dim,
                                         cfg.mlp_dim, 2)),
                "max_abs_diff": float(np.abs(got_k - want_k).max()),
                "largest_value": float(np.abs(want_k).max())}

    # ---- the 8-layer model through the paged programs: two prompts (one
    # of two chunks) prefilled into slots 0 and 5 of 8 — slot 5's two chunks
    # TAKE SLOT 0'S DECODE ROW ALONG, as a turn of the scheduler does
    # (ISSUE 40) — then 6 decode steps of both
    S, C, T, P = 8, 512, 16, 64
    caches = init_paged_caches(cfg, S * P + 1, T, P)
    tables = (1 + np.arange(S * P, dtype=np.int32)).reshape(S, P)
    prefill = jax.jit(lambda *a: paged_prefill_into_slot(
        cfg, *a, attn="pallas", moe_info=True, logits=True),
        donate_argnums=(6,))
    step = jax.jit(lambda *a: paged_decode_step(
        cfg, *a, attn="pallas", moe_info=True, logits=True),
        donate_argnums=(6,))
    ids = jnp.zeros(S, jnp.int32)  # the programs' own ids, temperature 0
    rng = np.random.default_rng(seed)
    prompts = {0: rng.integers(1, cfg.vocab_size, 320).tolist(),
               5: rng.integers(1, cfg.vocab_size, 700).tolist()}
    got = {s: [] for s in prompts}      # logits at the served positions
    taken = {s: [] for s in prompts}    # routes [L, tokens, k]
    fed = {s: [] for s in prompts}
    rows_routed = live_rows = 0
    active = np.zeros(S, np.int32)
    cursors = np.zeros(S, np.int32)  # the caller's: the pool keeps none
    greedy = (np.zeros(S, np.float32), np.zeros(S, np.uint32))
    ids_are_argmax = True  # what a program hands the next is what the host fed

    def feed():
        """The decoding rows' next tokens as the host would feed them, and
        whether the device's own vector holds just those."""
        toks = np.zeros(S, np.int32)
        for s in np.flatnonzero(active):
            fed[s].append(int(got[s][-1].argmax()))
            toks[s] = fed[s][-1]
        return [int(t) for t in np.asarray(ids)] == list(toks)

    for s, prompt in prompts.items():
        for c0 in range(0, len(prompt), C):
            chunk = prompt[c0:c0 + C]
            real = len(chunk)
            ids_are_argmax &= feed()
            ids, caches, moe, logits = prefill(
                params, jnp.asarray([chunk + [0] * (C - real)], jnp.int32),
                np.int32(real), np.int32(c0), jnp.asarray(tables[s]),
                jnp.asarray(tables[s]), caches, ids,
                np.int32(s if c0 + C >= len(prompt) else -1), np.float32(0),
                np.uint32(0), StepRows(active.copy(), cursors.copy(), tables,
                                       tables, *greedy))
            routes = np.asarray(moe["routes"])[:, 0]  # chunk rows, then step
            taken[s].append(routes[:, :real])
            rows_routed += int(np.asarray(moe["counts"]).sum())
            live_rows += real + int(active.sum())
            for row in np.flatnonzero(active):
                got[row].append(np.asarray(logits[1 + row], np.float32))
                taken[row].append(routes[:, C + row:C + row + 1])
            cursors = cursors + active
            cursors[s] += real
        got[s].append(np.asarray(logits[0], np.float32))
        active[s] = 1
    assert len(fed[0]) == 2 and not fed[5]  # slot 0 decoded beside 5's chunks
    for _ in range(6):
        ids_are_argmax &= feed()
        ids, caches, moe, logits = step(
            params, ids, jnp.asarray(active), cursors, jnp.asarray(tables),
            jnp.asarray(tables), caches, *greedy)
        cursors = cursors + active
        rows_routed += int(np.asarray(moe["counts"]).sum())
        live_rows += len(prompts)
        for s in prompts:
            got[s].append(np.asarray(logits[s], np.float32))
            taken[s].append(np.asarray(moe["routes"])[:, s])
    errs, flip_share, margins = [], [], []
    for s, prompt in prompts.items():
        tokens = jnp.asarray([prompt + fed[s]], jnp.int32)
        routes = jnp.asarray(np.concatenate(taken[s], axis=1))[:, None]
        want, probs = ref.forward_and_router(params, tokens, hp, routes)
        errs.append(rel(np.stack(got[s][:-1]),
                        np.asarray(want[0])[len(prompt) - 1:-1]))
        f, m = ref.routing_margin(probs, routes)
        flip_share.append(f)
        margins.append(m)
    out["model"] = {"routed_err_max": max(e["max"] for e in errs),
                    "routed_err_rms": max(e["rms"] for e in errs),
                    "flip_share": flip_share, "margin": margins,
                    "rows_routed": rows_routed,
                    "live_rows_x_k_x_layers": live_rows * k * L,
                    "ids_are_argmax": ids_are_argmax}
    bad = []
    if not ids_are_argmax:
        bad.append("a program's id is not the argmax of its logits")
    if out["layer"]["rows_routed"] != out["layer"]["live_rows_x_k"] \
            or rows_routed != live_rows * k * L:
        bad.append("a row was dropped or a dead row counted")
    if not out["layer"]["padding_is_zero"]:
        bad.append("a padded row came out of the expert layer non-zero")
    # both sum bf16 products in float32; the kernel rounds silu(gate) * up
    # once where XLA rounds gate, up and their product: a few roundings of
    # the largest value, 2^-8 each
    for name, read in out["kernel"].items():
        if not read["max_abs_diff"] <= 2.0 ** -5 * read["largest_value"]:
            bad.append(f"the experts' kernel at the {name} pairs is not "
                       "ragged_dot's")
    # the dense serving cells' tolerance (bf16 through the layers: 8% of
    # the largest logit, 6% root-mean-square; read there at most 4.3 / 3.8)
    if out["layer"]["routed_err"]["max"] > 0.03 \
            or out["model"]["routed_err_max"] > 0.08 \
            or out["model"]["routed_err_rms"] > 0.06:
        bad.append("routed error above the dense cells' tolerance")
    # another expert only where rounding can: bf16 hidden states move a
    # router probability (about 1/64 to 1/10) by well under 0.004
    if max(margins + [margin]) > 4e-3:
        bad.append("an expert was taken that the reference scores far "
                   "below its 8th")
    out["scheduler"] = served_batch(cfg, params, seed)
    if out["scheduler"]["runahead_share"] < 0.8 \
            or out["scheduler"]["discarded_rows"] \
            or out["scheduler"]["compiled_programs"] != 2 \
            or not out["scheduler"]["fused_turns"]:
        bad.append("the decode loop did not run a step ahead, paid for it, "
                   "or no chunk took a decode row along")
    if out["scheduler"]["worst_served_gap"] > 0.10:
        bad.append("a served token is not among the sequential cache's "
                   "best")
    if bad:
        raise RuntimeError(f"olmoe: {bad}: {out}")
    return {**out, **device_report()}


def fused_turn(prefill, step, params, caches, ids, tables, cursors, active,
               into: int, vocab: int, chunk: int, tol: dict,
               seed: int) -> dict:
    """A turn with a chunk as ONE program against the two it replaced (ISSUE
    44), on the chip and on the same inputs: a new prompt's first chunk
    into the free slot ``into`` while the rows of ``active`` decode — the
    chunk's program with the step's rows along against the chunk alone and
    then the step — and once more with EVERY row idle, which must leave
    every other slot's states bitwise alone. ``prefill`` / ``step``: the
    task's jitted programs (donated caches, ``logits=True``); ``tables``
    None for a model without pages. Raises outside ``tol`` (a cell's
    ``check_tolerance``); returns what it read."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.decode import StepRows

    S = len(active)
    copy = lambda: jax.tree.map(jnp.copy, caches)
    rows = lambda t: (None, None) if tables is None else (jnp.asarray(t),) * 2
    states = lambda cs: [a for c in cs if hasattr(c, "arrays")
                         for a in c.arrays().values()]
    tokens = np.random.default_rng(seed).integers(
        1, vocab, (1, chunk)).astype(np.int32)
    args = (params, tokens, np.int32(chunk), np.int32(0),
            *rows(None if tables is None else tables[into]))
    tail = (np.int32(into), np.float32(0), np.uint32(0))
    greedy = (np.zeros(S, np.float32), np.zeros(S, np.uint32))
    alone = prefill(*args, copy(), ids, *tail, None, np.int32(into))
    first = np.asarray(alone[2], np.float32)
    two = step(params, alone[0], jnp.asarray(active), cursors, *rows(tables),
               alone[1], *greedy)

    def one(live):
        return prefill(*args, copy(), ids, *tail, StepRows(
            jnp.asarray(live), cursors, *rows(tables), *greedy),
            np.int32(into))

    def off(got, want):
        got, want = (np.asarray(x, np.float32) for x in (got, want))
        scale = np.abs(want).max()
        return {"max": float(np.abs(got - want).max() / scale),
                "rms": float(np.sqrt(((got - want) ** 2).mean()
                                     / (want ** 2).mean())),
                "margin": float(max(
                    (g.max() - g[w.argmax()]) / scale
                    for g, w in zip(got.reshape(-1, got.shape[-1]),
                                    want.reshape(-1, want.shape[-1]))))}

    live = np.flatnonzero(active)
    fused = one(active)
    out = {"first_token": off(fused[2][0], first),
           "live_rows": off(fused[2][1 + live], two[2][live]),
           "ids_differing": int((np.asarray(fused[0])
                                 != np.asarray(two[0])).sum()),
           "states": max(float(np.abs(np.asarray(a) - np.asarray(b)).max()
                               / max(np.abs(np.asarray(b)).max(), 1e-30))
                         for a, b in zip(states(fused[1]), states(two[1])))}
    del fused
    idle = one(np.zeros_like(active))
    out["idle_first_token"] = off(idle[2][0], first)
    others = [r for r in range(S) if r != into]
    bad = []
    if not all(np.array_equal(np.asarray(a)[others], np.asarray(b)[others])
               for a, b in zip(states(idle[1]), states(caches))):
        bad.append("a row that is not live did not keep its state bitwise")
    if not np.array_equal(np.asarray(idle[0])[others],
                          np.asarray(ids)[others]):
        bad.append("a row that is not live was given a token")
    for name in ("first_token", "live_rows", "idle_first_token"):
        e = out[name]
        if (e["max"] > tol["logit_err"] or e["rms"] > tol["logit_rms_err"]
                or e["margin"] > tol["served_margin"]):
            bad.append(f"{name}: one program is outside the cell's limits "
                       "of the two")
    if bad:
        raise RuntimeError(f"fused turn: {bad}: {out}")
    return out


def sala_task(seed: int) -> dict:
    """The MiniCPM-SALA-width checks (ISSUE 32): the system in bf16 — paged
    prefill chunks, then decode steps, on a prompt past ``dense_len`` so
    that the selection is at work — against the plain float32 reference
    GIVEN the system's own choice of blocks, and how far that choice lies
    from the reference's own."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.lib import configs, weights
    from perfbench.lib import manifest as manifest_lib
    from perfbench.reference import minicpm_sala as ref
    from ray_tpu.models.decode import (init_paged_caches, paged_decode_step,
                                       paged_prefill_into_slot)

    require_chip()
    manifest = manifest_lib.load()
    hp = manifest_lib.config(manifest, "minicpm_sala_l16")
    tol = manifest_lib.read_json(manifest, "cells",
                                 "minicpm_sala_longdoc")["check_tolerance"]
    cfg = configs.build_program_config(*configs.program_overrides(
        hp, manifest_lib.read_json_from_bench("families", hp["model_type"])))
    params = weights.make_params(cfg, seed)
    S, C, T, P, slot, steps = 4, 512, 16, 640, 2, 6
    caches = init_paged_caches(cfg, S * P + 1, T, P, slots=S)
    tables = (1 + np.arange(S * P, dtype=np.int32)).reshape(S, P)
    prefill = jax.jit(lambda *a: paged_prefill_into_slot(
        cfg, *a, attn="pallas", logits=True, selected=True),
        donate_argnums=(6,))
    step = jax.jit(lambda *a: paged_decode_step(
        cfg, *a, attn="pallas", logits=True, selected=True),
        donate_argnums=(6,))
    prompt = np.random.default_rng(seed).integers(
        1, cfg.vocab_size, 9300).tolist()
    ids = jnp.zeros(S, jnp.int32)
    chose, got, chunk_s = [], [], []
    for c0 in range(0, len(prompt), C):
        chunk = prompt[c0:c0 + C]
        t0 = time.perf_counter()
        ids, caches, logits, picked = prefill(
            params, jnp.asarray([chunk + [0] * (C - len(chunk))], jnp.int32),
            np.int32(len(chunk)), np.int32(c0), jnp.asarray(tables[slot]),
            jnp.asarray(tables[slot]), caches, ids,
            np.int32(slot if c0 + C >= len(prompt) else -1), np.float32(0),
            np.uint32(0), None, np.int32(slot))
        chose.append(np.asarray(picked)[:, 0, :len(chunk)])
        chunk_s.append(time.perf_counter() - t0)
    got.append(np.asarray(logits, np.float32))
    active = np.zeros(S, np.int32)
    active[slot] = 1
    cursors = np.zeros(S, np.int32)
    cursors[slot] = len(prompt)
    fed, step_s = [], []
    for _ in range(steps):
        fed.append(int(got[-1].argmax()))
        t0 = time.perf_counter()
        ids, caches, logits, picked = step(
            params, ids, jnp.asarray(active), cursors, jnp.asarray(tables),
            jnp.asarray(tables), caches, np.zeros(S, np.float32),
            np.zeros(S, np.uint32))
        chose.append(np.asarray(picked)[:, slot])
        got.append(np.asarray(logits[slot], np.float32))
        step_s.append(time.perf_counter() - t0)
        cursors = cursors + active
    # another slot's states were never touched
    idle_states = float(max(np.abs(np.asarray(c.s[0])).max()
                            for c in caches if hasattr(c, "s")))
    # the turn as one program: a new prompt's chunk into slot 0 beside the
    # decoding row, against the two programs above
    turn = fused_turn(prefill, step, params, caches, ids, tables, cursors,
                      active, 0, cfg.vocab_size, C, tol, seed)
    del caches
    tokens = jnp.asarray([prompt + fed], jnp.int32)
    mine = np.concatenate(chose, axis=1)[:, None]   # [layers, 1, S, Hkv, NB]
    want, own = ref.forward(params, tokens, hp, selected=mine,
                            return_selected=True)
    want = want[0, len(prompt) - 1:-1]
    have = np.stack(got[:-1])
    err = {"max": float(np.abs(have - want).max() / np.abs(want).max()),
           "rms": float(np.sqrt(((have - want) ** 2).mean()
                                / (want ** 2).mean()))}
    # where the two choices differ, how far below the reference's cut the
    # system's block scored (a score is a sum of 16 heads' probabilities)
    differ = chosen = 0
    margins = []
    for layer, (theirs, score, cand) in enumerate(own):
        ours = mine[layer][..., :theirs.shape[-1]]
        differ += int((ours != theirs).sum())
        chosen += int(theirs.sum())
        ranked = np.where(np.logical_and(theirs, cand), score, np.inf)
        cut = ranked.min(axis=-1, keepdims=True)
        extra = np.logical_and(ours, np.logical_not(theirs))
        if extra.any():
            margins.append(float((cut - score)[extra].max()))
    out = {"given_err": err, "choices_differing": differ,
           "choices": chosen, "differing_share": differ / max(chosen, 1),
           "worst_margin": max(margins, default=0.0),
           "idle_slot_state": idle_states, "fused_turn": turn,
           "chunk_ms_by_position": [round(1e3 * x, 1) for x in chunk_s],
           "step_ms": [round(1e3 * x, 1) for x in step_s]}
    bad = []
    # the dense serving cells' tolerance (bf16 through 16 layers)
    if err["max"] > 0.08 or err["rms"] > 0.06:
        bad.append("error given the choice above the dense cells' tolerance")
    if idle_states != 0.0:
        bad.append("a slot without a sequence has a state")
    if bad:
        raise RuntimeError(f"sala: {bad}: {out}")
    return {**out, **device_report()}


def retention_chunk_ms(cfg, seed: int, calls: int = 20) -> float:
    """The retention chunk kernel ALONE at the cell's shape — one row, a
    512-token chunk, every query head over its K/V heads, bf16, on the state
    a first chunk left — in milliseconds a call, ``calls`` timed behind a
    warm-up (the call's glue around the kernel is in it: the rows cut into
    blocks, the gates' sums)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import power_retention as pr

    H, G, D, C = cfg.num_heads, cfg.kv_heads, cfg.head_dim, 512
    ks = jax.random.split(jax.random.key(seed & 0x7FFFFFFF), 4)
    q, k, v = (jax.random.normal(key, (1, C, heads, D), jnp.bfloat16)
               for key, heads in zip(ks, (H, G, G)))
    gate = jax.nn.log_sigmoid(jax.random.normal(ks[3], (1, C, G)) + 2.0)
    run = jax.jit(lambda *a: pr.power_retention_chunk(*a, C))
    state = run(q, k, v, gate, *(
        jnp.zeros(shape, jnp.float32)
        for shape in pr.state_shapes(1, G, D).values()))[1:]
    jax.block_until_ready(run(q, k, v, gate, *state))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = run(q, k, v, gate, *state)
    jax.block_until_ready(out)
    return round((time.perf_counter() - t0) / calls * 1e3, 4)


def brumby_task(seed: int) -> dict:
    """The Brumby-width checks (ISSUE 43): the two retention kernels in
    float32 against the attention form, then the system in bf16 — paged
    prefill chunks and decode steps, no page table — against the plain
    float32 reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.lib import configs, weights
    from perfbench.lib import manifest as manifest_lib
    from perfbench.reference import brumby as ref
    from ray_tpu.models.decode import (init_paged_caches, paged_decode_step,
                                       paged_prefill_into_slot)
    from ray_tpu.ops import power_retention as pr

    require_chip()
    manifest = manifest_lib.load()
    hp = manifest_lib.config(manifest, "brumby_14b_l8")
    tol = manifest_lib.read_json(manifest, "cells",
                                 "brumby_longgen")["check_tolerance"]
    cfg = configs.build_program_config(*configs.program_overrides(
        hp, manifest_lib.read_json_from_bench("families", hp["model_type"])))
    # ---- the kernels, float32, against the attention form
    H, G, D, n = cfg.num_heads, cfg.kv_heads, cfg.head_dim, 300
    ks = jax.random.split(jax.random.key(seed & 0x7FFFFFFF), 4)
    q = jax.random.normal(ks[0], (1, n, H, D))
    k = jax.random.normal(ks[1], (1, n, G, D))
    v = jax.random.normal(ks[2], (1, n, G, D))
    gate = jax.nn.log_sigmoid(jax.random.normal(ks[3], (1, n, G)) + 2.0)

    @jax.jit
    def attention_form(q, k, v, gate):
        with jax.default_matmul_precision("highest"):
            kk, vv = (jnp.repeat(x, H // G, axis=2) for x in (k, v))
            run = jnp.cumsum(jnp.repeat(gate, H // G, axis=2), axis=1)[0].T
            score = jnp.einsum("qhd,khd->hqk", q[0], kk[0]) / math.sqrt(D)
            seen = jnp.tril(jnp.ones((n, n), bool))
            a = score ** 2 * jnp.exp(jnp.where(
                seen, run[:, :, None] - run[:, None, :], -jnp.inf))
            return (jnp.einsum("hqk,khd->qhd", a, vv[0])
                    / (a.sum(-1).T[..., None] + pr.EPS))

    want = np.asarray(attention_form(q, k, v, gate))
    zero = [jnp.zeros(shape, jnp.float32)
            for shape in pr.state_shapes(1, G, D).values()]
    head = 256  # a chunk of two blocks, then steps on the state it left
    o, s, z = pr.power_retention_chunk(q[:, :head], k[:, :head], v[:, :head],
                                       gate[:, :head], *zero, head - 7)
    rel = lambda a, b: float(np.abs(np.asarray(a) - b).max()
                             / np.abs(want).max())
    kernel_err = {"chunk": rel(o[0, :head - 7], want[:head - 7])}
    stepped = []
    for t in range(head - 7, n):
        o1, s, z = pr.power_retention_step(
            q[:, t], k[:, t], v[:, t], gate[:, t], s, z,
            jnp.ones((1,), jnp.int32))
        stepped.append(np.asarray(o1[0]))
    kernel_err["steps_behind_it"] = rel(np.stack(stepped), want[head - 7:])
    del q, k, v, o, s, z, zero
    chunk_ms = retention_chunk_ms(cfg, seed)
    # ---- the paged programs, bf16, against the reference
    params = weights.make_params(cfg, seed)
    S, C, slot, other, steps = 4, 512, 2, 1, 6
    caches = init_paged_caches(cfg, 1, C, 1, slots=S)
    prefill = jax.jit(lambda *a: paged_prefill_into_slot(
        cfg, *a, attn="pallas", logits=True), donate_argnums=(6,))
    step = jax.jit(lambda *a: paged_decode_step(
        cfg, *a, attn="pallas", logits=True), donate_argnums=(6,))
    rng = np.random.default_rng(seed)
    # lengths of whole blocks of 128 and one token: each prompt's last
    # token is a block's first, which reads the state ACROSS blocks (the
    # chunk kernel's one product on rounded pairs) at its largest weight
    prompts = {slot: rng.integers(1, cfg.vocab_size, 2177).tolist(),
               other: rng.integers(1, cfg.vocab_size, 641).tolist()}
    ids = jnp.zeros(S, jnp.int32)
    first, chunk_s = {}, []
    for row, prompt in prompts.items():
        for c0 in range(0, len(prompt), C):
            chunk = prompt[c0:c0 + C]
            t0 = time.perf_counter()
            ids, caches, logits = prefill(
                params, jnp.asarray([chunk + [0] * (C - len(chunk))],
                                    jnp.int32),
                np.int32(len(chunk)), np.int32(c0), None, None, caches, ids,
                np.int32(row if c0 + C >= len(prompt) else -1),
                np.float32(0), np.uint32(0), None, np.int32(row))
            first[row] = np.asarray(logits, np.float32)
            chunk_s.append(time.perf_counter() - t0)
    got = {row: [first[row]] for row in prompts}
    fed = {row: [] for row in prompts}
    active = np.zeros(S, np.int32)
    active[[slot, other]] = 1
    cursors = np.zeros(S, np.int32)
    for row, prompt in prompts.items():
        cursors[row] = len(prompt)
    step_s = []
    for _ in range(steps):
        for row in prompts:
            fed[row].append(int(got[row][-1].argmax()))
        t0 = time.perf_counter()
        ids, caches, logits = step(
            params, ids, jnp.asarray(active), cursors, None, None, caches,
            np.zeros(S, np.float32), np.zeros(S, np.uint32))
        for row in prompts:
            got[row].append(np.asarray(logits[row], np.float32))
        step_s.append(time.perf_counter() - t0)
        cursors = cursors + active
    served_on_device = np.asarray(ids)
    idle_states = float(max(np.abs(np.asarray(a[0])).max() for c in caches
                            for a in c.arrays().values()))
    # the turn as one program: a new prompt's chunk into slot 0 beside the
    # two decoding rows, against the two programs above
    turn = fused_turn(prefill, step, params, caches, ids, None, cursors,
                      active, 0, cfg.vocab_size, C, tol, seed)
    del caches
    err = {}
    for row, prompt in prompts.items():
        want = ref.forward(params, jnp.asarray([prompt + fed[row]],
                                               jnp.int32),
                           hp)[0, len(prompt) - 1:-1]
        have = np.stack(got[row][:-1])
        err[f"slot{row}"] = {
            "max": float(np.abs(have - want).max() / np.abs(want).max()),
            "rms": float(np.sqrt(((have - want) ** 2).mean()
                                 / (want ** 2).mean())),
            "margin": max(float((w.max() - w[t]) / np.abs(want).max())
                          for w, t in zip(want, fed[row]))}
        del want
    out = {"kernel_err_f32": kernel_err, "retention_chunk_ms": chunk_ms,
           "paged_err": err,
           "idle_slot_state": idle_states, "fused_turn": turn,
           "ids_on_device": [int(served_on_device[r]) for r in prompts],
           "chunk_ms": [round(1e3 * x, 1) for x in chunk_s],
           "step_ms": [round(1e3 * x, 1) for x in step_s]}
    bad = []
    if max(kernel_err.values()) > 1e-3:
        bad.append("a retention kernel is off the attention form in float32")
    for row, e in err.items():
        if (e["max"] > tol["logit_err"] or e["rms"] > tol["logit_rms_err"]
                or e["margin"] > tol["served_margin"]):
            bad.append(f"{row}: the paged programs are outside the cell's "
                       "limits")
    if idle_states != 0.0:
        bad.append("a slot without a sequence has a state")
    if bad:
        raise RuntimeError(f"brumby: {bad}: {out}")
    return {**out, **device_report()}


def mellum_task(seed: int) -> dict:
    """The Mellum-width checks (ISSUE 46): the paged chunk, step and fused
    turn in bf16, a page pool a kind of layer, against the plain float32
    reference given the system's own routes, with what lies behind a window
    poisoned."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.lib import configs, weights
    from perfbench.lib import manifest as manifest_lib
    from perfbench.reference import mellum as ref
    from perfbench.reference.olmoe import routing_margin
    from ray_tpu.models.decode import (StepRows, init_paged_caches,
                                       paged_decode_step,
                                       paged_prefill_into_slot)
    from ray_tpu.models.transformer import ATTENTION, SLIDING

    require_chip()
    manifest = manifest_lib.load()
    hp = manifest_lib.config(manifest, "mellum2_12b_l8")
    cfg = configs.build_program_config(*configs.program_overrides(
        hp, manifest_lib.read_json_from_bench("families", hp["model_type"])))
    W, L, k = cfg.sliding_window, cfg.num_layers, cfg.moe_top_k
    params = weights.make_params(cfg, seed)
    S, C, T, P = 4, 512, 16, 192
    # the window pool is handed out once and never again: a released page
    # stays poisoned to the end
    caches = init_paged_caches(cfg, S * P + 1, T, P, window_pages=300)
    full = np.zeros((S, P), np.int32)
    window = np.zeros((S, P), np.int32)
    held = {s: [] for s in range(S)}
    handed = [0]
    peak_held = released = 0

    def ensure(caches, s, cursor, upto):
        """Slot ``s``'s pages for a program whose first row is at
        ``cursor`` and whose last real row is before ``upto``, as the
        scheduler sees to them."""
        nonlocal peak_held, released
        need = -(-upto // T)
        full[s, :need] = 1 + s * P + np.arange(need)
        first_kept = max(cursor - W + 1, 0) // T
        gone = [j for j in held[s] if j < first_kept]
        if gone:
            pages = window[s, gone].copy()
            window[s, gone] = 0
            held[s] = [j for j in held[s] if j >= first_kept]
            released += len(gone)
            caches = [dataclasses.replace(
                c, k=c.k.at[pages].set(jnp.nan), v=c.v.at[pages].set(jnp.nan))
                if kind == SLIDING else c for c, kind in zip(caches,
                                                             cfg.kinds)]
        for j in range(max(held[s], default=-1) + 1, need):
            if j >= first_kept:
                handed[0] += 1
                window[s, j] = handed[0]
                held[s].append(j)
        peak_held = max(peak_held, len(held[s]))
        return caches

    def tables(s=None):
        rows = slice(None) if s is None else s
        both = {ATTENTION: jnp.asarray(full[rows]),
                SLIDING: jnp.asarray(window[rows])}
        return both, both

    prefill = jax.jit(lambda *a: paged_prefill_into_slot(
        cfg, *a, attn="pallas", moe_info=True, logits=True),
        donate_argnums=(6,))
    step = jax.jit(lambda *a: paged_decode_step(
        cfg, *a, attn="pallas", moe_info=True, logits=True),
        donate_argnums=(6,))
    ids = jnp.zeros(S, jnp.int32)
    rng = np.random.default_rng(seed)
    prompts = {0: rng.integers(1, cfg.vocab_size, 2300).tolist(),
               2: rng.integers(1, cfg.vocab_size, 1020).tolist()}
    got = {s: [] for s in prompts}
    taken = {s: [] for s in prompts}
    fed = {s: [] for s in prompts}
    active = np.zeros(S, np.int32)
    cursors = np.zeros(S, np.int32)
    greedy = (np.zeros(S, np.float32), np.zeros(S, np.uint32))
    rows_routed = live_rows = 0

    def feed():
        for s in np.flatnonzero(active):
            fed[s].append(int(got[s][-1].argmax()))

    for s, prompt in prompts.items():
        for c0 in range(0, len(prompt), C):
            chunk = prompt[c0:c0 + C]
            real = len(chunk)
            feed()
            caches = ensure(caches, s, c0, c0 + real)
            for row in np.flatnonzero(active):
                caches = ensure(caches, row, int(cursors[row]),
                                int(cursors[row]) + 1)
            ids, caches, moe, logits = prefill(
                params, jnp.asarray([chunk + [0] * (C - real)], jnp.int32),
                np.int32(real), np.int32(c0), *tables(s), caches, ids,
                np.int32(s if c0 + C >= len(prompt) else -1), np.float32(0),
                np.uint32(0), StepRows(active.copy(), cursors.copy(),
                                       *tables(), *greedy))
            routes = np.asarray(moe["routes"])[:, 0]
            taken[s].append(routes[:, :real])
            rows_routed += int(np.asarray(moe["counts"]).sum())
            live_rows += real + int(active.sum())
            for row in np.flatnonzero(active):
                got[row].append(np.asarray(logits[1 + row], np.float32))
                taken[row].append(routes[:, C + row:C + row + 1])
            cursors = cursors + active
            cursors[s] += real
        got[s].append(np.asarray(logits[0], np.float32))
        active[s] = 1
    assert len(fed[0]) == 2 and not fed[2]  # slot 0 decoded beside 2's chunks
    for _ in range(6):
        feed()
        for row in prompts:
            caches = ensure(caches, row, int(cursors[row]),
                            int(cursors[row]) + 1)
        ids, caches, moe, logits = step(
            params, ids, jnp.asarray(active), cursors, *tables(), caches,
            *greedy)
        cursors = cursors + active
        rows_routed += int(np.asarray(moe["counts"]).sum())
        live_rows += len(prompts)
        for s in prompts:
            got[s].append(np.asarray(logits[s], np.float32))
            taken[s].append(np.asarray(moe["routes"])[:, s])
    assert cursors[2] > W > len(prompts[2])  # slot 2 crossed the window

    def rel(got, want):
        return {"max": float(np.abs(got - want).max() / np.abs(want).max()),
                "rms": float(np.sqrt(((got - want) ** 2).mean()
                                     / (want ** 2).mean()))}

    errs, flip_share, margins = {}, [], []
    for s, prompt in prompts.items():
        tokens = jnp.asarray([prompt + fed[s]], jnp.int32)
        routes = jnp.asarray(np.concatenate(taken[s], axis=1))[:, None]
        want, probs = ref.forward_and_router(params, tokens, hp, routes)
        errs[s] = rel(np.stack(got[s][:-1]), want[0][len(prompt) - 1:-1])
        f, m = routing_margin(probs, routes)
        flip_share.append(f)
        margins.append(m)
    poisoned = [bool(jnp.isnan(c.k).any()) for c in caches]
    out = {"routed_err": errs, "flip_share": flip_share, "margin": margins,
           "rows_routed": rows_routed,
           "live_rows_x_k_x_layers": live_rows * k * L,
           "window_pages_released": released,
           "peak_window_pages_a_slot": peak_held,
           "bound_window_pages_a_slot": -(-(W + C) // T) + 1,
           "full_pages_slot_0": int((full[0] > 0).sum()),
           "pools_poisoned": poisoned}
    bad = []
    if not all(np.isfinite(g).all() for rows in got.values() for g in rows):
        bad.append("a logit is not finite: a released page was read")
    if poisoned != [kind == SLIDING for kind in cfg.kinds] or not released:
        bad.append("the poison is not in the window layers' pools alone")
    if peak_held > out["bound_window_pages_a_slot"]:
        bad.append("a slot held more of the window pool than window + chunk")
    if rows_routed != live_rows * k * L:
        bad.append("a row was dropped or a dead row counted")
    if max(e["max"] for e in errs.values()) > 0.08 \
            or max(e["rms"] for e in errs.values()) > 0.06:
        bad.append("routed error above the dense cells' tolerance")
    if max(margins) > 4e-3:
        bad.append("an expert was taken that the reference scores far "
                   "below its 8th")
    if bad:
        raise RuntimeError(f"mellum: {bad}: {out}")
    del caches
    control = out["float8_control"] = mellum_float8_control(cfg, hp, params,
                                                            seed)
    # NOT correct, by the comparison without routes (its root mean square)
    # and by the one given them; the two statistics that read ONE position's
    # worst cannot tell a flipped position from float8 (the cell's file)
    if control["checks"]["reference_logits"] \
            or control["checks"]["reference_logits_given_choices"]:
        raise RuntimeError(f"mellum: the float8 control passes a comparison "
                           f"that has to refuse it: {control}")
    return {**out, **device_report()}


def keye_task(seed: int, control: bool = True) -> dict:
    """The Keye-width checks (ISSUE 51): the paged chunk, step and fused
    turn in bf16 at the published widths (hidden 2048, 32 heads over 4 K/V
    heads of 128, an indexer of 16 heads of 64 that picks 2048 tokens, 128
    experts of 768; the benchmark's 5 layers) against the plain float32
    reference GIVEN the system's own routes and the tokens its queries
    attended. Two prompts that end on a page's first token (4113 = 257 x 16
    + 1 tokens, past ``topk`` twice over, and 2065 = 129 x 16 + 1, just past
    it), the second's chunks taking the first's decode row along; every
    page no table names filled with NaN in K, V and the index keys, as a
    released page would be. Then the selection alone at the cell's chunk
    (``select_timing``), the two kernels that read a context alone
    (``indexed_timing``), and (``control``) the float8 control through the
    harness's own
    comparison under the limits of ``cells/keye_longctx.json``, which must
    refuse it."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.lib import configs, weights
    from perfbench.lib import manifest as manifest_lib
    from perfbench.reference import keye as ref
    from perfbench.reference.olmoe import routing_margin
    from ray_tpu.models.decode import (StepRows, init_paged_caches,
                                       paged_decode_step,
                                       paged_prefill_into_slot)

    require_chip()
    manifest = manifest_lib.load()
    hp = manifest_lib.config(manifest, "keye_vl2_30b_a3b_l5")
    cfg = configs.build_program_config(*configs.program_overrides(
        hp, manifest_lib.read_json_from_bench("families", hp["model_type"])))
    L, k, topk = cfg.num_layers, cfg.moe_top_k, cfg.indexer.topk
    params = weights.make_params(cfg, seed)
    S, C, T, P = 4, 512, 16, 272
    caches = init_paged_caches(cfg, S * P + 1 + 64, T, P)
    rng = np.random.default_rng(seed)
    prompts = {0: rng.integers(1, cfg.vocab_size, 4113).tolist(),
               2: rng.integers(1, cfg.vocab_size, 2065).tolist()}
    tables = np.zeros((S, P), np.int32)
    for s in prompts:
        tables[s] = 1 + s * P + np.arange(P)
    loose = np.setdiff1d(np.arange(1, S * P + 65), tables)
    caches = [dataclasses.replace(c, **{
        name: getattr(c, name).at[loose].set(jnp.nan)
        for name in ("k", "v", "ik")}) for c in caches]
    both = jnp.asarray(tables)

    prefill = jax.jit(lambda *a: paged_prefill_into_slot(
        cfg, *a, attn="pallas", moe_info=True, logits=True, selected=True),
        donate_argnums=(6,))
    step = jax.jit(lambda *a: paged_decode_step(
        cfg, *a, attn="pallas", moe_info=True, logits=True, selected=True),
        donate_argnums=(6,))
    ids = jnp.zeros(S, jnp.int32)
    got = {s: [] for s in prompts}
    taken = {s: [] for s in prompts}
    picked = {s: [] for s in prompts}
    fed = {s: [] for s in prompts}
    active = np.zeros(S, np.int32)
    cursors = np.zeros(S, np.int32)
    greedy = (np.zeros(S, np.float32), np.zeros(S, np.uint32))
    rows_routed = live_rows = 0

    def feed():
        for s in np.flatnonzero(active):
            fed[s].append(int(got[s][-1].argmax()))

    for s, prompt in prompts.items():
        for c0 in range(0, len(prompt), C):
            chunk = prompt[c0:c0 + C]
            real = len(chunk)
            feed()
            ids, caches, moe, logits, chosen = prefill(
                params, jnp.asarray([chunk + [0] * (C - real)], jnp.int32),
                np.int32(real), np.int32(c0), both[s], both[s], caches, ids,
                np.int32(s if c0 + C >= len(prompt) else -1), np.float32(0),
                np.uint32(0), StepRows(active.copy(), cursors.copy(), both,
                                       both, *greedy))
            routes = np.asarray(moe["routes"])[:, 0]
            taken[s].append(routes[:, :real])
            picked[s].append(np.asarray(chosen[0])[:, 0, :real])
            rows_routed += int(np.asarray(moe["counts"]).sum())
            live_rows += real + int(active.sum())
            for row in np.flatnonzero(active):
                got[row].append(np.asarray(logits[1 + row], np.float32))
                taken[row].append(routes[:, C + row:C + row + 1])
                picked[row].append(np.asarray(chosen[1])[:, row])
            cursors = cursors + active
            cursors[s] += real
        got[s].append(np.asarray(logits[0], np.float32))
        active[s] = 1
    assert len(fed[0]) == 5 and not fed[2]  # slot 0 decoded beside 2's chunks
    for _ in range(6):
        feed()
        ids, caches, moe, logits, chosen = step(
            params, ids, jnp.asarray(active), cursors, both, both, caches,
            *greedy)
        cursors = cursors + active
        rows_routed += int(np.asarray(moe["counts"]).sum())
        live_rows += len(prompts)
        for s in prompts:
            got[s].append(np.asarray(logits[s], np.float32))
            taken[s].append(np.asarray(moe["routes"])[:, s])
            picked[s].append(np.asarray(chosen)[:, s])

    def rel(got, want):
        return {"max": float(np.abs(got - want).max() / np.abs(want).max()),
                "rms": float(np.sqrt(((got - want) ** 2).mean()
                                     / (want ** 2).mean()))}

    errs, flip_share, margins, own_choice = {}, [], [], {}
    for s, prompt in prompts.items():
        tokens = jnp.asarray([prompt + fed[s]], jnp.int32)
        n = tokens.shape[1]
        routes = jnp.asarray(np.concatenate(taken[s], axis=1))[:, None]
        choice = np.concatenate([c[..., :n] for c in picked[s]], 1)[:, None]
        counts = choice.sum(-1)[:, 0]
        if not (counts == np.minimum(np.arange(n) + 1, topk)).all():
            raise RuntimeError("keye: a query did not attend min(t + 1, "
                               f"topk) tokens: {counts[:, -8:]}")
        want, probs, theirs = ref.forward_and_choices(params, tokens, hp,
                                                      routes, choice)
        errs[s] = rel(np.stack(got[s][:-1]), want[0][len(prompt) - 1:-1])
        f, m = routing_margin(probs, routes)
        flip_share.append(f)
        margins.append(m)
        # what the float32 reference's own indexer picks, given the same
        # inputs layer by layer: the share of (query, token) choices on
        # which bf16 scores and float32 ones part
        _, _, own = ref.forward_and_choices(params, tokens, hp, routes)
        own_choice[s] = float((own != choice).sum() / choice.sum())
    poisoned = [bool(jnp.isnan(getattr(c, name)[loose]).all())
                for c in caches for name in ("k", "v", "ik")]
    out = {"given_err": errs, "flip_share": flip_share, "margin": margins,
           "choice_differs_share": own_choice,
           "rows_routed": rows_routed,
           "live_rows_x_k_x_layers": live_rows * k * L,
           "pages_poisoned": int(loose.size)}
    bad = []
    if not all(np.isfinite(g).all() for rows in got.values() for g in rows):
        bad.append("a logit is not finite: a page no table names was read")
    if not all(poisoned):
        bad.append("a page no table names was written")
    if rows_routed != live_rows * k * L:
        bad.append("a row was dropped or a dead row counted")
    if max(e["max"] for e in errs.values()) > 0.08 \
            or max(e["rms"] for e in errs.values()) > 0.06:
        bad.append("error given both choices above the dense cells' "
                   "tolerance")
    if max(margins) > 4e-3:
        bad.append("an expert was taken that the reference scores far "
                   "below its 8th")
    if bad:
        raise RuntimeError(f"keye: {bad}: {out}")
    del caches
    out["select"] = select_timing(seed, topk, ref)
    out["indexed_timing"] = indexed_timing(seed, topk)
    if control:
        seen = out["float8_control"] = float8_control(
            cfg, hp, params, seed, ref, "keye", "keye_longctx")
        if seen["checks"]["reference_logits"] \
                or seen["checks"]["reference_logits_given_choices"]:
            raise RuntimeError("keye: the float8 control passes a "
                               f"comparison that has to refuse it: {seen}")
    return {**out, **device_report()}


def glm_task(seed: int, control: bool = True) -> dict:
    """The GLM-4.7-Flash-width checks (ISSUE 55): the paged chunk, step and
    fused turn in bf16 at the published widths (hidden 2048, 20 heads of 192
    + 64 q/k and 256 v values over a latent of 512 and one shared rotated
    key, a dense layer of 10240 then 64 experts of 1536 top-4 sigmoid beside
    a shared one; the benchmark's 1 + 5 layers) — which attend the latents
    ABSORBED — against the plain float32 reference, which rebuilds every
    key and value, GIVEN the system's own routes. Two prompts that end on a
    page's first token (4113 = 257 x 16 + 1 tokens and 2065 = 129 x 16 + 1),
    the second's chunks taking the first's decode row along; then a third
    sequence that SPLICES the first's leading 2048 tokens as the radix cache
    would (their pages in its read table and not in its write table) and
    goes on with a tail of its own; every page no table names filled with
    NaN, as a released page would be. Then the chunk's attention alone,
    absorbed against expanded (``latent_timing``), and (``control``) the
    float8 control through the harness's own comparison under the limits of
    ``cells/glm47_flash_longdocs.json``, which must refuse it."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.lib import configs, weights
    from perfbench.lib import manifest as manifest_lib
    from perfbench.reference import glm_moe_lite as ref
    from ray_tpu.models.decode import (StepRows, init_paged_caches,
                                       paged_decode_step,
                                       paged_prefill_into_slot)

    require_chip()
    manifest = manifest_lib.load()
    hp = manifest_lib.config(manifest, "glm47_flash_l6")
    cfg = configs.build_program_config(*configs.program_overrides(
        hp, manifest_lib.read_json_from_bench("families", hp["model_type"])))
    L, k = cfg.expert_layers, cfg.moe_top_k
    params = weights.make_params(cfg, seed)
    S, C, T, P, shared = 4, 512, 16, 272, 2048
    caches = init_paged_caches(cfg, S * P + 1 + 64, T, P)
    rng = np.random.default_rng(seed)
    prompts = {0: rng.integers(1, cfg.vocab_size, 4113).tolist(),
               2: rng.integers(1, cfg.vocab_size, 2065).tolist()}
    # the third sequence: slot 0's first 2048 tokens, then 600 of its own
    prompts[3] = prompts[0][:shared] + rng.integers(
        1, cfg.vocab_size, 600).tolist()
    read = np.zeros((S, P), np.int32)
    for s in prompts:
        read[s] = 1 + s * P + np.arange(P)
    write = read.copy()
    read[3, :shared // T] = read[0, :shared // T]   # spliced: read only
    write[3, :shared // T] = 0
    loose = np.setdiff1d(np.arange(1, S * P + 65), np.union1d(read, write))
    caches = [dataclasses.replace(c, ckr=c.ckr.at[loose].set(jnp.nan))
              for c in caches]
    reads, writes = jnp.asarray(read), jnp.asarray(write)

    prefill = jax.jit(lambda *a: paged_prefill_into_slot(
        cfg, *a, attn="pallas", moe_info=True, logits=True),
        donate_argnums=(6,))
    step = jax.jit(lambda *a: paged_decode_step(
        cfg, *a, attn="pallas", moe_info=True, logits=True),
        donate_argnums=(6,))
    ids = jnp.zeros(S, jnp.int32)
    got = {s: [] for s in prompts}
    taken = {s: [] for s in prompts}
    fed = {s: [] for s in prompts}
    active = np.zeros(S, np.int32)
    cursors = np.zeros(S, np.int32)
    greedy = (np.zeros(S, np.float32), np.zeros(S, np.uint32))
    rows_routed = live_rows = 0

    def feed():
        for s in np.flatnonzero(active):
            fed[s].append(int(got[s][-1].argmax()))

    for s, prompt in prompts.items():
        start = shared if s == 3 else 0   # the spliced tokens are resident
        for c0 in range(start, len(prompt), C):
            chunk = prompt[c0:c0 + C]
            real = len(chunk)
            feed()
            ids, caches, moe, logits = prefill(
                params, jnp.asarray([chunk + [0] * (C - real)], jnp.int32),
                np.int32(real), np.int32(c0), reads[s], writes[s], caches,
                ids, np.int32(s if c0 + C >= len(prompt) else -1),
                np.float32(0), np.uint32(0),
                StepRows(active.copy(), cursors.copy(), reads, writes,
                         *greedy))
            routes = np.asarray(moe["routes"])[:, 0]
            taken[s].append(routes[:, :real])
            rows_routed += int(np.asarray(moe["counts"]).sum())
            live_rows += real + int(active.sum())
            for row in np.flatnonzero(active):
                got[row].append(np.asarray(logits[1 + row], np.float32))
                taken[row].append(routes[:, C + row:C + row + 1])
            cursors = cursors + active
            cursors[s] = c0 + real
        got[s].append(np.asarray(logits[0], np.float32))
        active[s] = 1
    assert len(fed[0]) == 7 and len(fed[2]) == 2 and not fed[3]
    for _ in range(6):
        feed()
        ids, caches, moe, logits = step(
            params, ids, jnp.asarray(active), cursors, reads, writes, caches,
            *greedy)
        cursors = cursors + active
        rows_routed += int(np.asarray(moe["counts"]).sum())
        live_rows += len(prompts)
        for s in prompts:
            got[s].append(np.asarray(logits[s], np.float32))
            taken[s].append(np.asarray(moe["routes"])[:, s])

    def rel(got, want):
        return {"max": float(np.abs(got - want).max() / np.abs(want).max()),
                "rms": float(np.sqrt(((got - want) ** 2).mean()
                                     / (want ** 2).mean()))}

    errs, flips = {}, {}
    for s, prompt in prompts.items():
        tokens = jnp.asarray([prompt + fed[s]], jnp.int32)
        first = len(prompt) - 1
        routes = np.concatenate(taken[s], axis=1)[:, None]
        if s == 3:
            # the spliced tokens' routes were slot 0's, position by position
            routes = np.concatenate(
                [np.concatenate(taken[0], axis=1)[:, None, :shared], routes],
                axis=2)
        want, scores = ref.forward_and_router(params, tokens, hp,
                                              jnp.asarray(routes))
        errs[s] = rel(np.stack(got[s][:-1]), want[0][first:-1])
        # the share of rows whose biased top 4 in float32 is not the
        # program's in bf16
        bias = np.stack([np.asarray(
            params["blocks"]["body"]["mlp"]["e_bias"][i], np.float32)
            for i in range(L)])[:, None, None]
        own = np.sort(np.asarray(jax.lax.top_k(
            np.asarray(scores) + bias, k)[1]), -1)
        flips[s] = float((own != np.sort(routes, -1)).any(-1).mean())
    poisoned = [bool(jnp.isnan(c.ckr[loose]).all()) for c in caches]
    out = {"given_err": errs, "flip_share": flips,
           "rows_routed": rows_routed,
           "live_rows_x_k_x_layers": live_rows * k * L,
           "pages_poisoned": int(loose.size)}
    bad = []
    if not all(np.isfinite(g).all() for rows in got.values() for g in rows):
        bad.append("a logit is not finite: a page no table names was read")
    if not all(poisoned):
        bad.append("a page no table names was written")
    if rows_routed != live_rows * k * L:
        bad.append("a row was dropped or a dead row counted")
    if max(e["max"] for e in errs.values()) > 0.08 \
            or max(e["rms"] for e in errs.values()) > 0.06:
        bad.append("error given the routes above the dense cells' "
                   "tolerance")
    if bad:
        raise RuntimeError(f"glm: {bad}: {out}")
    del caches
    out["latent_timing"] = latent_timing(cfg, params, seed)
    if control:
        seen = out["float8_control"] = float8_control(
            cfg, hp, params, seed, ref, "glm_moe_lite",
            "glm47_flash_longdocs")
        if seen["checks"]["reference_logits"] \
                and seen["checks"]["reference_logits_given_choices"]:
            raise RuntimeError("glm: the float8 control passes both "
                               f"comparisons of logits: {seen}")
    return {**out, **device_report()}


def deepseek_task(seed: int, control: bool = True) -> dict:
    """The DeepSeek-V3.2-Exp-width checks (ISSUE 61): the paged chunk, step
    and fused turn in bf16 at the published widths (hidden 7168, 128 heads
    over a latent of 512 and one shared rotated key of 64, an indexer of 64
    heads of 128 that picks 2048 latents, a dense layer of 18432 then 16
    HELD of 256 experts of 2048 top-8 in 8 groups beside a shared one; the
    benchmark's 1 + 4 layers, an eighth of the vocabulary) — which attend
    the PICKED latents absorbed, gathered through the page table — against
    the plain float32 reference, which rebuilds every key and value, GIVEN
    the system's own routes and the tokens its queries attended. Two
    prompts that end on a page's first token (4113 = 257 x 16 + 1 tokens,
    past ``topk`` twice over, and 2065 = 129 x 16 + 1, just past it), the
    second's chunks taking the first's decode row along; then a third
    sequence that SPLICES the first's leading 2048 tokens as the radix cache
    would (their pages — latents AND index keys — in its read table and not
    in its write table) and goes on with a tail of its own, past ``topk``;
    every page no table names filled with NaN in both arrays, as a released
    page would be. Then the picked attention alone at the cell's shapes
    (``picked_timing``), the experts' kernel alone at the cell's shape
    (``experts_timing``), and (``control``) the float8 control through the
    harness's own comparison under the limits of
    ``cells/deepseek_v32_longdocs.json``, which must refuse it."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.lib import configs, weights
    from perfbench.lib import manifest as manifest_lib
    from perfbench.reference import deepseek_v32 as ref
    from ray_tpu.models.decode import (StepRows, init_paged_caches,
                                       paged_decode_step,
                                       paged_prefill_into_slot)

    require_chip()
    manifest = manifest_lib.load()
    hp = manifest_lib.config(manifest, "deepseek_v32_exp_l5")
    cfg = configs.build_program_config(*configs.program_overrides(
        hp, manifest_lib.read_json_from_bench("families", hp["model_type"])))
    L, k, topk = cfg.expert_layers, cfg.moe_top_k, cfg.indexer.topk
    params = weights.make_params(cfg, seed)
    S, C, T, P, shared = 4, 512, 16, 272, 2048
    caches = init_paged_caches(cfg, S * P + 1 + 64, T, P)
    rng = np.random.default_rng(seed)
    prompts = {0: rng.integers(1, cfg.vocab_size, 4113).tolist(),
               2: rng.integers(1, cfg.vocab_size, 2065).tolist()}
    prompts[3] = prompts[0][:shared] + rng.integers(
        1, cfg.vocab_size, 600).tolist()
    read = np.zeros((S, P), np.int32)
    for s in prompts:
        read[s] = 1 + s * P + np.arange(P)
    write = read.copy()
    read[3, :shared // T] = read[0, :shared // T]   # spliced: read only
    write[3, :shared // T] = 0
    loose = np.setdiff1d(np.arange(1, S * P + 65), np.union1d(read, write))
    caches = [dataclasses.replace(c, ckr=c.ckr.at[loose].set(jnp.nan),
                                  ik=c.ik.at[loose].set(jnp.nan))
              for c in caches]
    reads, writes = jnp.asarray(read), jnp.asarray(write)

    prefill = jax.jit(lambda *a: paged_prefill_into_slot(
        cfg, *a, attn="pallas", moe_info=True, logits=True, selected=True),
        donate_argnums=(6,))
    step = jax.jit(lambda *a: paged_decode_step(
        cfg, *a, attn="pallas", moe_info=True, logits=True, selected=True),
        donate_argnums=(6,))
    ids = jnp.zeros(S, jnp.int32)
    got = {s: [] for s in prompts}
    taken = {s: [] for s in prompts}
    picked = {s: [] for s in prompts}
    fed = {s: [] for s in prompts}
    active = np.zeros(S, np.int32)
    cursors = np.zeros(S, np.int32)
    greedy = (np.zeros(S, np.float32), np.zeros(S, np.uint32))
    routes_chosen = held_routes = live_rows = 0

    def feed():
        for s in np.flatnonzero(active):
            fed[s].append(int(got[s][-1].argmax()))

    def count(moe):
        nonlocal routes_chosen, held_routes
        landed = int(np.asarray(moe["counts"]).sum())
        held_routes += landed
        routes_chosen += landed + int(np.asarray(moe["left_out"]).sum())

    for s, prompt in prompts.items():
        start = shared if s == 3 else 0   # the spliced tokens are resident
        for c0 in range(start, len(prompt), C):
            chunk = prompt[c0:c0 + C]
            real = len(chunk)
            feed()
            ids, caches, moe, logits, chosen = prefill(
                params, jnp.asarray([chunk + [0] * (C - real)], jnp.int32),
                np.int32(real), np.int32(c0), reads[s], writes[s], caches,
                ids, np.int32(s if c0 + C >= len(prompt) else -1),
                np.float32(0), np.uint32(0),
                StepRows(active.copy(), cursors.copy(), reads, writes,
                         *greedy))
            routes = np.asarray(moe["routes"])[:, 0]
            taken[s].append(routes[:, :real])
            picked[s].append(np.asarray(chosen[0])[:, 0, :real])
            count(moe)
            live_rows += real + int(active.sum())
            for row in np.flatnonzero(active):
                got[row].append(np.asarray(logits[1 + row], np.float32))
                taken[row].append(routes[:, C + row:C + row + 1])
                picked[row].append(np.asarray(chosen[1])[:, row])
            cursors = cursors + active
            cursors[s] = c0 + real
        got[s].append(np.asarray(logits[0], np.float32))
        active[s] = 1
    for _ in range(6):
        feed()
        ids, caches, moe, logits, chosen = step(
            params, ids, jnp.asarray(active), cursors, reads, writes, caches,
            *greedy)
        cursors = cursors + active
        count(moe)
        live_rows += len(prompts)
        for s in prompts:
            got[s].append(np.asarray(logits[s], np.float32))
            taken[s].append(np.asarray(moe["routes"])[:, s])
            picked[s].append(np.asarray(chosen)[:, s])

    def rel(got, want):
        return {"max": float(np.abs(got - want).max() / np.abs(want).max()),
                "rms": float(np.sqrt(((got - want) ** 2).mean()
                                     / (want ** 2).mean()))}

    errs, flips, own_choice = {}, {}, {}
    layers = len(cfg.kinds)
    for s, prompt in prompts.items():
        tokens = jnp.asarray([prompt + fed[s]], jnp.int32)
        n, first = tokens.shape[1], len(prompt) - 1
        routes = np.concatenate(taken[s], axis=1)[:, None]
        choice = np.concatenate([c[..., :n] for c in picked[s]], 1)[:, None]
        if s == 3:
            # the spliced tokens' routes were slot 0's, position by
            # position; within ``topk`` of the start a query attends every
            # token before it
            routes = np.concatenate(
                [np.concatenate(taken[0], axis=1)[:, None, :shared], routes],
                axis=2)
            causal = np.tril(np.ones((shared, n), bool))
            choice = np.concatenate([np.broadcast_to(
                causal, (layers, 1, shared, n)), choice], axis=2)
        counts = choice.sum(-1)[:, 0]
        if not (counts == np.minimum(np.arange(n) + 1, topk)).all():
            raise RuntimeError("deepseek: a query did not attend min(t + 1, "
                               f"topk) tokens: {counts[:, -8:]}")
        want = ref.forward(params, tokens, hp, jnp.asarray(routes), choice)
        errs[s] = rel(np.stack(got[s][:-1]), want[0][first:-1])
        # what the float32 reference's own router and indexer pick, given
        # the same inputs layer by layer: the share of rows whose top 8 and
        # of (query, token) choices on which bf16 and float32 part
        _, _, own = ref.forward_and_choices(
            params, tokens, hp, jnp.asarray(routes))
        own_choice[s] = float((own != choice).sum() / choice.sum())
        _, own_routes, _ = ref.forward_and_choices(
            params, tokens, hp, None, choice)
        flips[s] = float((np.sort(own_routes, -1)
                          != np.sort(routes, -1)).any(-1).mean())
    poisoned = [bool(jnp.isnan(pool[loose]).all())
                for c in caches for pool in (c.ckr, c.ik)]
    out = {"given_err": errs, "flip_share": flips,
           "choice_differs_share": own_choice,
           "routes_chosen": routes_chosen, "held_routes": held_routes,
           "live_rows_x_k_x_layers": live_rows * k * L,
           "pages_poisoned": int(loose.size)}
    bad = []
    if not all(np.isfinite(g).all() for rows in got.values() for g in rows):
        bad.append("a logit is not finite: a page no table names was read")
    if not all(poisoned):
        bad.append("a page no table names was written")
    if routes_chosen != live_rows * k * L:
        bad.append("a row was dropped or a dead row counted")
    if max(e["max"] for e in errs.values()) > 0.08 \
            or max(e["rms"] for e in errs.values()) > 0.06:
        bad.append("error given both choices above the dense cells' "
                   "tolerance")
    if bad:
        raise RuntimeError(f"deepseek: {bad}: {out}")
    del caches
    out["picked_timing"] = picked_timing(seed)
    out["experts_timing"] = experts_timing(seed)
    if control:
        seen = out["float8_control"] = float8_control(
            cfg, hp, params, seed, ref, "deepseek_v32",
            "deepseek_v32_longdocs")
        if seen["checks"]["reference_logits"] \
                and seen["checks"]["reference_logits_given_choices"]:
            raise RuntimeError("deepseek: the float8 control passes both "
                               f"comparisons of logits: {seen}")
    return {**out, **device_report()}


def experts_timing(seed: int, calls: int = 8) -> dict:
    """The experts' kernel ``moe_grouped_matmul`` ALONE at the cell's shape
    (since PR 64): a fused turn's 4,160 pairs over the 16 held experts of
    7168 x 2048 of the SECOND of two stacked layers, bf16 — 88 MB an expert,
    which ``tile_sizes`` walks in 8 column tiles under a grid of 48 visits
    — in milliseconds a call behind a warm-up: with the ~270 pairs a
    sixteenth of DeepSeek's experts is sent (the fullest group 78), with
    every pair held, and with none; beside each the visits that carry rows
    and their weights' bytes over the time (GB/s; the memory gives 819)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.moe import _visits, expert_mlp, tile_sizes

    G, d, f, pairs = 16, 7168, 2048, 4160
    t = tile_sizes(pairs, G, d, f, 2)
    n_tiles = -(-pairs // t.rows)
    ks = jax.random.split(jax.random.key(seed & 0x7FFFFFFF), 4)
    xs = jax.random.normal(ks[0], (pairs, d), jnp.bfloat16)
    w = [0.02 * jax.random.normal(key, shape, jnp.bfloat16)
         for key, shape in zip(ks[1:], [(2 * G, d, f), (2 * G, d, f),
                                        (2 * G, f, d)])]
    kernel = jax.jit(lambda xs, wg, wu, wd, g: expert_mlp(
        xs, wg, wu, wd, g, G))
    out = {"tiles": list(t), "grid_visits": n_tiles + G - 1}
    for name, counts in (
            ("a_sixteenth_held", (78, 3, 0, 31, 12, 0, 22, 9, 41, 0, 17, 5,
                                  26, 8, 14, 4)),
            ("every_pair_held", (pairs // G,) * G), ("none_held", (0,) * G)):
        g = jnp.asarray(counts, jnp.int32)
        jax.block_until_ready(kernel(xs, *w, g))
        t0 = time.perf_counter()
        for _ in range(calls):
            got = kernel(xs, *w, g)
        jax.block_until_ready(got)
        ms = (time.perf_counter() - t0) / calls * 1e3
        visits = int(_visits(g, jnp.int32(G), n_tiles, t.rows)[4][0])
        out[name] = {"ms": round(ms, 3), "visits": visits,
                     "visited_gb_s": round(visits * 3 * d * f * 2 / ms / 1e6,
                                           1)}
    return out


def picked_timing(seed: int, calls: int = 4) -> dict:
    """The picked latent attention ALONE at the cell's shapes (128 heads
    over rows of 640 lanes, 64 index heads of 128, 2048 picked, a table of
    4128 pages PERMUTED over a pool of 33,024, seeded), one layer: a 512
    chunk at contexts of 8k, 20k and 55k and the step's 8 rows, in
    milliseconds a call behind a warm-up — the whole op, of it the
    indexer's two kernels (``index_score`` + ``indexed_select``) alone, and
    for the chunk the kernel alone that copies the slot's pages in, moves
    each query's chosen rows together and attends them (``*_kernel_ms``,
    since PR 62: 2048 sorted positions a query drawn below the context);
    the rest is the choice's compaction, and for the step the gather."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops.indexed_attention import (IndexerSizes, index_scores,
                                               select)
    from ray_tpu.ops.picked_latent_attention import (_attend_chunk,
                                                     picked_latent_attention)

    sizes = IndexerSizes(indexer_num_heads=64, indexer_head_dim=128,
                         topk=2048)
    N, T, P = 33024, 16, 4128
    ks = jax.random.split(jax.random.key(seed & 0x7FFFFFFF), 8)
    normal = lambda key, shape: jax.random.normal(key, shape, jnp.bfloat16)
    pool, ik = normal(ks[0], (N, T, 640)), normal(ks[1], (N, T, 128))
    rng = np.random.default_rng(seed)
    tables = jnp.asarray(np.stack([rng.permutation(N - 1)[:P] + 1
                                   for _ in range(8)]), jnp.int32)

    def ms(fn, *args):
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        return round((time.perf_counter() - t0) / calls * 1e3, 3)

    whole = jax.jit(lambda *a: picked_latent_attention(
        *a, sizes, sm_scale=0.135, impl="pallas"))
    indexer = jax.jit(lambda qi, w, ik, tables, pos: select(
        index_scores(qi, w, ik, tables, pos, False), pos, 2048, False))
    kernel = jax.jit(lambda q, at, pool, tables, last: _attend_chunk(
        q, at, jnp.full(at.shape[:2], 2048, jnp.int32), pool, tables, last,
        P * T, 512, False, "picked_latent_chunk_attention"))
    out = {}
    for name, B, S, contexts in (("chunk", 1, 512, (8192, 20480, 56320)),
                                 ("step", 8, 1, (20480, 56320))):
        q_c, q_r = normal(ks[2], (B, S, 128, 512)), normal(
            ks[3], (B, S, 128, 64))
        qi = normal(ks[4], (B, S, 64, 128))
        w = jax.random.normal(ks[5], (B, S, 64), jnp.float32)
        for ctx in contexts:
            lengths = jnp.full((B,), ctx - S, jnp.int32)
            pos = lengths[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
            out[f"{name}_{ctx // 1024}k_ms"] = ms(
                whole, q_c, q_r, qi, w, pool, ik, tables[:B], pos, lengths)
            out[f"{name}_{ctx // 1024}k_indexer_ms"] = ms(
                indexer, qi, w, ik, tables[:B], pos)
            if S > 1:
                at = np.stack([np.sort(rng.choice(ctx - S, 2048, False))
                               for _ in range(S)])[None]
                out[f"{name}_{ctx // 1024}k_kernel_ms"] = ms(
                    kernel, normal(ks[6], (B, S, 128, 640)),
                    jnp.asarray(at, jnp.int32), pool, tables[:B],
                    pos[:, -1])
    return out


def ouro_task(seed: int, control: bool = True, seeds: int = 5) -> dict:
    """The Ouro-2.6B-width checks (ISSUE 65): the benchmark's configuration
    WHOLE — published widths, all 48 layers, 4 passes, bf16 — under
    ``ouro_reason``'s deployment (8 slots, chunks of 256, 353 pages x 192
    pools of 16 tokens: weights and pools are the cell's 14.2 GB). For each
    of ``seeds`` seeds (``seed``, ``seed + 1``, ...: weights and tokens): a
    320-token prompt in slot 1 (two chunks, the second padded) and a
    200-token one in slot 3 whose chunk takes slot 1's decode row along,
    then 4 steps of both, through the PAGED programs (the looped forward,
    the kernel told which pool) against ``perfbench/reference/ouro.py`` on
    LOGITS, teacher-forced; every returned exit pass the reference's own
    (the published threshold: the last pass); every page no table names is
    filled with NaN first, in all 192 pools, and still is afterwards. The
    worst readings over the seeds are what ``check_tolerance`` in
    ``cells/ouro_reason.json`` is twice of. And (``control``) for each of
    the first three seeds, on ITS weights and check prompt and with the
    pools dropped, the float8 control goes through the harness's own
    comparison under that cell's limits, which must refuse every one."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.lib import configs, traffic, weights
    from perfbench.lib import manifest as manifest_lib
    from perfbench.reference import ouro as ref
    from ray_tpu.models.decode import (StepRows, init_paged_caches,
                                       paged_decode_step,
                                       paged_prefill_into_slot)

    require_chip()
    manifest = manifest_lib.load()
    hp = manifest_lib.config(manifest, "ouro_2_6b")
    cell = manifest_lib.read_json(manifest, "cells", "ouro_reason")
    cfg = configs.build_program_config(*configs.program_overrides(
        hp, manifest_lib.read_json_from_bench("families", hp["model_type"])))
    dep = cell["deployment"]
    slots, C, T = dep["slots"], dep["prefill_chunk"], dep["page_tokens"]
    P = dep["arena_len"] // T
    n = {1: int(cell["check_prompt_tokens"]), 3: 200}
    new = int(cell["check_new_tokens"])
    both = np.zeros((slots, P), np.int32)
    for s in n:
        both[s] = 1 + s * P + np.arange(P)
    bad = np.setdiff1d(np.arange(dep["kv_pages"]), np.unique(both))
    kw = dict(attn="pallas", logits=True, loop_info=True)
    prefill = jax.jit(functools.partial(paged_prefill_into_slot, cfg, **kw),
                      donate_argnums=(6,))
    step = jax.jit(functools.partial(paged_decode_step, cfg, **kw),
                   donate_argnums=(6,))
    # a page at a time, in place: one scatter of the 264 pages' 3 GB of NaN
    # would be made whole first, beside 14 GB
    spoil = jax.jit(lambda caches: jax.tree.map(lambda pool: jax.lax.fori_loop(
        0, len(bad), lambda i, pool: jax.lax.dynamic_update_slice(
            pool, jnp.full((1,) + pool.shape[1:], jnp.nan, pool.dtype),
            (jnp.asarray(bad)[i], 0, 0, 0)), pool), caches), donate_argnums=0)
    spoiled = jax.jit(lambda caches: jnp.stack([jax.lax.fori_loop(
        1, len(bad), lambda i, still: still & jnp.isnan(
            jax.lax.dynamic_index_in_dim(pool, jnp.asarray(bad)[i])).all(),
        jnp.asarray(True)) for pool in jax.tree.leaves(caches)]))

    def rel(got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        return {"max": float(np.abs(got - want).max() / np.abs(want).max()),
                "rms": float(np.sqrt(((got - want) ** 2).mean()
                                     / (want ** 2).mean()))}

    def rows_of(live, cursor):
        active, cursors = np.zeros(slots, np.int32), np.zeros(slots, np.int32)
        for s in live:
            active[s], cursors[s] = 1, cursor[s]
        return StepRows(active, cursors, both, both,
                        np.zeros(slots, np.float32),
                        np.zeros(slots, np.uint32))

    readings, controls, t_first = [], [], None
    for own in range(seed, seed + seeds):
        t0 = time.perf_counter()
        params = weights.make_params(cfg, own)
        tokens = traffic.rng_for(own, 9).integers(
            1, cfg.vocab_size, size=(2, max(n.values()) + 2 + new))
        row = {1: 0, 3: 1}
        caches = spoil(init_paged_caches(cfg, dep["kv_pages"], T, P))
        got, left = {s: [] for s in n}, {s: [] for s in n}
        cursor = dict.fromkeys(n, 0)
        for s, live in ((1, []), (3, [1])):
            for c0 in range(0, n[s], C):
                real = min(C, n[s] - c0)
                padded = np.zeros((1, C), np.int32)
                padded[0, :real] = tokens[row[s], c0:c0 + real]
                ids = np.zeros(slots, np.int32)
                for o in live:
                    ids[o] = tokens[row[o], cursor[o]]
                last = c0 + real == n[s]
                _, caches, told, logits = prefill(
                    params, padded, np.int32(real), np.int32(c0), both[s],
                    both[s], caches, ids, np.int32(s if last else -1),
                    np.float32(0), np.uint32(0), rows_of(live, cursor),
                    np.int32(s))
                cursor[s] = c0 + real
                for o in live:
                    got[o].append(logits[1 + o])
                    left[o].append(int(told["exit_pass"][1 + o]))
                    cursor[o] += 1
            got[s].append(logits[0])
            left[s].append(int(told["exit_pass"][0]))
        for _ in range(new):
            rows = rows_of(list(n), cursor)
            fed = np.zeros(slots, np.int32)
            for s in n:
                fed[s] = tokens[row[s], cursor[s]]
            _, caches, told, logits = step(
                params, fed, rows.active, rows.cursors, both, both, caches,
                rows.temperature, rows.seeds)
            for s in n:
                got[s].append(logits[s])
                left[s].append(int(told["exit_pass"][s]))
                cursor[s] += 1
        clean = bool(np.asarray(spoiled(caches)).all())
        del caches
        t_first = t_first or round(time.perf_counter() - t0, 1)
        want, exits, shares = ref.forward_and_exits(
            params, jnp.asarray(tokens, jnp.int32), hp)
        want, exits = np.asarray(want), np.asarray(exits)
        one = {"seed": own, "poisoned_pages_left_alone": clean}
        for s in n:
            mine = np.asarray(jnp.stack(got[s]), np.float32)
            first = n[s] - 1
            one[f"slot{s}"] = rel(mine, want[row[s],
                                             first:first + len(mine)])
            one[f"slot{s}_finite"] = bool(np.isfinite(mine).all())
            one[f"slot{s}_exits_are_the_references"] = left[s] == exits[
                row[s], first:first + len(mine)].tolist()
        one["largest_share_before_the_last_pass"] = float(
            np.asarray(shares)[:-1].sum(0).max())
        readings.append(one)
        if control and len(controls) < 3:
            # on THIS seed's weights and check prompt, the pools gone
            controls.append(float8_control(cfg, hp, params, own, ref, "ouro",
                                           "ouro_reason"))
    out = {"readings": readings, "first_seed_s": t_first,
           "worst": {k: max(r[f"slot{s}"][k] for r in readings for s in n)
                     for k in ("max", "rms")},
           "every_exit_the_last_pass": all(
               r[f"slot{s}_exits_are_the_references"] for r in readings
               for s in n)}
    if not all(r["poisoned_pages_left_alone"] and r["slot1_finite"]
               and r["slot3_finite"] for r in readings):
        raise RuntimeError(f"ouro: a program read or wrote a page no table "
                           f"names: {readings}")
    if control:
        out["float8_control"] = controls
        if any(all(c["checks"].values()) for c in controls):
            raise RuntimeError("ouro: a float8 control passes the cell's "
                               f"limits: {controls}")
    return out


def ssm_timing(cfg, seed: int, rows: int, calls: int = 20) -> dict:
    """The two state-space kernels ALONE at the cell's shapes, in
    milliseconds a call behind a warm-up: the step over ``rows`` slots'
    states of one layer (in place), the chunked scan of one row's 512
    tokens; and the step's bytes (a row's scan state read and written) over
    its time, as a share of the memory's bandwidth."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import ssm

    sizes = cfg.ssm
    H, P, G, N = sizes.heads, sizes.head_dim, sizes.groups, sizes.state
    ks = jax.random.split(jax.random.key(seed & 0x7FFFFFFF), 6)
    normal = jax.random.normal
    a = -jnp.exp(jax.random.uniform(ks[4], (H,), minval=0.0, maxval=2.5))
    d = jnp.ones((H,), jnp.float32)

    def ms(fn, state, *args):
        out = fn(*args, state)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args, out[1])
        jax.block_until_ready(out)
        return round((time.perf_counter() - t0) / calls * 1e3, 4)

    step = jax.jit(lambda x, dt, bm, cm, active, state: ssm.ssd_step(
        x, dt, a, bm, cm, d, state, active, sizes, "pallas"),
        donate_argnums=(5,))
    step_ms = ms(
        step, jnp.zeros(ssm.state_shapes(rows, sizes)["ssm"], jnp.float32),
        normal(ks[0], (rows, H, P), jnp.bfloat16),
        jax.nn.softplus(normal(ks[1], (rows, H)) - 2.0),
        normal(ks[2], (rows, G, N), jnp.bfloat16),
        normal(ks[3], (rows, G, N), jnp.bfloat16),
        jnp.ones((rows,), jnp.int32))
    chunk = jax.jit(lambda x, dt, bm, cm, state: ssm.ssd_chunk(
        x, dt, a, bm, cm, d, state, sizes, jnp.int32(500), "pallas"))
    chunk_ms = ms(
        chunk, jnp.zeros(ssm.state_shapes(1, sizes)["ssm"], jnp.float32),
        normal(ks[0], (1, 512, H, P), jnp.bfloat16),
        jax.nn.softplus(normal(ks[1], (1, 512, H)) - 2.0),
        normal(ks[2], (1, 512, G, N), jnp.bfloat16),
        normal(ks[3], (1, 512, G, N), jnp.bfloat16))
    moved = rows * 2 * H * P * N * 4
    return {"rows": rows, "step_ms": step_ms, "chunk_ms": chunk_ms,
            "step_bandwidth_share": round(moved / (step_ms * 1e-3) / 819e9,
                                          3)}


def nemotron_task(seed: int, control: bool = True) -> dict:
    """The Nemotron-3-Nano-width checks (ISSUE 59). The two state-space
    kernels in float32 at the published head sizes against their
    ``jax.numpy`` form, across a block boundary with padding and from a
    state that is not zero (1e-3, as the other state kinds' kernels), and
    alone at the cell's shapes, timed (``ssm_timing``). Then the system in
    bf16 at the benchmark's configuration (nine layers MEMEM*EME, 64 of 128
    ``relu^2`` experts held, half the vocabulary): a 1100-token prompt
    (eight blocks of 128 and 76 tokens; two chunks of 512 and 76) and a
    300-token one whose chunk takes the first's decode row along, through
    the paged chunks, fused turns and 6 steps on states and pages, against
    ``perfbench/reference/nemotron_h.py`` GIVEN the system's routes; two
    slots hold no sequence and their states must come back bitwise, every
    page no table names is filled with NaN. Then (``control``) the float8
    control through the harness's own comparison under the limits of
    ``cells/nemotron3_nano_reason.json``, which must refuse it."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.lib import configs, weights
    from perfbench.lib import manifest as manifest_lib
    from perfbench.reference import nemotron_h as ref
    from ray_tpu.models.decode import (StepRows, init_paged_caches,
                                       paged_decode_step,
                                       paged_prefill_into_slot)
    from ray_tpu.models.transformer import MAMBA
    from ray_tpu.ops import ssm

    require_chip()
    manifest = manifest_lib.load()
    hp = manifest_lib.config(manifest, "nemotron3_nano_30b_a3b_l9")
    cell = manifest_lib.read_json(manifest, "cells", "nemotron3_nano_reason")
    cfg = configs.build_program_config(*configs.program_overrides(
        hp, manifest_lib.read_json_from_bench("families", hp["model_type"])))

    def rel(got, want):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        return {"max": float(np.abs(got - want).max() / np.abs(want).max()),
                "rms": float(np.sqrt(((got - want) ** 2).mean()
                                     / (want ** 2).mean()))}

    # ---- the kernels, float32, against their jax.numpy form
    sizes = cfg.ssm
    H, P, G, N = sizes.heads, sizes.head_dim, sizes.groups, sizes.state
    ks = jax.random.split(jax.random.key(seed & 0x7FFFFFFF), 8)
    n, real = 600, 555
    case = dict(
        x=jax.random.normal(ks[0], (2, n, H, P)),
        bm=jax.random.normal(ks[1], (2, n, G, N)) * 0.3,
        cm=jax.random.normal(ks[2], (2, n, G, N)) * 0.3,
        dt=jax.nn.softplus(jax.random.normal(ks[3], (2, n, H)) - 2.0),
        a=-jnp.exp(jax.random.uniform(ks[4], (H,), minval=0.0, maxval=2.5)),
        d=jax.random.normal(ks[5], (H,)),
        state=jax.random.normal(ks[6], ssm.state_shapes(2, sizes)["ssm"]))

    def chunk(impl):
        with jax.default_matmul_precision("highest"):
            return ssm.ssd_chunk(case["x"], case["dt"], case["a"],
                                 case["bm"], case["cm"], case["d"],
                                 case["state"], sizes, jnp.int32(real), impl)

    def step(impl):
        with jax.default_matmul_precision("highest"):
            return ssm.ssd_step(
                case["x"][:, 0], case["dt"][:, 0], case["a"],
                case["bm"][:, 0], case["cm"][:, 0], case["d"], case["state"],
                jnp.asarray([1, 0], jnp.int32), sizes, impl)

    (ky, ks_), (ry, rs) = chunk("pallas"), chunk("reference")
    (sy, ss), (qy, qs) = step("pallas"), step("reference")
    kernels = {"chunk_y": rel(ky[:, :real], ry[:, :real])["max"],
               "chunk_state": rel(ks_, rs)["max"],
               "step_y": rel(sy[:1], qy[:1])["max"],
               "step_state": rel(ss, qs)["max"],
               "idle_row_bitwise": bool(
                   (np.asarray(ss[1]) == np.asarray(case["state"][1])).all())}
    if max(v for k, v in kernels.items() if k != "idle_row_bitwise") > 1e-3 \
            or not kernels["idle_row_bitwise"]:
        raise RuntimeError(f"nemotron: the state-space kernels: {kernels}")
    del case, ky, ks_, ry, rs, ss, qs
    out = {"kernels": kernels,
           "ssm_timing": ssm_timing(cfg, seed,
                                    int(cell["deployment"]["slots"]))}

    # ---- the system, bf16, paged chunks, fused turns and steps
    L, k = cfg.expert_layers, cfg.moe_top_k
    params = weights.make_params(cfg, seed)
    S, C, T, Pg = 4, 512, 16, 96
    caches = init_paged_caches(cfg, S * Pg + 1 + 64, T, Pg, slots=S)
    rng = np.random.default_rng(seed)
    prompts = {0: rng.integers(1, cfg.vocab_size, 1100).tolist(),
               2: rng.integers(1, cfg.vocab_size, 300).tolist()}
    tables = np.zeros((S, Pg), np.int32)
    for s in prompts:
        tables[s] = 1 + s * Pg + np.arange(Pg)
    loose = np.setdiff1d(np.arange(1, S * Pg + 65), tables)
    idle = jnp.asarray([1, 3])

    def spoil(c, kind):
        if kind == MAMBA:   # slots without a sequence: must stay bitwise
            return dataclasses.replace(c, conv=c.conv.at[idle].set(7.0),
                                       ssm=c.ssm.at[idle].set(7.0))
        if c is None:
            return c
        return dataclasses.replace(c, k=c.k.at[loose].set(jnp.nan),
                                   v=c.v.at[loose].set(jnp.nan))

    caches = [spoil(c, kind) for c, kind in zip(caches, cfg.kinds)]
    both = jnp.asarray(tables)
    prefill = jax.jit(lambda *a: paged_prefill_into_slot(
        cfg, *a, attn="pallas", moe_info=True, logits=True),
        donate_argnums=(6,))
    step = jax.jit(lambda *a: paged_decode_step(
        cfg, *a, attn="pallas", moe_info=True, logits=True),
        donate_argnums=(6,))
    ids = jnp.zeros(S, jnp.int32)
    got = {s: [] for s in prompts}
    taken = {s: [] for s in prompts}
    fed = {s: [] for s in prompts}
    active = np.zeros(S, np.int32)
    cursors = np.zeros(S, np.int32)
    greedy = (np.zeros(S, np.float32), np.zeros(S, np.uint32))
    held = left_out = live_rows = 0

    def feed():
        for s in np.flatnonzero(active):
            fed[s].append(int(got[s][-1].argmax()))

    def count(moe):
        return (int(np.asarray(moe["counts"]).sum()),
                int(np.asarray(moe["left_out"]).sum()))

    for s, prompt in prompts.items():
        for c0 in range(0, len(prompt), C):
            part = prompt[c0:c0 + C]
            feed()
            ids, caches, moe, logits = prefill(
                params, jnp.asarray([part + [0] * (C - len(part))],
                                    jnp.int32),
                np.int32(len(part)), np.int32(c0), both[s], both[s], caches,
                ids, np.int32(s if c0 + C >= len(prompt) else -1),
                np.float32(0), np.uint32(0),
                StepRows(active.copy(), cursors.copy(), both, both, *greedy),
                np.int32(s))
            routes = np.asarray(moe["routes"])[:, 0]
            taken[s].append(routes[:, :len(part)])
            here, away = count(moe)
            held, left_out = held + here, left_out + away
            live_rows += len(part) + int(active.sum())
            for row in np.flatnonzero(active):
                got[row].append(np.asarray(logits[1 + row], np.float32))
                taken[row].append(routes[:, C + row:C + row + 1])
            cursors = cursors + active
            cursors[s] = c0 + len(part)
        got[s].append(np.asarray(logits[0], np.float32))
        active[s] = 1
    for _ in range(6):
        feed()
        ids, caches, moe, logits = step(
            params, ids, jnp.asarray(active), cursors, both, both, caches,
            *greedy)
        cursors = cursors + active
        here, away = count(moe)
        held, left_out = held + here, left_out + away
        live_rows += len(prompts)
        for s in prompts:
            got[s].append(np.asarray(logits[s], np.float32))
            taken[s].append(np.asarray(moe["routes"])[:, s])

    errs, flips = {}, {}
    for s, prompt in prompts.items():
        tokens = jnp.asarray([prompt + fed[s]], jnp.int32)
        first = len(prompt) - 1
        routes = np.concatenate(taken[s], axis=1)[:, None]
        want, scores = ref.forward_and_router(params, tokens, hp,
                                              jnp.asarray(routes))
        errs[s] = rel(np.stack(got[s][:-1]), want[0][first:-1])
        # the share of rows whose biased top 6 in float32 is not the
        # program's in bf16
        bias = np.stack([np.asarray(
            params["blocks"][f"p{i}"]["mlp"]["e_bias"][0], np.float32)
            for i, symbol in enumerate(cfg.layer_pattern) if symbol == "E"]
        )[:, None, None]
        own = np.sort(np.asarray(jax.lax.top_k(
            np.asarray(scores) + bias, k)[1]), -1)
        flips[s] = float((own != np.sort(routes, -1)).any(-1).mean())
    states_kept = all(
        bool((np.asarray(state)[np.asarray(idle)] == 7.0).all())
        for c, kind in zip(caches, cfg.kinds) if kind == MAMBA
        for state in (c.conv, c.ssm))
    poisoned = all(bool(jnp.isnan(c.k[loose]).all()) for c, kind in zip(
        caches, cfg.kinds) if kind == "attention")
    out.update(given_err=errs, flip_share=flips, routes_held=held,
               routes_left_out=left_out,
               live_rows_x_k_x_layers=live_rows * k * L)
    bad = []
    if not all(np.isfinite(g).all() for rows in got.values() for g in rows):
        bad.append("a logit is not finite: a page no table names was read")
    if not states_kept:
        bad.append("an idle slot's state was touched")
    if not poisoned:
        bad.append("a page no table names was written")
    if held + left_out != live_rows * k * L:
        bad.append("a row was dropped or a dead row counted")
    if not 0.35 < held / (held + left_out) < 0.65:
        bad.append("the held experts' share of the routes is far from a half")
    if max(e["max"] for e in errs.values()) > 0.08 \
            or max(e["rms"] for e in errs.values()) > 0.06:
        bad.append("error given the routes above the dense cells' "
                   "tolerance")
    if bad:
        raise RuntimeError(f"nemotron: {bad}: {out}")
    del caches
    if control:
        seen = out["float8_control"] = float8_control(
            cfg, hp, params, seed, ref, "nemotron_h",
            "nemotron3_nano_reason")
        if seen["checks"]["reference_logits"] \
                and seen["checks"]["reference_logits_given_choices"]:
            raise RuntimeError("nemotron: the float8 control passes both "
                               f"comparisons of logits: {seen}")
    return {**out, **device_report()}


def latent_timing(cfg, params, seed: int) -> dict:
    """A 512 chunk's latent attention alone, one layer, at contexts of 8k,
    20k and 55k of one slot's table (the cell's 4128 pages): ABSORBED (what
    the program runs: the latents attended as they lie, ``2 (576 + 512)``
    operations a (query, key, head) pair) against EXPANDED (keys and values
    rebuilt from the slot's latents — ``2 x 512 x 8960`` operations a
    context token, 17.9 KB of temporaries a token — then the paged kernel
    over them at heads of 256, ``2 x 512`` a pair), each in milliseconds a
    call, four calls timed behind a warm-up. And the step's kernel over 8
    rows at those contexts. The absorbed path twice (ISSUE 56): the slots'
    pages IN ORDER in the pool, as nothing but this script lays them, and
    PERMUTED over the pool's 33,024 pages (seeded), as an arena that has
    served a while hands them out — ``*_permuted_ms``: what a block's 32
    page copies cost when they land 20 KB apart and not in one run."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.transformer import (latent_finish, latent_mix,
                                            layer_params)
    from ray_tpu.ops.latent_attention import pool_width
    from ray_tpu.ops.paged_attention import paged_attention

    H, nope, rope, rank, dv = (cfg.num_heads, cfg.latent_nope_dim,
                               cfg.latent_rope_dim, cfg.latent_kv_rank,
                               cfg.latent_v_dim)
    T, P, C = 16, 4128, 512
    p = layer_params(cfg, params, 1)["attn"]
    keys = jax.random.split(jax.random.PRNGKey(seed & 0x7FFFFFFF), 4)
    pool = jax.random.normal(keys[0], (8 * P + 1, T, pool_width(rank, rope)),
                             jnp.bfloat16)
    tables = 1 + jnp.arange(8 * P, dtype=jnp.int32).reshape(8, P)
    permuted = jnp.asarray(1 + np.random.default_rng(seed).permutation(
        8 * P).reshape(8, P), jnp.int32)

    def rows(k0, n, K):
        return (jax.random.normal(keys[k0], (n, K, H, nope), jnp.bfloat16),
                jax.random.normal(keys[k0 + 1], (n, K, H, rope),
                                  jnp.bfloat16))

    # the pool is an argument: closed over, its 1.3 GB would be a constant
    # of the program
    @jax.jit
    def absorbed(pool, q, cursor, tables):
        return latent_finish(cfg, p, latent_mix(
            cfg, p, q, (pool,), tables, cursor, impl="pallas"))

    @jax.jit
    def expanded(pool, q, cursor, tables):
        ckr = pool[tables[0]]                              # [P, T, width]
        kv = jnp.einsum("ptr,rhk->pthk", ckr[..., :rank],
                        p["wkv_b"].astype(jnp.bfloat16))
        kr = jnp.broadcast_to(ckr[..., None, rank:rank + rope],
                              kv.shape[:3] + (rope,))
        k = jnp.concatenate([kv[..., :nope], kr], -1).reshape(P, T, -1)
        v = kv[..., nope:].reshape(P, T, -1)
        o = paged_attention(jnp.concatenate(q, -1), k, v,
                            jnp.arange(P, dtype=jnp.int32)[None], cursor,
                            impl="pallas")
        return jnp.einsum("bshv,hvd->bsd", o, p["wo"].astype(jnp.bfloat16))

    def ms(fn, *args):
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(4):
            out = fn(*args)
        jax.block_until_ready(out)
        return round((time.perf_counter() - t0) / 4 * 1e3, 3)

    out = {}
    for context in (8192, 20480, 55296):
        cursor = jnp.asarray([context - C], jnp.int32)
        q = rows(1, 1, C)
        a, e = (fn(pool, q, cursor, tables[:1])
                for fn in (absorbed, expanded))
        off = float(jnp.abs(a.astype(jnp.float32) - e.astype(jnp.float32)
                            ).max() / jnp.abs(e.astype(jnp.float32)).max())
        steps = rows(1, 8, 1), jnp.full((8,), context, jnp.int32)
        out[str(context)] = {
            "chunk_absorbed_ms": ms(absorbed, pool, q, cursor, tables[:1]),
            "chunk_absorbed_permuted_ms": ms(absorbed, pool, q, cursor,
                                             permuted[:1]),
            "chunk_expanded_ms": ms(expanded, pool, q, cursor, tables[:1]),
            "absorbed_off_expanded": off,
            "step_8_rows_ms": ms(absorbed, pool, *steps, tables),
            "step_8_rows_permuted_ms": ms(absorbed, pool, *steps, permuted)}
    return out


def select_timing(seed: int, topk: int, ref) -> dict:
    """``indexed_select`` alone (ISSUE 53) at the cell's chunk: 512 rows
    that end at 2k, 16k, 38k and 49k of a 49,664-lane table of seeded
    scores, every lane behind a row's position NaN (nobody computed it);
    four such chunks a call, so that the device is timed and not the
    dispatch. By last position: the milliseconds a chunk, the fewest and
    most passes a tile of 8 rows ran (33 run every bit, 50 with a tied
    cut's passes, 2 a tile that takes every token) — and the choice is a
    sort's."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import indexed_attention as ia

    lanes, rows, chunks, out = 49664, 512, 4, {}
    scores = jax.random.normal(jax.random.PRNGKey(seed),
                               (chunks, rows, lanes), jnp.float32)
    run = jax.jit(lambda s, p: ia.select(s, p, topk, False, passes=True))
    for end in (2048, 16384, 38912, lanes):
        pos = jnp.broadcast_to(jnp.arange(end - rows, end, dtype=jnp.int32),
                               (chunks, rows))
        seen = jnp.where(jnp.arange(lanes) <= pos[..., None], scores, jnp.nan)
        tau, bound, passes = jax.block_until_ready(run(seen, pos))
        t0 = time.perf_counter()
        for _ in range(20):
            last = run(seen, pos)
        jax.block_until_ready(last)
        ms = (time.perf_counter() - t0) / 20 / chunks * 1e3
        got = ia.chosen(seen, pos[..., None], tau[..., None],
                        bound[..., None])
        want = ref.select_block(scores, end - rows, topk)
        if not bool((got == want).all()):
            raise RuntimeError(f"keye: the selection at {end} is not a "
                               f"sort's: {int((got != want).sum())} choices")
        passes = np.asarray(passes)[:, ::ia.SELECT_ROWS]
        out[str(end)] = {"ms": round(ms, 4), "passes": [
            int(passes.min()), int(passes.max())]}
    return out


def indexed_timing(seed: int, topk: int) -> dict:
    """The indexed mixer's two kernels that read a slot's CONTEXT, alone at
    the cell's shapes (ISSUE 60; pools of 24,833 pages of 16, tables of
    3,104): ``index_score`` for a 512 chunk's rows and for the step's 8 rows
    (16 index heads of 64 over the index keys' rows), and
    ``indexed_chunk_attention`` for the chunk (32 heads over 4 K/V heads of
    128, the choice from the kernels' own scores and selection), with the
    queries ending at 8k, 20k and 49k of the table, milliseconds a call (four
    calls a program, so the device is timed and not the dispatch). Each
    twice: the slots' pages IN ORDER in the pool and PERMUTED over it
    (seeded), as an arena that has served a while hands them out —
    ``*_permuted_ms``. What a call's time holds is whatever stands between
    the pools and the kernel: a gather of the context, or the kernel's own
    page copies."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import indexed_attention as ia

    T, P, slots, C, reps = 16, 3104, 8, 512, 4
    H, G, D, Hi, Di = 32, 4, 128, 16, 64
    key = jax.random.split(jax.random.PRNGKey(seed & 0x7FFFFFFF), 8)
    pools = [jax.random.normal(k, (1 + slots * P, T, width), jnp.bfloat16)
             for k, width in zip(key, (G * D, G * D, Di))]
    pools[2] = ia.index_row(pools[2])
    tables = 1 + jnp.arange(slots * P, dtype=jnp.int32).reshape(slots, P)
    permuted = jnp.asarray(1 + np.random.default_rng(seed).permutation(
        slots * P).reshape(slots, P), jnp.int32)

    # the pools are arguments: closed over, they would be constants
    @jax.jit
    def score(qi, w, ik_pool, tables, positions):
        return [ia.index_scores(qi * (1 + i), w, ik_pool, tables, positions,
                                False) for i in range(reps)]

    @jax.jit
    def attend(q, k_pool, v_pool, tables, positions, scores, tau, bound):
        return [ia._chunk_attention(q * (1 + i), k_pool, v_pool, tables,
                                    positions, scores, tau, bound, False)
                for i in range(reps)]

    def ms(fn, *args, calls=8):
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(calls):
            out = fn(*args)
        jax.block_until_ready(out)
        return round((time.perf_counter() - t0) / calls / reps * 1e3, 4)

    out = {}
    for context in (8192, 20480, 49152):
        pos = jnp.arange(context - C, context, dtype=jnp.int32)[None]
        step = jnp.full((slots, 1), context - 1, jnp.int32)
        rows = lambda k, n, K: (
            jax.random.normal(key[k], (n, K, Hi, Di), jnp.bfloat16),
            jax.random.normal(key[k + 1], (n, K, Hi), jnp.float32))
        q = jax.random.normal(key[7], (1, C, H, D), jnp.bfloat16)
        here = out[str(context)] = {}
        for name, table in (("", tables), ("_permuted", permuted)):
            scores = score(*rows(3, 1, C), pools[2], table[:1], pos)[0]
            cut = ia.select(scores, pos, topk, False)
            here.update({
                f"score_chunk{name}_ms": ms(score, *rows(3, 1, C), pools[2],
                                            table[:1], pos),
                f"score_step_8_rows{name}_ms": ms(
                    score, *rows(5, slots, 1), pools[2], table, step,
                    calls=20),
                f"attention_chunk{name}_ms": ms(
                    attend, q, *pools[:2], table[:1], pos, scores, *cut)})
    return out


class Float8Control:
    """Stands where the replica stands in ``BenchLLMServer.reference_check``:
    the plain reference's own forward pass with every weight rounded to
    float8 e4m3 (the nearest type below the configuration's bf16) answers for
    the program. ``_prefill`` and ``_decode_step`` hand out its logits row by
    row, ``_forward_with_choices`` its logits GIVEN the routes the program's
    own uncached forward took on the true weights, so the harness's own
    comparison reads the control. The rounded weights stay float8 arrays (the
    reference casts every weight it touches to float32): nothing can fold
    the rounding away, and they fit beside the true ones."""

    def __init__(self, cfg, params, ref, hp, fed):
        import jax
        import jax.numpy as jnp

        self.cfg, self.params, self.ref, self.hp, self.fed = (
            cfg, params, ref, hp, fed)
        self._jax = jax
        self.rounded = jax.tree.map(
            jax.jit(lambda a: a.astype(jnp.float8_e4m3fn)), params)

    def _prefill(self, params, prompt, caches):
        import jax.numpy as jnp
        import numpy as np

        tokens = jnp.concatenate(
            [prompt, jnp.asarray([self.fed], jnp.int32)], axis=1)
        self.rows = np.asarray(self.ref.forward(
            self.rounded, tokens, self.hp)[0], np.float32)[
                prompt.shape[1] - 1:]
        self._row = iter(self.rows)
        return next(self._row)[None], caches

    def _decode_step(self, params, token, caches):
        return next(self._row)[None], caches

    def _forward_with_choices(self, keyword, tokens, first, end):
        import numpy as np

        from perfbench.lib.serve_app import BenchLLMServer

        _, choice = BenchLLMServer._forward_with_choices(
            self, keyword, tokens, first, end)
        mine = self.ref.forward(self.rounded, tokens, self.hp,
                                **{keyword: choice})
        return np.asarray(mine[0], np.float32)[first:end], choice


def mellum_float8_control(cfg, hp, params, seed: int) -> dict:
    from perfbench.reference import mellum as ref

    return float8_control(cfg, hp, params, seed, ref, "mellum",
                          "mellum2_shortlong")


def float8_control(cfg, hp, params, seed: int, ref, reference: str,
                   cell_name: str) -> dict:
    """The cell's own comparison (``BenchLLMServer.reference_check`` with
    the routes given too, then the limits of ``cells/<cell_name>.json`` as
    ``serve_cell.run`` applies them) on the float8 control, which has to
    come out NOT correct: on the cell's check prompt from ``seed``, fed the
    tokens the program's own greedy pass answers it with (``prefill`` +
    ``decode_step``, what the check itself drives). The control's tokens
    are its logits' best at each of those positions. ``ref``: the family's
    plain reference, ``reference`` its file's name. Returns the readings,
    the verdicts, and which limits the control passes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.lib import manifest as manifest_lib
    from perfbench.lib import traffic
    from perfbench.lib.serve_app import BenchLLMServer
    from ray_tpu.models.decode import decode_step, init_caches, prefill

    cell = manifest_lib.read_json(manifest_lib.load(), "cells", cell_name)
    tol = cell["check_tolerance"]
    n, new = int(cell["check_prompt_tokens"]), int(cell["check_new_tokens"])
    ids = traffic.rng_for(seed, 9).integers(1, cfg.vocab_size,
                                            size=n).tolist()
    pre = jax.jit(lambda p, t, c: prefill(cfg, p, t, c))
    dec = jax.jit(lambda p, t, c: decode_step(cfg, p, t, c))
    logits, caches = pre(params, jnp.asarray([ids], jnp.int32),
                         init_caches(cfg, 1, n + new))
    served = [int(logits[0].argmax())]
    for _ in range(new - 1):
        logits, caches = dec(params, jnp.asarray([[served[-1]]], jnp.int32),
                             caches)
        served.append(int(logits[0].argmax()))
    del caches, logits
    control = Float8Control(cfg, params, ref, hp, served[:-1])
    path = os.path.join(manifest_lib.BENCH_DIR, "reference",
                        reference + ".py")
    check = BenchLLMServer.reference_check(control, ids, served, hp, path,
                                           given=True)
    # the harness read the PROGRAM's tokens' margin; the control's own
    # tokens by the same expression
    want = np.asarray(ref.forward(
        params, jnp.asarray([ids + served[:-1]], jnp.int32), hp)[0],
        np.float32)[n - 1:]
    scale = float(np.abs(want).max())
    check["served_margin"] = max(
        float((want[i].max() - want[i][int(row.argmax())]) / scale)
        for i, row in enumerate(control.rows))
    given = [k for k in tol if k.startswith("given_")]
    verdicts = {
        "reference_logits": check["logit_err"] <= tol["logit_err"]
        and check["logit_rms_err"] <= tol["logit_rms_err"],
        "served_tokens_near_argmax":
            check["served_margin"] <= tol["served_margin"],
        "reference_logits_given_choices": all(
            check[k] <= tol[k] for k in given)}
    return {"seed": seed, "positions": new,
            "compared": {k: {"value": check[k], "limit": tol[k]}
                         for k in sorted(tol)},
            "checks": verdicts,
            "limits_passed": sorted(k for k in tol if check[k] <= tol[k])}


def served_batch(cfg, params, seed: int) -> dict:
    """A short mixed batch through ``ContinuousScheduler`` at the widths
    ``params`` has (ISSUE 29): twelve requests over eight slots, the loop
    one step ahead, against ``decode_step`` fed the same tokens one at a
    time on a sequential cache. bf16 and another order of reduction can
    turn a near-tie, so equality is reported as a share and what is held
    is the benchmark's limit: every served token within 3% of the largest
    logit the sequential cache has there."""
    import asyncio
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.decode import decode_step, init_caches
    from ray_tpu.serve._private.continuous import ContinuousScheduler

    rng = np.random.default_rng(seed)
    requests = [(rng.integers(1, cfg.vocab_size, n).tolist(), new)
                for n, new in ((5, 12), (150, 8), (33, 20), (64, 6), (90, 16),
                               (12, 20), (70, 9), (128, 14), (3, 18), (48, 7),
                               (101, 11), (20, 15))]
    sched = ContinuousScheduler(cfg, params, slots=8, prefill_chunk=64,
                                arena_len=256, page_tokens=16,
                                kv_pages=8 * 16 + 1, attn="pallas")

    async def one(prompt, new):
        queue = asyncio.Queue()
        sched.submit(prompt, max_new_tokens=new, temperature=0.0,
                     loop=asyncio.get_running_loop(), queue=queue)
        out = []
        while True:
            kind, value, _ = await queue.get()
            if kind == "tok":
                out.append(value)
            elif kind == "end":
                return out
            else:
                raise RuntimeError(f"served batch: {kind}: {value}")

    async def drive():
        return await asyncio.gather(*(one(*r) for r in requests))

    try:
        served = asyncio.run(drive())
        stats = sched.stats()
    finally:
        sched.shutdown()
    step = jax.jit(partial(decode_step, cfg))
    equal = total = 0
    worst = 0.0
    for (prompt, new), out in zip(requests, served):
        if len(out) != new:
            raise RuntimeError(f"served batch: {len(out)} tokens of {new}")
        caches = init_caches(cfg, 1, 256)
        for i, token in enumerate(prompt + out[:-1]):
            logits, caches = step(params, jnp.asarray([[token]], jnp.int32),
                                  caches)
            if i >= len(prompt) - 1:
                row = np.asarray(logits[0], np.float32)
                tok = out[i - len(prompt) + 1]
                equal += int(row.argmax()) == tok
                total += 1
                worst = max(worst, float((row.max() - row[tok])
                                         / abs(row.max())))
    return {"requests": len(requests), "tokens": total,
            "tokens_equal_share": round(equal / total, 4),
            "worst_served_gap": round(worst, 5),
            "runahead_share": round(stats["runahead_steps"]
                                    / stats["decode_steps"], 4),
            "decode_steps": stats["decode_steps"],
            "prefill_chunks": stats["prefill_chunks"],
            "fused_turns": stats["fused_turns"],
            "fused_step_rows": stats["fused_step_rows"],
            "pipeline_drains": stats["pipeline_drains"],
            "discarded_rows": stats["discarded_rows"],
            "compiled_programs": stats["compiled_programs"]}


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


def on_chips(name: str, loop, config: dict, chips: int) -> dict:
    """What ``loop`` reports from one JaxTrainer worker holding ``chips``."""
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train import JaxTrainer

    result = JaxTrainer(
        loop, train_loop_config=config,
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                     tpus_per_worker=chips),
    ).fit()
    if result.error is not None:
        raise RuntimeError(f"{name}: training failed: {result.error}")
    return dict(result.metrics)


def fit(name: str, seed: int, steps: int, mesh, chips: int) -> dict:
    t0 = time.perf_counter()
    out = on_chips(name, train_loop,
                   {"seed": seed, "steps": steps, "mesh": mesh}, chips)
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


def olmoe_phase(seed: int) -> None:
    import ray_tpu

    t0 = time.perf_counter()
    out = ray_tpu.get(
        ray_tpu.remote(num_tpus=1)(olmoe_task).remote(seed), timeout=1500)
    emit("olmoe", seconds=round(time.perf_counter() - t0, 1), **out)


def sala_phase(seed: int) -> None:
    import ray_tpu

    t0 = time.perf_counter()
    out = ray_tpu.get(
        ray_tpu.remote(num_tpus=1)(sala_task).remote(seed), timeout=1500)
    emit("sala", seconds=round(time.perf_counter() - t0, 1), **out)


def brumby_phase(seed: int) -> None:
    import ray_tpu

    t0 = time.perf_counter()
    out = ray_tpu.get(
        ray_tpu.remote(num_tpus=1)(brumby_task).remote(seed), timeout=1500)
    emit("brumby", seconds=round(time.perf_counter() - t0, 1), **out)


def mellum_phase(seed: int) -> None:
    import ray_tpu

    t0 = time.perf_counter()
    out = ray_tpu.get(
        ray_tpu.remote(num_tpus=1)(mellum_task).remote(seed), timeout=1500)
    emit("mellum", seconds=round(time.perf_counter() - t0, 1), **out)


def keye_phase(seed: int) -> None:
    import ray_tpu

    t0 = time.perf_counter()
    out = ray_tpu.get(
        ray_tpu.remote(num_tpus=1)(keye_task).remote(seed), timeout=2400)
    emit("keye", seconds=round(time.perf_counter() - t0, 1), **out)


def glm_phase(seed: int) -> None:
    import ray_tpu

    t0 = time.perf_counter()
    out = ray_tpu.get(
        ray_tpu.remote(num_tpus=1)(glm_task).remote(seed), timeout=2400)
    emit("glm", seconds=round(time.perf_counter() - t0, 1), **out)


def deepseek_phase(seed: int) -> None:
    import ray_tpu

    t0 = time.perf_counter()
    out = ray_tpu.get(
        ray_tpu.remote(num_tpus=1)(deepseek_task).remote(seed), timeout=2400)
    emit("deepseek", seconds=round(time.perf_counter() - t0, 1), **out)


def nemotron_phase(seed: int) -> None:
    import ray_tpu

    t0 = time.perf_counter()
    out = ray_tpu.get(
        ray_tpu.remote(num_tpus=1)(nemotron_task).remote(seed), timeout=2400)
    emit("nemotron", seconds=round(time.perf_counter() - t0, 1), **out)


def ouro_phase(seed: int) -> None:
    import ray_tpu

    t0 = time.perf_counter()
    out = ray_tpu.get(
        ray_tpu.remote(num_tpus=1)(ouro_task).remote(seed), timeout=2400)
    emit("ouro", seconds=round(time.perf_counter() - t0, 1), **out)


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
         runahead_steps=stats["runahead_steps"],
         pipeline_drains=stats["pipeline_drains"],
         discarded_rows=stats["discarded_rows"],
         prefill_chunks=stats["prefill_chunks"],
         fused_turns=stats["fused_turns"],
         fused_step_rows=stats["fused_step_rows"],
         prefix_hit_tokens=stats.get("prefix_hit_tokens"),
         weights={k: weights.get(k) for k in ("mode", "nbytes")},
         store_capacity=store["capacity"],
         native={"allocator": native_available("allocator"),
                 "codec": native_available("codec")})


def one_chip(seed: int) -> dict:
    out = fit("train", seed, steps=6, mesh=None, chips=1)
    kernels_phase(seed)
    olmoe_phase(seed)
    sala_phase(seed)
    brumby_phase(seed)
    mellum_phase(seed)
    keye_phase(seed)
    glm_phase(seed)
    nemotron_phase(seed)
    deepseek_phase(seed)
    ouro_phase(seed)
    serve_phase(seed)
    return out["device"]


def overlap_parity(seed: int) -> None:
    """The four-chip cell's step with ``training.OVERLAP_REDUCES`` against
    the same step without: the first two steps' loss within 1e-5 of each
    other and gradient norm within 1e-4 (the landed options read both equal
    to seven digits; the option that is NOT taken read the loss 9e-4 and the
    norm 0.4 away), twice the reduces at the same bytes either way, and
    some of them hidden only with the options."""
    t0 = time.perf_counter()
    out = on_chips("overlap_parity", overlap_loop, {"seed": seed}, chips=4)
    on, off = out["with"], out["without"]
    rel = {key: [abs(a - b) / abs(b) for a, b in zip(on[key], off[key])]
           for key in ("loss", "grad_norm")}
    emit("overlap_parity", seconds=round(time.perf_counter() - t0, 1),
         rel_diff=rel, **out)
    if not all(math.isfinite(x) for x in on["loss"] + on["grad_norm"]):
        raise RuntimeError(f"overlap_parity: a step is not finite: {on}")
    if max(rel["loss"]) > 1e-5 or max(rel["grad_norm"]) > 1e-4:
        raise RuntimeError(f"overlap_parity: the options compile another "
                           f"step: {rel}")
    hid = [side["tp_reduces"]["hidden"] for side in (on, off)]
    if (on["tp_reduces"]["bytes"] != off["tp_reduces"]["bytes"]
            or on["tp_reduces"]["calls"] != off["tp_reduces"]["calls"]
            or not hid[0] > hid[1] == 0):
        raise RuntimeError(f"overlap_parity: the reduces are not the same, "
                           f"or none is hidden: {on['tp_reduces']} with, "
                           f"{off['tp_reduces']} without")


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
    overlap_parity(seed)

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
    # this process's own compile record: it drives and never imports JAX, so
    # ``watching`` is false and every total 0 (a worker's is in its phase's
    # line, ``compiles``, and behind profile_actor(..., kind="compiles"))
    from ray_tpu._private import profiling

    own = profiling.collect("compiles")
    emit("compiles", **{k: v for k, v in own.items() if k != "jit_programs"},
         programs=len(own["jit_programs"]))
    if device["platform"] != "tpu" or device["count"] != args.chips:
        raise SystemExit(f"chip_smoke: worker saw {device}")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
