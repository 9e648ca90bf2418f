"""Serve-decode throughput harness: batched autoregressive decode on the
local chip (the BASELINE "Serve-equivalent LLM deployment ... batched
replica throughput" row) plus the ISSUE-9 open-loop load generator.

Modes:

  * default — the jitted prefill + per-token decode loop from
    `ray_tpu.models.decode` across batch sizes (raw device decode
    capacity);
  * --serve — end-to-end through a live Serve deployment (router ->
    replica -> continuous scheduler);
  * --loadgen — OPEN-LOOP load generator against the replica serve path:
    Poisson arrivals; `--workload prefix` (default, ISSUE 13) draws each
    prompt as a Zipf-distributed shared preamble (8 x 224-token system
    prompts / few-shot preambles) plus a unique 4-10-token tail, while
    `--workload mixed` keeps the ISSUE-9 mixed-length/heavy-tail shape.
    Drives THREE schedulers at the same offered load — paged arena +
    radix prefix cache, the PR-9 contiguous continuous arena, and the
    request-level `@serve.batch` baseline — and reports p50/p99 TTFT,
    p50/p99 inter-token latency, useful tokens/s and `prefix_hit_rate`,
    plus the paged/continuous and continuous/baseline ratios. Every
    record carries the device of the process that ran the model
    (`device`, `device_kind`, `device_count`): this process for the
    in-process modes, the replicas (from their `scheduler_stats()`) for
    `--serve` and `--fleet`, whose parent stays off JAX so that each
    replica's own process can hold a chip.

    python bench_serve.py --loadgen [--rate 20] [--requests 60]
                          [--seed 0] [--json-out SERVE_BENCH.json]

vs_baseline of the default mode is decode tokens/s at the best batch
divided by 1000 (a single-GPU 7B-class continuous-batching serving rate
is O(1000) tok/s; the debug-size model here is smaller, so treat it as a
scale probe, not a model-for-model comparison).
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time


def bench_decode(preset: str, prompt_len: int, new_tokens: int,
                 batches=(1, 8, 32)) -> dict:
    import functools

    import jax

    from ray_tpu.models import presets
    from ray_tpu.models.decode import generate
    from ray_tpu.models.transformer import init_params

    cfg = getattr(presets, preset)()
    params = init_params(cfg, jax.random.PRNGKey(0))
    # one compiled program per batch size: prefill + lax.scan over decode
    # steps — the replica-side program shape
    gen = jax.jit(functools.partial(generate, cfg,
                                    max_new_tokens=new_tokens),
                  static_argnames=())

    results = []
    for batch in batches:
        tokens = jax.random.randint(jax.random.PRNGKey(1),
                                    (batch, prompt_len), 0, cfg.vocab_size)
        key = jax.random.PRNGKey(2)
        jax.block_until_ready(gen(params, tokens, key))  # compile + warmup
        t0 = time.perf_counter()
        iters = 5
        for _ in range(iters):
            toks = gen(params, tokens, key)
        jax.block_until_ready(toks)
        dt = (time.perf_counter() - t0) / iters
        decode_tps = batch * new_tokens / dt
        results.append({
            "batch": batch,
            "decode_tokens_per_sec": round(decode_tps, 1),
            "latency_ms_per_token": round(dt / new_tokens * 1e3, 2),
            "end_to_end_s": round(dt, 3),
        })
    return {"per_batch": results, "preset": preset,
            "prompt_len": prompt_len, "new_tokens": new_tokens}


def bench_serve_path(preset: str, new_tokens: int, concurrency: int,
                     requests_total: int) -> dict:
    """End-to-end CONTINUOUS-BATCHING measurement: concurrent requests
    through a live Serve deployment (router -> replica -> @serve.batch
    coalescing -> one batched generate per flush), tokens/s counted at
    the client. This is the serving number; `bench_decode` is the raw
    device decode capacity it converges to as batching amortizes."""
    import threading

    import ray_tpu
    import ray_tpu.serve as serve
    from ray_tpu.serve.llm import build_app

    ray_tpu.init(num_cpus=8, object_store_memory=512 * 1024 * 1024)
    try:
        h = serve.run(build_app(preset=preset, max_new_tokens=new_tokens,
                                max_batch_size=max(8, concurrency)),
                      name="llmbench", route_prefix="/llmbench")
        h.remote({"prompt": "warmup"}).result(timeout=600)  # compile

        lock = threading.Lock()
        done = {"started": 0, "ok": 0, "errors": 0}

        def client(k):
            while True:
                with lock:
                    if done["started"] >= requests_total:
                        return
                    done["started"] += 1
                try:
                    h.remote({"prompt": f"request {k}"}).result(timeout=600)
                    with lock:
                        done["ok"] += 1
                except Exception:
                    with lock:
                        done["errors"] += 1

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(concurrency)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        n_ok = done["ok"]
        st = h.scheduler_stats.remote().result(timeout=60)
        return {
            **_replica_device([st]),
            "requests": n_ok,
            "errors": done["errors"],
            "concurrency": concurrency,
            "requests_per_sec": round(n_ok / dt, 2),
            "serve_decode_tokens_per_sec": round(n_ok * new_tokens / dt, 1),
            "elapsed_s": round(dt, 2),
        }
    finally:
        ray_tpu.shutdown()


# ---------------------------------------------------------------- loadgen


def _this_process_device() -> dict:
    """Device stamp for records whose model ran in THIS process."""
    import jax

    devices = jax.devices()
    return {"device": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices)}


def _replica_device(replica_stats) -> dict:
    """Device stamp for records whose model ran in serve replicas, from
    what each replica's own process reports."""
    seen = {(st["platform"], st["device_kind"], st["device_count"])
            for st in replica_stats}
    if len(seen) != 1:
        raise RuntimeError(f"replicas disagree on their device: {seen}")
    platform, kind, count = seen.pop()
    return {"device": platform, "device_kind": kind, "device_count": count}


def _percentiles(xs, unit_scale=1e3):
    import numpy as np

    if not xs:
        return {"p50": None, "p99": None}
    a = np.asarray(xs, float) * unit_scale
    return {"p50": round(float(np.percentile(a, 50)), 2),
            "p99": round(float(np.percentile(a, 99)), 2)}


def _make_load(seed: int, n: int, rate_rps: float, new_tokens_cap: int):
    """The offered load: Poisson arrivals, mixed prompt lengths, heavy-
    tailed (Pareto) per-request generation budgets — the shape that makes
    flush-and-drain batching pathological (one long request pins its whole
    flush; queued requests wait a full generation)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_rps, size=n))
    lens = rng.choice([4, 12, 24, 40], size=n, p=[0.35, 0.35, 0.2, 0.1])
    letters = "abcdefghijklmnopqrstuvwxyz"
    prompts = ["".join(rng.choice(list(letters), size=int(L)))
               for L in lens]
    budgets = [int(min(new_tokens_cap, 1 + round(4 * rng.pareto(1.5))))
               for _ in range(n)]
    return list(zip(arrivals.tolist(), prompts, budgets))


def _make_prefix_load(seed: int, n: int, rate_rps: float,
                      new_tokens_cap: int, *, n_prefixes: int = 8,
                      prefix_len: int = 224, zipf_s: float = 1.1,
                      max_seq_len: int = 256):
    """ISSUE-13 shared-prefix workload: a handful of long system-prompt /
    few-shot preambles chosen Zipf-distributed (a few preambles dominate,
    the tail is cold — real multi-tenant traffic shape), each followed by
    a short unique per-request tail. Prefix reuse is the whole game here:
    a scheduler that re-prefills every preamble burns ~prefix_len tokens
    of compute per request that a radix cache turns into a page-table
    splice."""
    import numpy as np

    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_rps, size=n))
    letters = "abcdefghijklmnopqrstuvwxyz "
    prefixes = ["".join(rng.choice(list(letters), size=prefix_len))
                for _ in range(n_prefixes)]
    ranks = np.arange(1, n_prefixes + 1, dtype=float)
    p = ranks ** (-zipf_s)
    p /= p.sum()
    which = rng.choice(n_prefixes, size=n, p=p)
    tail_lens = rng.integers(4, 11, size=n)
    prompts = [prefixes[w] + f"{i:03d}" +
               "".join(rng.choice(list(letters), size=int(t)))
               for i, (w, t) in enumerate(zip(which, tail_lens))]
    # prompt + budget must fit the (possibly overridden) context window
    # regardless of the mixed-workload cap
    cap = min(new_tokens_cap, max_seq_len - (prefix_len + 3 + 10) - 2)
    cap = max(2, min(cap, 12))
    budgets = [int(min(cap, 2 + round(3 * rng.pareto(1.5))))
               for _ in range(n)]
    return list(zip(arrivals.tolist(), prompts, budgets))


async def _drive_open_loop(server, load, streaming: bool):
    """Replay the arrival schedule against one replica callable. Streaming
    consumption measures true TTFT/inter-token latency; non-streaming
    (the flush-and-drain baseline delivers every token at completion)
    records completion time as the first-token time — which IS that
    path's honest TTFT."""
    results = []
    loop = asyncio.get_running_loop()
    t_start = loop.time()

    async def one(at, prompt, budget):
        await asyncio.sleep(max(0.0, t_start + at - loop.time()))
        t0 = time.perf_counter()
        times = []
        if streaming:
            gen = await server({"prompt": prompt, "stream": True,
                                "max_new_tokens": budget})
            async for _chunk in gen:
                times.append(time.perf_counter())
        else:
            out = await server({"prompt": prompt,
                                "max_new_tokens": budget})
            times = [time.perf_counter()] * out["num_tokens"]
        results.append({"t0": t0, "times": times})

    await asyncio.gather(*[one(*req) for req in load])
    wall = max(r["times"][-1] for r in results) - min(
        r["t0"] for r in results)
    ttfts = [r["times"][0] - r["t0"] for r in results]
    itls = [b - a for r in results if streaming
            for a, b in zip(r["times"], r["times"][1:])]
    tokens = sum(len(r["times"]) for r in results)
    return {"wall_s": round(wall, 3), "tokens": tokens,
            "tokens_per_sec": round(tokens / wall, 1),
            "requests": len(results),
            "ttft_ms": _percentiles(ttfts),
            "inter_token_ms": _percentiles(itls)}


def run_loadgen(mode: str, preset: str, rate_rps: float, n: int, seed: int,
                *, slots: int = 8, prefill_chunk: int = 16,
                new_tokens_cap: int = 48, workload: str = "mixed",
                kv_layout: str = "contiguous",
                prefix_cache: bool = False,
                prefix_len: int = 224, max_seq_len: int = 256,
                kv_pages: int = 0, attn: str = None) -> dict:
    """One open-loop run against a directly-instantiated replica callable
    (the serve path minus transport: scheduler + jitted programs — what
    the ISSUE-9/13 comparisons are about). mode: "continuous" | "batch";
    workload: "mixed" (ISSUE 9) | "prefix" (ISSUE 13 Zipf shared-prefix);
    kv_layout/prefix_cache select the paged arena + radix cache vs the
    PR-9 contiguous arena (continuous mode only); attn selects the paged
    attention lane (ISSUE 20: in-place "reference"/"pallas" vs the
    gathered-view "gather" baseline; None = the config default)."""
    from ray_tpu.serve.llm import LLMServerImpl

    kw = {}
    if mode == "continuous":
        kw = {"kv_layout": kv_layout,
              "prefix_cache": prefix_cache if kv_layout == "paged" else None}
        if kv_layout == "paged" and kv_pages:
            kw["kv_pages"] = kv_pages
        if kv_layout == "paged" and attn is not None:
            kw["attn"] = attn
    if workload == "prefix":
        # the shared preambles need a context window wider than the debug
        # preset's 128 (production few-shot preambles dwarf the tails);
        # every candidate gets the same window
        kw["preset_overrides"] = {"max_seq_len": max_seq_len}
    server = LLMServerImpl(
        preset=preset, max_new_tokens=new_tokens_cap, scheduler=mode,
        slots=slots, prefill_chunk=prefill_chunk, share_weights=False,
        max_batch_size=slots, **kw)
    try:
        if workload == "prefix":
            load = _make_prefix_load(seed, n, rate_rps, new_tokens_cap,
                                     prefix_len=prefix_len,
                                     max_seq_len=max_seq_len)
        else:
            load = _make_load(seed, n, rate_rps, new_tokens_cap)
        # warmup = a full replay of the SAME load, off the clock: the
        # request-level baseline compiles one program per (batch, length,
        # steps) shape its flushes happen to form — measuring its shape-
        # churn compiles would flatter the continuous path (which compiles
        # exactly two programs) for the wrong reason on CPU. For the
        # prefix-cache comparison the warm replay also PRE-POPULATES the
        # radix cache for both candidates symmetrically (the measured run
        # sees the steady-state hit rate, not the cold ramp)
        asyncio.run(_drive_open_loop(
            server, load, streaming=(mode == "continuous")))
        warm = (server.scheduler_stats()
                if mode == "continuous" else {})
        out = asyncio.run(_drive_open_loop(
            server, load, streaming=(mode == "continuous")))
        out["scheduler"] = server.scheduler_stats()
        if mode == "continuous":
            st = out["scheduler"]
            # fallback guard: the ITERATION-LEVEL path must have engaged —
            # a silent fall-back to flush-and-drain cannot vacuously pass
            assert st["mode"] == "continuous", st
            assert st["admitted_mid_flight"] > 0, (
                "no request was admitted mid-generation; the open-loop "
                f"load never exercised continuous batching: {st}")
            assert st["kv_layout"] == kv_layout, st
            if prefix_cache and kv_layout == "paged":
                # fallback guard: the radix cache must actually have
                # spliced prefixes, and exactly two programs compiled
                assert st["prefix_hits"] > 0, (
                    f"prefix cache never hit on the shared-prefix load: "
                    f"{st}")
                assert st["compiled_programs"] == 2, st
                # steady-state hit rate: the MEASURED run's delta only
                # (the warmup replay exists precisely to absorb the
                # cold-ramp misses — don't blend them back in)
                dh = st["prefix_hits"] - warm.get("prefix_hits", 0)
                dm = st["prefix_misses"] - warm.get("prefix_misses", 0)
                out["prefix_hit_rate"] = round(dh / max(dh + dm, 1), 4)
        return out
    finally:
        server.shutdown()


# ----------------------------------------------------------------- fleet


def _fleet_arm(affinity: bool, load, *, replicas: int, slots: int,
               prefill_chunk: int, new_cap: int, max_seq_len: int,
               kv_pages: int, spec_k: int, skew: int, log) -> dict:
    """One fleet measurement: `replicas` copies of the LLM app through
    the REAL control plane (controller + router + replica actors), the
    Zipf shared-prefix load replayed open-loop from COLD caches. With
    ``affinity`` the router steers on prefix digests (fleet hits land on
    the holder; skew/fail fallbacks pull pages cross-replica); without it
    the same router runs affinity-blind pow-2 — the ISSUE-18 baseline."""
    import threading

    import ray_tpu
    import ray_tpu.serve as serve
    from ray_tpu._private import config as _conf_mod
    from ray_tpu.serve.llm import build_app

    os.environ["RAY_TPU_SERVE_AFFINITY"] = "1" if affinity else "0"
    # a tight skew bound matters under a Zipf head: overflow traffic must
    # fall back (and MIGRATE the prefix) instead of queueing on the
    # holder — that keeps p99 TTFT flat while the hit rate stays fleet-
    # wide (a migrated splice is still a prefix hit on the puller)
    os.environ["RAY_TPU_SERVE_AFFINITY_SKEW"] = str(skew)
    # the router reads the knobs at construction — refresh the cached
    # config so each arm's router sees its own settings
    _conf_mod._global_config = None
    name = "fleetaff" if affinity else "fleetblind"
    h = serve.run(build_app(num_replicas=replicas, max_new_tokens=new_cap,
                            slots=slots, prefill_chunk=prefill_chunk,
                            preset_overrides={"max_seq_len": max_seq_len},
                            kv_pages=kv_pages, drafter="self",
                            spec_k=spec_k),
                  name=name, route_prefix=f"/{name}")
    try:
        # compile every replica's programs off-meter (prefill + verify +
        # drafter); a lazily-compiling replica would pollute p99 TTFT
        # with multi-second compiles, asymmetrically between the arms
        h.remote({"prompt": "warmup"}).result(timeout=600)
        router = h._get_router()
        for rep in list(router._replicas):
            ray_tpu.get(rep.handle_request.remote(
                "__call__", ({"prompt": "warmup"},), {}), timeout=600)
        time.sleep(1.0)  # let the warmup digests propagate fleet-wide

        def rep_stats():
            out = []
            for rep in list(router._replicas):
                out.append(ray_tpu.get(rep.handle_request.remote(
                    "scheduler_stats", (), {}), timeout=60))
            return out

        st0 = rep_stats()
        sh = h.options(stream=True)
        lock = threading.Lock()
        results = []
        errors = [0]
        t_start = time.perf_counter()

        def one(at, prompt, budget):
            delay = t_start + at - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            t0 = time.perf_counter()
            times = []
            try:
                for _chunk in sh.remote({"prompt": prompt, "stream": True,
                                         "max_new_tokens": budget}):
                    times.append(time.perf_counter())
            except Exception:
                with lock:
                    errors[0] += 1
                return
            with lock:
                results.append((t0, times))

        threads = [threading.Thread(target=one, args=req) for req in load]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        st1 = rep_stats()

        def agg(key):
            return sum(b.get(key, 0) - a.get(key, 0)
                       for a, b in zip(st0, st1))

        hits, misses = agg("prefix_hits"), agg("prefix_misses")
        drafted, accepted = agg("spec_drafted_tokens"), agg(
            "spec_accepted_tokens")
        rounds = agg("spec_rounds")
        emitted = sum(len(times) for _t0, times in results)
        ttfts = [times[0] - t0 for t0, times in results if times]
        wall = (max(t for _t0, ts in results for t in ts)
                - min(t0 for t0, _ts in results))
        out = {
            **_replica_device(st1),
            "affinity": affinity,
            "replicas": replicas,
            "requests_ok": len(results),
            "errors": errors[0],
            "wall_s": round(wall, 3),
            "tokens": emitted,
            "tokens_per_sec": round(emitted / wall, 1),
            "ttft_ms": _percentiles(ttfts),
            "fleet_prefix_hits": hits,
            "fleet_prefix_misses": misses,
            "fleet_hit_rate": round(hits / max(hits + misses, 1), 4),
            "migrations": agg("migrations"),
            "migrated_pages": agg("migrated_pages"),
            "migration_failures": agg("migration_failures"),
            "spec_drafted_tokens": drafted,
            "spec_accepted_tokens": accepted,
            "spec_decode_accept_rate": round(
                accepted / drafted, 4) if drafted else 0.0,
            "spec_tokens_per_step": round(
                sum(b.get("spec_tokens_per_step", 0.0) for b in st1)
                / max(sum(1 for b in st1
                          if b.get("spec_rounds", 0) > 0), 1), 3),
            "spec_rounds": rounds,
        }
        log(f"{name}: hit_rate={out['fleet_hit_rate']} "
            f"p99_ttft={out['ttft_ms']['p99']}ms "
            f"migrations={out['migrations']} "
            f"accept={out['spec_decode_accept_rate']}")
        return out
    finally:
        serve.shutdown()


def _replica_stamp(arm: dict) -> dict:
    return {k: arm[k] for k in ("device", "device_kind", "device_count")}


def fleet_records(args, log) -> list:
    """The ISSUE-18 fleet record pair: affinity-steered vs affinity-blind
    pow-2 over the same Zipf shared-prefix schedule, 4 replicas each.
    This process never touches JAX: the replicas own the devices."""
    import ray_tpu

    n = args.fleet_requests
    prefix_len = 192  # + tail + budget + spec reserve fits max_seq_len 256
    load = _make_prefix_load(args.seed, n, args.fleet_rate,
                             args.new_tokens_cap, prefix_len=prefix_len,
                             n_prefixes=args.fleet_prefixes,
                             max_seq_len=args.max_seq_len)
    from ray_tpu._private.config import global_config

    pt = global_config().serve_page_tokens
    pool = (args.slots * (args.max_seq_len // pt)
            + 8 * (prefix_len // pt) + 1)
    common = dict(replicas=args.fleet_replicas, slots=args.slots,
                  prefill_chunk=args.prefill_chunk,
                  new_cap=args.new_tokens_cap,
                  max_seq_len=args.max_seq_len, kv_pages=pool,
                  spec_k=args.spec_k, skew=args.fleet_skew, log=log)
    ray_tpu.init(num_cpus=max(8, 2 * args.fleet_replicas),
                 object_store_memory=512 * 1024 * 1024)
    try:
        log("fleet arm: affinity steering + migration + spec decode ...")
        aff = _fleet_arm(True, load, **common)
        log("fleet arm: affinity-blind pow-2 baseline ...")
        blind = _fleet_arm(False, load, **common)
    finally:
        ray_tpu.shutdown()
        os.environ.pop("RAY_TPU_SERVE_AFFINITY", None)
        os.environ.pop("RAY_TPU_SERVE_AFFINITY_SKEW", None)
        from ray_tpu._private import config as _conf_mod

        _conf_mod._global_config = None

    # the ISSUE-18 acceptance floor: steering must make prefix reuse a
    # FLEET property, not a per-replica accident
    assert aff["fleet_hit_rate"] >= 0.9, aff
    assert aff["errors"] == 0 and blind["errors"] == 0, (aff, blind)
    assert aff["spec_decode_accept_rate"] > 0, aff
    assert aff["spec_tokens_per_step"] > 1.0, aff
    detail = {"requests": n, "seed": args.seed,
              "rate_rps": args.fleet_rate, "slots": args.slots,
              "preset": args.preset, "prefix_len": prefix_len,
              "max_seq_len": args.max_seq_len, "spec_k": args.spec_k,
              "drafter": "self", "arrivals": "poisson",
              "workload": "prefix",
              "prefix_dist": (f"zipf(s=1.1) over {args.fleet_prefixes} x "
                              f"{prefix_len}-token preambles, "
                              f"4-10-token tails"),
              "measured_from": "cold caches (no warm replay): the ramp "
                               "IS the mechanism under test"}
    return [
        {"metric": "serve_fleet_affinity_hit_rate",
         "value": aff["fleet_hit_rate"], "unit": "fraction",
         "detail": {**aff, **detail}},
        {"metric": "serve_fleet_blind_hit_rate",
         "value": blind["fleet_hit_rate"], "unit": "fraction",
         "detail": {**blind, **detail}},
        {"metric": "serve_fleet_affinity_p99_ttft_ms",
         "value": aff["ttft_ms"]["p99"], "unit": "ms",
         "detail": {"vs_blind_p99_ttft_ms": blind["ttft_ms"]["p99"],
                    "vs_blind_p50_ttft_ms": blind["ttft_ms"]["p50"],
                    "affinity_p50_ttft_ms": aff["ttft_ms"]["p50"],
                    "migrations": aff["migrations"],
                    "migrated_pages": aff["migrated_pages"],
                    **detail, **_replica_stamp(aff)}},
        {"metric": "serve_fleet_spec_decode_accept_rate",
         "value": aff["spec_decode_accept_rate"], "unit": "fraction",
         "detail": {"spec_tokens_per_step": aff["spec_tokens_per_step"],
                    "spec_drafted_tokens": aff["spec_drafted_tokens"],
                    "spec_accepted_tokens": aff["spec_accepted_tokens"],
                    "spec_rounds": aff["spec_rounds"],
                    **detail, **_replica_stamp(aff)}},
    ]


def loadgen_main(args) -> None:
    log = lambda m: print(f"bench_serve: {m}", file=sys.stderr)  # noqa: E731
    prov = _this_process_device()
    common = dict(slots=args.slots, new_tokens_cap=args.new_tokens_cap,
                  prefill_chunk=args.prefill_chunk,
                  prefix_len=args.prefix_len,
                  max_seq_len=args.max_seq_len)
    base_detail = {"requests": args.requests, "seed": args.seed,
                   "slots": args.slots, "preset": args.preset,
                   "new_tokens_cap": args.new_tokens_cap,
                   "arrivals": "poisson"}
    records = []

    # ---- ISSUE-13: Zipf shared-prefix workload, three-way ----
    # paged arena + radix prefix cache vs the PR-9 continuous arena vs
    # request-level batching, same offered load (saturating, so tokens/s
    # measures CAPACITY, not the arrival rate). The paged pool gets
    # headroom for the radix working set (the 8 preambles stay resident)
    # on top of the slots' demand — that residency IS the mechanism being
    # measured; the scheduler stats in the detail show what it held
    from ray_tpu._private.config import global_config

    pt = global_config().serve_page_tokens  # the scheduler's actual size
    pool = (args.slots * (args.max_seq_len // pt)
            + 8 * (args.prefix_len // pt) + 1)
    pfx_detail = {**base_detail, "workload": "prefix",
                  "rate_rps": args.prefix_rate,
                  "max_seq_len": args.max_seq_len,
                  "new_tokens_dist": "2+3*pareto(1.5), capped at 12",
                  "prefix_dist": (
                      f"zipf(s=1.1) over 8 x {args.prefix_len}-token "
                      f"preambles, 4-10-token tails")}
    log("paged+prefix continuous (zipf shared-prefix workload) ...")
    paged = run_loadgen("continuous", args.preset, args.prefix_rate,
                        args.requests, args.seed, workload="prefix",
                        kv_layout="paged", prefix_cache=True,
                        kv_pages=pool, attn=args.attn, **common)
    log("PR-9 contiguous continuous (zipf shared-prefix workload) ...")
    cont_p = run_loadgen("continuous", args.preset, args.prefix_rate,
                         args.requests, args.seed, workload="prefix",
                         kv_layout="contiguous", **common)
    log("request-level batch (zipf shared-prefix workload) ...")
    base_p = run_loadgen("batch", args.preset, args.prefix_rate,
                         args.requests, args.seed, workload="prefix",
                         **common)
    paged_speedup = paged["tokens_per_sec"] / max(
        cont_p["tokens_per_sec"], 1e-9)
    records += [
        {"metric": "serve_loadgen_paged_prefix_tokens_per_sec",
         "value": paged["tokens_per_sec"], "unit": "tokens/s",
         "detail": {**paged, **pfx_detail, **prov}},
        {"metric": "serve_loadgen_continuous_prefix_tokens_per_sec",
         "value": cont_p["tokens_per_sec"], "unit": "tokens/s",
         "detail": {**cont_p, **pfx_detail, **prov}},
        {"metric": "serve_loadgen_request_batch_prefix_tokens_per_sec",
         "value": base_p["tokens_per_sec"], "unit": "tokens/s",
         "detail": {**base_p, **pfx_detail, **prov}},
        {"metric": "serve_paged_prefix_speedup",
         "value": round(paged_speedup, 2), "unit": "x",
         "detail": {"vs": "PR-9 contiguous continuous, same offered load",
                    "prefix_hit_rate": paged.get("prefix_hit_rate"),
                    # arena accounting, auditable from the record alone:
                    # the paged pool carries the radix working set ON TOP
                    # of the slots' demand — that residency is the
                    # mechanism being measured, not hidden headroom
                    "paged_pool_pages": paged["scheduler"]["num_pages"],
                    "paged_page_tokens":
                        paged["scheduler"]["page_tokens"],
                    "paged_peak_pages_in_use":
                        paged["scheduler"]["peak_pages_in_use"],
                    "contiguous_arena_tokens":
                        args.slots * args.max_seq_len,
                    "paged_p99_ttft_ms": paged["ttft_ms"]["p99"],
                    "continuous_p99_ttft_ms": cont_p["ttft_ms"]["p99"],
                    "paged_p50_ttft_ms": paged["ttft_ms"]["p50"],
                    "continuous_p50_ttft_ms": cont_p["ttft_ms"]["p50"],
                    **pfx_detail, **prov}},
    ]

    # ---- ISSUE-20: paged attention lane, in-place vs gathered-view ----
    # the SAME paged scheduler + radix cache + offered load, only the
    # attention lane differs: the in-place lane attends through the page
    # table, the gather baseline materializes every slot's provisioned
    # logical view per layer per step. attn_bytes_moved in the detail is
    # the audit trail — the gather arm's traffic tracks provisioning
    lane = paged["scheduler"]["attn_lane"]
    if lane != "gather":
        log("paged+prefix continuous, gathered-view attn lane "
            "(measured baseline) ...")
        paged_g = run_loadgen("continuous", args.preset, args.prefix_rate,
                              args.requests, args.seed, workload="prefix",
                              kv_layout="paged", prefix_cache=True,
                              kv_pages=pool, attn="gather", **common)
        assert paged_g["scheduler"]["attn_lane"] == "gather", (
            "gather arm resolved the wrong lane")
        lane_speedup = paged["tokens_per_sec"] / max(
            paged_g["tokens_per_sec"], 1e-9)
        records += [
            {"metric": "serve_loadgen_paged_gather_tokens_per_sec",
             "value": paged_g["tokens_per_sec"], "unit": "tokens/s",
             "detail": {**paged_g, **pfx_detail, **prov}},
            {"metric": "serve_paged_attn_lane_speedup",
             "value": round(lane_speedup, 2), "unit": "x",
             "detail": {"vs": "gathered-view lane, same paged scheduler "
                              "and offered load",
                        "attn_lane": lane,
                        "inplace_attn_bytes_moved":
                            paged["scheduler"]["attn_bytes_moved"],
                        "gather_attn_bytes_moved":
                            paged_g["scheduler"]["attn_bytes_moved"],
                        "inplace_p99_ttft_ms": paged["ttft_ms"]["p99"],
                        "gather_p99_ttft_ms": paged_g["ttft_ms"]["p99"],
                        **pfx_detail, **prov}},
        ]

    # ---- ISSUE-9 continuity: mixed workload, continuous vs batch ----
    # (the PR-9 record, re-measured on the PR-9 contiguous arena: the
    # mixed-length heavy-tail load where iteration-level scheduling wins;
    # uniform near-window-length prompts would instead flatter the
    # whole-prompt-prefill batch path)
    mix_detail = {**base_detail, "workload": "mixed",
                  "rate_rps": args.rate,
                  "new_tokens_dist": "1+4*pareto(1.5), capped"}
    log("PR-9 contiguous continuous (mixed workload) ...")
    cont = run_loadgen("continuous", args.preset, args.rate, args.requests,
                       args.seed, workload="mixed",
                       kv_layout="contiguous", **common)
    log("request-level batch baseline (mixed workload) ...")
    base = run_loadgen("batch", args.preset, args.rate, args.requests,
                       args.seed, workload="mixed", **common)
    speedup = cont["tokens_per_sec"] / max(base["tokens_per_sec"], 1e-9)
    ttft_ratio = (base["ttft_ms"]["p99"] or 0.0) / max(
        cont["ttft_ms"]["p99"] or 1e-9, 1e-9)
    records += [
        {"metric": "serve_loadgen_continuous_tokens_per_sec",
         "value": cont["tokens_per_sec"], "unit": "tokens/s",
         "detail": {**cont, **mix_detail, **prov}},
        {"metric": "serve_loadgen_request_batch_tokens_per_sec",
         "value": base["tokens_per_sec"], "unit": "tokens/s",
         "detail": {**base, **mix_detail, **prov}},
        {"metric": "serve_continuous_speedup",
         "value": round(speedup, 2), "unit": "x",
         "detail": {"p99_ttft_improvement_x": round(ttft_ratio, 2),
                    "continuous_p99_ttft_ms": cont["ttft_ms"]["p99"],
                    "baseline_p99_ttft_ms": base["ttft_ms"]["p99"],
                    "continuous_p50_ttft_ms": cont["ttft_ms"]["p50"],
                    "baseline_p50_ttft_ms": base["ttft_ms"]["p50"],
                    **mix_detail, **prov}},
    ]
    for rec in records:
        print(json.dumps(rec))
    if args.json_out:
        _write_doc(records, args.json_out)


def _write_doc(records, path) -> None:
    doc = {
        "suite": "serve_llm_continuous_batching",
        "captured": time.strftime("%Y-%m-%d %H:%M:%S"),
        "host": __import__("platform").platform(),
        "records": records,
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="gpt2_small")
    ap.add_argument("--prompt-len", type=int, default=128)
    ap.add_argument("--new-tokens", type=int, default=64)
    ap.add_argument("--serve", action="store_true",
                    help="drive the full Serve deployment (continuous "
                         "batching) instead of the raw decode program")
    ap.add_argument("--loadgen", action="store_true",
                    help="open-loop load generator: continuous vs "
                         "request-level batching at the same offered load")
    ap.add_argument("--fleet", action="store_true",
                    help="ISSUE-18 fleet arms: 4 replicas through the real "
                         "control plane, prefix-affinity steering + page "
                         "migration + speculative decoding vs affinity-"
                         "blind pow-2, same Zipf shared-prefix schedule")
    ap.add_argument("--fleet-replicas", type=int, default=4)
    ap.add_argument("--fleet-rate", type=float, default=8.0,
                    help="fleet-arm Poisson arrival rate (req/s); fast "
                         "enough that the blind arm's cold prefills queue "
                         "(the contrast under test) while digest "
                         "propagation (0.5s reconcile) still keeps up")
    ap.add_argument("--fleet-requests", type=int, default=320)
    ap.add_argument("--fleet-prefixes", type=int, default=8,
                    help="distinct Zipf preambles in the fleet schedule; "
                         "affinity pins each to one holder, pow-2 "
                         "scatters them across the fleet")
    ap.add_argument("--fleet-skew", type=int, default=4,
                    help="affinity load-skew bound for the fleet arms "
                         "(holder inflight may exceed the min by this "
                         "much before steering falls back + migrates)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens per speculative round (fleet arms)")
    ap.add_argument("--rate", type=float, default=75.0,
                    help="mixed-workload Poisson arrival rate (req/s); the "
                         "default saturates the request-level baseline "
                         "on a CPU host so the capacity gap is visible")
    ap.add_argument("--prefix-rate", type=float, default=600.0,
                    help="shared-prefix-workload arrival rate (req/s); "
                         "must saturate BOTH continuous schedulers so "
                         "tokens/s measures capacity, not arrivals")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--new-tokens-cap", type=int, default=48)
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="scheduler prefill chunk width (both continuous "
                         "candidates)")
    ap.add_argument("--prefix-len", type=int, default=224,
                    help="shared preamble length (tokens) for the prefix "
                         "workload")
    ap.add_argument("--max-seq-len", type=int, default=256,
                    help="context-window override for the prefix workload "
                         "(preamble + tail + budget must fit)")
    ap.add_argument("--attn", default=None,
                    choices=["auto", "pallas", "reference", "gather"],
                    help="paged attention lane for the paged loadgen arm "
                         "(default: the RAY_TPU_SERVE_PAGED_ATTN config "
                         "default); when it resolves in-place, a gather-"
                         "lane arm runs too for the ISSUE-20 comparison")
    ap.add_argument("--json-out", default="",
                    help="also write the full loadgen suite to this file")
    ap.add_argument("--concurrency", type=int, default=16)
    ap.add_argument("--requests", type=int, default=None,
                    help="total requests (default: 150 loadgen, 64 serve)")
    args = ap.parse_args(argv)
    if args.requests is None:
        args.requests = 150 if args.loadgen else 64

    if args.loadgen and args.fleet:
        # --loadgen runs the model in THIS process, which then holds the
        # chip; --fleet's replicas each need one. One process, one chip.
        ap.error("--loadgen and --fleet are two commands: the loadgen "
                 "arms run the model in this process, the fleet arms in "
                 "replica processes, and a chip belongs to one process")
    if args.loadgen or args.fleet:
        if args.preset == "gpt2_small":
            args.preset = "llama_debug"  # loadgen default: runnable anywhere
        if args.fleet:
            log = lambda m: print(  # noqa: E731
                f"bench_serve: {m}", file=sys.stderr)
            records = fleet_records(args, log)
            for rec in records:
                print(json.dumps(rec))
            if args.json_out:
                _write_doc(records, args.json_out)
            return
        loadgen_main(args)
        return

    if args.serve:
        detail = bench_serve_path(args.preset, args.new_tokens,
                                  args.concurrency, args.requests)
        print(json.dumps({
            "metric": "serve_llm_decode_tokens_per_sec",
            "value": detail["serve_decode_tokens_per_sec"],
            "unit": "tokens/s",
            "vs_baseline": round(
                detail["serve_decode_tokens_per_sec"] / 1000.0, 4),
            "detail": dict(detail, preset=args.preset,
                           new_tokens=args.new_tokens),
        }))
        return

    detail = bench_decode(args.preset, args.prompt_len, args.new_tokens)
    best = max(detail["per_batch"],
               key=lambda r: r["decode_tokens_per_sec"])
    print(json.dumps({
        "metric": "llm_decode_tokens_per_sec",
        "value": best["decode_tokens_per_sec"],
        "unit": "tokens/s",
        "vs_baseline": round(best["decode_tokens_per_sec"] / 1000.0, 4),
        "detail": dict(detail, **_this_process_device()),
    }))


if __name__ == "__main__":
    main()
