"""A serving cell: the benchmark's replica deployed as ``build_app`` deploys
the program's (``serve.deployment``, one chip leased where the cluster shows
chips), served by ``serve.run``, loaded through the handle.

Set-up: replica start (weights on the device from the seed, scheduler and
KV pool), one request that compiles the scheduler's two programs, the
reference check, then ``warmup_s`` seconds of the cell's own traffic so that
the window opens on a steady queue and a filled prefix cache. The window is
``--seconds`` long; requests due inside it are drained after it for at most
``grace_s``. In a traced run the replica traces ``trace_s`` seconds in the
middle of the window; counters span the whole window.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Tuple

from perfbench.lib import hostwatch
from perfbench.lib import load as load_lib
from perfbench.lib import traffic


def deploy(ctx: Dict[str, Any]) -> Tuple[Callable, Callable]:
    """Start the replica; returns ``send(request) -> iterator of token
    chunks`` (streamed through the handle) and ``call(method, *args)``."""
    import ray_tpu
    import ray_tpu.serve as serve
    from perfbench.lib.serve_app import BenchLLMServer

    cell = ctx["cell"]
    deployment = serve.deployment(
        name="llm",
        max_ongoing_requests=int(cell["max_ongoing_requests"]))(
        BenchLLMServer)
    actor_options = {}
    if ray_tpu.cluster_resources().get("TPU", 0) >= 1:
        actor_options["num_tpus"] = 1  # as build_app: one chip a replica
    app = deployment.options(
        num_replicas=1, ray_actor_options=actor_options).bind(
        bench={"seed": ctx["seed"], "preset": ctx["preset"],
               "overrides": ctx["overrides"],
               "require_tpu": ctx["require_tpu"],
               "trace_dir": ctx["trace_dir"]},
        max_new_tokens=16, temperature=0.0, **cell["deployment"])
    handle = serve.run(app, name="llm", route_prefix="/llm", timeout_s=1100)
    streaming = handle.options(stream=True)

    def send(request: traffic.Request):
        return iter(streaming.remote({
            "prompt_ids": request.prompt_ids, "stream": True,
            "max_new_tokens": request.max_new_tokens, "temperature": 0.0}))

    def call(method: str, *args):
        return getattr(handle, method).remote(*args).result(timeout=600)

    return send, call


def measure(ctx: Dict[str, Any], send: Callable, call: Callable,
            mix: Dict[str, Any], vocab: int, seconds: float,
            traced: bool) -> Dict[str, Any]:
    """Warm up with ``mix``, then measure one window of it."""
    cell = ctx["cell"]
    load = load_lib.Load(traffic.RequestStream(mix, ctx["seed"], vocab), send)
    load.start()
    time.sleep(float(cell["warmup_s"]))
    info_before = call("bench_info")
    compiles_before = info_before["compiles"]
    client_before = hostwatch.process_reading()
    before = call("scheduler_stats")
    window_start = time.time()
    t0 = time.perf_counter()
    summary, traced_at, late = None, None, []
    if traced:
        hostwatch.sleep_until(t0 + 0.4 * seconds, t0, late)
        call("trace_start")
        traced_at = [time.perf_counter()]
        hostwatch.sleep_until(traced_at[0] + float(cell["trace_s"]), t0, late)
        traced_at.append(time.perf_counter())
        summary = call("trace_stop")
    hostwatch.sleep_until(t0 + seconds, t0, late)
    t1 = time.perf_counter()
    after = call("scheduler_stats")
    info_after = call("bench_info")
    host = {"client_slices_late": late,
            "client": hostwatch.delta(client_before,
                                      hostwatch.process_reading()),
            "replica": hostwatch.delta(info_before["host"],
                                       info_after["host"])}
    load.stop(float(cell["grace_s"]))
    records = load.snapshot()
    seen = load_lib.window_results(records, t0, t1)
    trace_window = {}
    if traced_at:
        trace_window = load_lib.attention_work(
            records, *traced_at, int(cell["deployment"]["prefill_chunk"]))
    delta = {k: after[k] - before[k] for k in after
             if isinstance(after[k], (int, float))
             and not isinstance(after[k], bool)
             and isinstance(before.get(k), (int, float))}
    return {"seen": seen, "delta": delta, "end": after, "trace": summary,
            "trace_window": trace_window, "window_start": window_start,
            "host": host,
            "compiles_in_window":
                call("bench_info")["compiles"] - compiles_before}


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    import ray_tpu.serve as serve

    cell = ctx["cell"]
    send, call = deploy(ctx)
    t_deployed = time.time()

    # ---- correct, part one: the served path against the plain reference
    info = call("bench_info")
    vocab = info["sizes"]["vocab_size"]
    ids = traffic.rng_for(ctx["seed"], 9).integers(
        1, vocab, size=int(cell["check_prompt_tokens"])).tolist()
    served = [t for chunk in send(traffic.Request(
        -1, ids, int(cell["check_new_tokens"]), None)) for t in chunk]
    t_served = time.time()
    check = call("reference_check", ids, served, ctx["config"],
                 ctx["reference_path"])
    t_checked = time.time()

    m = measure(ctx, send, call, ctx["traffic"], vocab, ctx["seconds"],
                bool(ctx["trace"]))
    end = call("bench_info")
    serve.shutdown()

    seen, after = m["seen"], m["end"]
    tol = cell["check_tolerance"]
    checks = {
        "reference_logits": check["logit_err"] <= tol["logit_err"]
        and check["logit_rms_err"] <= tol["logit_rms_err"],
        "served_tokens_near_argmax":
            check["served_margin"] <= tol["served_margin"],
        "check_request_length": len(served) == int(cell["check_new_tokens"]),
        "every_request_answered_in_full": seen["failed"] == 0,
        "no_compile_in_window": m["compiles_in_window"] == 0,
    }
    e2e = {k: seen[k] for k in ("serve_tokens_per_s", "ttft_p95_ms",
                                "gap_p95_ms") if k in seen}
    e2e["setup_s"] = m["window_start"] - ctx["t_process_start"]
    return {
        "correct": all(checks.values()), "checks": checks,
        "compared": {
            **{k: {"value": check[k], "limit": tol[k]} for k in sorted(tol)},
            "requests_failed": {"value": seen["failed"], "limit": 0},
            "compiles_in_window": {"value": m["compiles_in_window"],
                                   "limit": 0}},
        "attempted": seen["attempted"], "failed": seen["failed"],
        "e2e": e2e, "device": end["device"], "trace": m["trace"],
        "clock": {"worker_start_s": info["first_line"] - ctx["t_init"],
                  "replica_ready_s": info["ready"] - info["first_line"]},
        "sizes": info["sizes"],
        "counters": {"delta": m["delta"], "end": after, "window": seen,
                     "trace_window": m["trace_window"]},
        "setup_phases": {
            "open_chip": info["chip_open"] - info["first_line"],
            "replica_build": info["ready"] - info["chip_open"],
            "deployed": t_deployed - info["ready"],
            "first_request": t_served - t_deployed,
            "reference_check": t_checked - t_served,
            "warmup_traffic": m["window_start"] - t_checked},
        "notes": {"reference_check": check, "window": seen,
                  "compiles_in_window": m["compiles_in_window"],
                  "memory_stats": end["memory_stats"],
                  "scheduler": {k: after.get(k) for k in (
                      "attn_lane", "slots", "prefill_chunk", "arena_len",
                      "usable_pages", "peak_pages_in_use",
                      "peak_queue_depth", "compiled_programs",
                      "max_active_slots", "platform")},
                  "delta": {k: v for k, v in m["delta"].items() if v},
                  "host": m["host"],
                  "program_runs": (m["trace"] or {}).get("program_runs")},
    }
