"""Benchmark: causal-LM training MFU on the local chip and a 1B-class
second config.

One process that measures on the chip it finds. It fails — prints no
record, exits non-zero — when JAX finds no TPU (a CPU number is never
written under the name of a device metric), when the device kind has no
published peak in ``PEAK_FLOPS``, and when any phase fails.

Prints ONE JSON line {"metric", "value", "unit", "vs_baseline", "detail"}.
Baseline (BASELINE.md): the reference delegates device math to torch; our
target band is 45% MFU for the Train-equivalent path, so vs_baseline is
measured MFU / 0.45.

    python bench.py
    python bench.py --data-regime compute_bound | input_bound
"""

from __future__ import annotations

import json
import sys
import time

PEAK_FLOPS = {
    # published bf16 peak per chip, keyed by jax's device_kind
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,  # v5e (Google Cloud documentation, "TPU v5e")
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6e": 918e12,
    "TPU v6 lite": 918e12,
}


def _chip():
    """The device this process measures on. No TPU, or a TPU whose peak is
    not in the table, is an error: there is no CPU branch and no nominal
    peak. Returns (device, peak_flops, stamp-for-records)."""
    from ray_tpu._private import compile_cache

    compile_cache.enable()
    import jax

    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform "
                         f"{device.platform!r}); nothing is measured on a CPU")
    if device.device_kind not in PEAK_FLOPS:
        raise SystemExit(f"bench: no published peak for device kind "
                         f"{device.device_kind!r}; add it to PEAK_FLOPS "
                         f"with its source")
    stamp = {"device": device.platform, "device_kind": device.device_kind,
             "device_count": len(devices)}
    return device, PEAK_FLOPS[device.device_kind], stamp


def _run_config(cfg, batch: int, seq: int, steps: int):
    """Compile + time one train-step config; returns (dt, n_params)."""
    import jax

    from ray_tpu.models import count_params
    from ray_tpu.models.training import (OptimizerConfig, init_train_state,
                                         make_train_step)

    ocfg = OptimizerConfig(warmup_steps=10, decay_steps=1000)
    state, tx = init_train_state(cfg, ocfg, jax.random.PRNGKey(0))
    # grad_norm logging costs a full extra pass over the grads; clipping
    # inside the optimizer still sees the norm
    step = make_train_step(cfg, tx, log_grad_norm=False)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0,
                                cfg.vocab_size)
    b = {"tokens": tokens}

    state, m = step(state, b)  # compile + warmup
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, m = step(state, b)
    jax.block_until_ready(m["loss"])  # jax returns before the device is done
    dt = (time.perf_counter() - t0) / steps
    return dt, count_params(state.params)


def _mfu_record(metric, dt, n_params, cfg, batch, seq, peak,
                tp=1, dp=1, pp=1, virtual_stages=1):
    tokens_per_step = batch * seq
    # Model FLOPs only (MFU convention — remat recompute excluded):
    # fwd+bwd ≈ 6 flops/param/token + attention 12*L*S*E per token.
    # n_params is the FUSED model; under tensor parallelism each rank
    # executes 1/tp of those flops (column/row shards split every matmul
    # evenly), so the per-device utilization divides by tp. dp replicates
    # compute (no division) and pp splits by stage via n_params already
    # being the per-stage count at the call site.
    flops_per_token = 6 * n_params + 12 * cfg.num_layers * seq * cfg.embed_dim
    flops_per_token_per_rank = flops_per_token / max(int(tp), 1)
    mfu = flops_per_token_per_rank * tokens_per_step / dt / peak
    return {
        "metric": metric,
        "value": round(mfu, 4),
        "unit": "fraction_of_peak",
        "vs_baseline": round(mfu / 0.45, 4),
        "detail": {
            "tokens_per_sec": round(tokens_per_step / dt),
            "step_time_ms": round(dt * 1e3, 2),
            "params": n_params,
            "remat": cfg.remat_policy if cfg.remat else "none",
            # parallelism stamp: MFU records from different grid shapes
            # must not be compared without knowing the axes
            "tp": int(tp),
            "dp": int(dp),
            "pp": int(pp),
            "virtual_stages": int(virtual_stages),
            "flops_per_token_per_rank": int(flops_per_token_per_rank),
        },
    }


def main() -> None:
    """gpt2s train MFU, then the 1B config. A phase that fails fails the
    run."""
    device, peak, stamp = _chip()

    from ray_tpu.models import gpt2_small, gpt_1b

    batch, seq = 16, 1024
    cfg = gpt2_small()
    dt, n_params = _run_config(cfg, batch, seq, steps=20)
    rec = _mfu_record("gpt2s_train_mfu", dt, n_params, cfg, batch, seq, peak)
    rec["detail"].update(stamp)

    # second perf point: a ~1B-param GPT config — the bridge toward the
    # Llama-8B FSDP target; full remat is what fits params + adam + grads
    # in 16 GB
    b1, s1 = 4, 1024
    cfg1 = gpt_1b()
    dt1, n1 = _run_config(cfg1, b1, s1, steps=10)
    rec["detail"]["gpt1b_mfu"] = _mfu_record(
        "gpt1b_train_mfu", dt1, n1, cfg1, b1, s1, peak)
    print(json.dumps(rec))


def _feed_tokens_batch(vocab: int, seq: int, delay_s: float, b):
    """Streaming-feed transform (module-level so it pickles into the
    transform actors): ids -> a [rows, seq] int32 token block, with an
    optional per-block sleep that makes the LOADER the bottleneck (the
    input-bound regime — a stand-in for slow storage/decode)."""
    import numpy as np

    if delay_s:
        time.sleep(delay_s)
    ids = np.asarray(b["id"])
    rng = np.random.default_rng(1234 + int(ids[0]))
    return {"tokens": rng.integers(
        0, vocab, (len(ids), seq)).astype(np.int32)}


def data_regime_main(regime: str) -> None:
    """The input-bound-vs-compute-bound knob, wired through the REAL
    gpt2s trainer: the train step consumes batches from a streaming
    `ray_tpu.data` pipeline via ``StreamingExecutor.feed()`` (read-only
    arena views, acked after each step), and the record reports the
    measured consumer stall fraction — ~0 when compute-bound (the
    stream keeps the trainer fed), large when ``input_bound`` throttles
    the loader below the trainer's demand. This process does all the
    device work and holds the chip; its cluster's workers lease no chip
    and so run on the CPU backend. Records carry this process's device.

        python bench.py --data-regime compute_bound
        python bench.py --data-regime input_bound
    """
    import functools

    log = lambda m: print(f"bench: {m}", file=sys.stderr)  # noqa: E731
    if regime not in ("compute_bound", "input_bound"):
        raise SystemExit(
            f"--data-regime must be compute_bound or input_bound, "
            f"got {regime!r}")
    _, _, prov = _chip()
    import jax
    import numpy as np

    import ray_tpu
    from ray_tpu.data._internal.exchange import ExchangeExecutor
    from ray_tpu.data._internal.streaming import StreamingExecutor
    from ray_tpu.models import gpt2_small
    from ray_tpu.models.training import (OptimizerConfig, init_train_state,
                                         make_train_step)

    cfg = gpt2_small()
    batch, seq, steps = 8, 1024, 24
    ocfg = OptimizerConfig(warmup_steps=10, decay_steps=1000)
    state, tx = init_train_state(cfg, ocfg, jax.random.PRNGKey(0))
    step = make_train_step(cfg, tx, log_grad_norm=False)

    # calibrate the bare step (compile + 3 timed steps) so the
    # input-bound throttle is sized off the MEASURED trainer demand
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    state, m = step(state, {"tokens": tokens})
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    for _ in range(3):
        state, m = step(state, {"tokens": tokens})
    jax.block_until_ready(m["loss"])
    step_dt = (time.perf_counter() - t0) / 3
    # one reader/transform lane: a 2x-the-step-time block delay starves
    # the trainer by construction (expected stall fraction ~0.5)
    delay = 2.0 * step_dt if regime == "input_bound" else 0.0
    log(f"bare step {step_dt * 1e3:.1f} ms; regime={regime} "
        f"block delay {delay * 1e3:.1f} ms")

    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024)
    try:
        ds = ray_tpu.data.range(
            steps * batch, parallelism=steps).map_batches(
            functools.partial(_feed_tokens_batch, cfg.vocab_size, seq,
                              delay))
        ex = StreamingExecutor(ds._ops, batch_size=batch, epochs=3,
                               seed=0, num_readers=1)
        stall = [0.0]
        last_end = [None]
        n_steps = [0]
        state_box = [state]

        def train_step(b):
            now = time.perf_counter()
            if last_end[0] is not None:
                stall[0] += now - last_end[0]
            s2, met = step(state_box[0],
                           {"tokens": np.asarray(b["tokens"])})
            jax.block_until_ready(met["loss"])  # the step really ran
            state_box[0] = s2
            n_steps[0] += 1
            last_end[0] = time.perf_counter()

        t_first_end = None
        try:
            for _ in ex.feed(train_step):
                if t_first_end is None:
                    # first step absorbs executor spin-up + compile
                    # reuse; the stall window starts here
                    t_first_end = last_end[0]
                    stall[0] = 0.0
                if n_steps[0] >= steps:
                    break
        finally:
            ex.shutdown()
        total = max(last_end[0] - t_first_end, 1e-9)
        stall_frac = stall[0] / total
        measured = n_steps[0] - 1  # steps inside the stall window
        rec = {
            "metric": "gpt2s_streamfeed_stall_fraction",
            "value": round(stall_frac, 3),
            "unit": "fraction",
            "detail": {
                "regime": regime,
                "feed": "StreamingExecutor.feed",
                "steps_per_sec": round(measured / total, 2),
                "bare_step_ms": round(step_dt * 1e3, 2),
                "block_delay_ms": round(delay * 1e3, 2),
                "steps": measured,
                "batch": batch, "seq": seq,
                **prov,
            },
        }
        print(json.dumps(rec))

        # -- second arm: the SAME throttled loader, but the plan ends in
        # a seeded random_shuffle run on the streaming all-to-all
        # exchange (producer stage -> R x C channel mesh -> consumer
        # merge), fed to the trainer with the same ack-after-step
        # contract. One loader lane keeps the regime semantics identical
        # to the arm above: input_bound still offers 2x the trainer's
        # demand, so its stall fraction stays large by construction.
        ds2 = ray_tpu.data.range(
            steps * batch, parallelism=steps).map_batches(
            functools.partial(_feed_tokens_batch, cfg.vocab_size, seq,
                              delay)).random_shuffle(seed=1)
        # drop_last: the hash deal leaves ragged per-consumer tails and
        # a jitted train step recompiles per shape — fixed [batch, seq]
        # is the honest trainer-feeding contract
        ex2 = ExchangeExecutor(ds2._ops, batch_size=batch, epochs=3,
                               seed=0, num_producers=1, num_consumers=2,
                               drop_last=True)
        # a silent barrier fallback would report the wrong data path
        assert ex2.is_channel_backed, "exchange arm is not channel-backed"
        stall[0], last_end[0], n_steps[0] = 0.0, None, 0
        t_first_end = None
        try:
            for _ in ex2.feed(train_step):
                if t_first_end is None:
                    t_first_end = last_end[0]
                    stall[0] = 0.0
                if n_steps[0] >= steps:
                    break
        finally:
            ex2.shutdown()
        total = max(last_end[0] - t_first_end, 1e-9)
        measured = n_steps[0] - 1
        ep_stats = ex2.epoch_stats
        rec = {
            "metric": "gpt2s_exchange_stall_fraction",
            "value": round(stall[0] / total, 3),
            "unit": "fraction",
            "detail": {
                "regime": regime,
                "feed": "ExchangeExecutor.feed",
                "mesh": "1x2",
                "steps_per_sec": round(measured / total, 2),
                "bare_step_ms": round(step_dt * 1e3, 2),
                "block_delay_ms": round(delay * 1e3, 2),
                "steps": measured,
                "consumer_skew": (round(ep_stats[0]["skew"], 3)
                                  if ep_stats else None),
                "batch": batch, "seq": seq,
                **prov,
            },
        }
        print(json.dumps(rec))
    finally:
        ray_tpu.shutdown()


if __name__ == "__main__":
    if "--data-regime" in sys.argv:
        idx = sys.argv.index("--data-regime")
        if idx + 1 >= len(sys.argv):
            raise SystemExit(
                "--data-regime needs a value: compute_bound | input_bound")
        data_regime_main(sys.argv[idx + 1])
    else:
        main()
