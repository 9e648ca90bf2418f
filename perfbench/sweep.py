"""Find the knee of an open-loop serving cell, once, on the chip.

    python3 perfbench/sweep.py --workload mistral7b_chat --rates 3,4,5,6,7 \
        --seconds 20

One replica is started as the cell starts it; the cell's traffic is then
offered at each rate in turn for ``--seconds`` (after the cell's warm-up at
that rate), and a line per rate says what came back: tokens per second,
tails, and how the scheduler's queue stood at the end. The knee is the
highest rate whose backlog does not grow; the cell's traffic file is then
given four fifths of it, as a number. A builder's tool: the driver never
runs it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perfbench import run as run_module  # noqa: E402
from perfbench.lib import manifest as manifest_lib  # noqa: E402
from perfbench.lib import serve_cell  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    args.trace = 0
    manifest = manifest_lib.load()
    ctx = run_module.build_context(args, manifest, require_tpu=True)
    run_module.prepare_environment()

    import ray_tpu
    import ray_tpu.serve as serve

    ctx["t_init"] = time.time()
    ray_tpu.init(log_to_driver=False)
    try:
        send, call = serve_cell.deploy(ctx)
        vocab = call("bench_info")["sizes"]["vocab_size"]
        for rate in [float(r) for r in args.rates.split(",")]:
            mix = json.loads(json.dumps(ctx["traffic"]))
            mix["arrival"]["rate_per_s"] = rate
            m = serve_cell.measure(ctx, send, call, mix, vocab,
                                   args.seconds, False)
            seen, end = m["seen"], m["end"]
            print(json.dumps({
                "rate_per_s": rate, "attempted": seen["attempted"],
                "failed": seen["failed"],
                "serve_tokens_per_s": seen["serve_tokens_per_s"],
                "ttft_p50_ms": seen.get("ttft_p50_ms"),
                "ttft_p95_ms": seen.get("ttft_p95_ms"),
                "gap_p50_ms": seen.get("gap_p50_ms"),
                "gap_p95_ms": seen.get("gap_p95_ms"),
                "late_p95_ms": seen["generator_late_p95_ms"],
                "queue_depth_end": end["queue_depth"],
                "peak_queue_depth": end["peak_queue_depth"],
                "active_slots_end": end["active_slots"],
                "decode_steps": m["delta"]["decode_steps"],
                "prefill_chunks": m["delta"]["prefill_chunks"]}), flush=True)
            time.sleep(3.0)
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
