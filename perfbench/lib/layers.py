"""What the per-layer readers added with the program's phase clock share
(PR 24): the counters' deltas, the scheduler thread's seconds by phase, and
the operations of the flash kernel's calls.

A program from before the phase clock reports none of these counters and
names neither its serving programs nor its flash kernel. There a reader has
nothing to read and says so with 0 (``predates_phase_clock``): the driver
runs the traced cells on the parent commit too, with these readers laid over
it, and the harness refuses a line that lacks a listed metric. Where the
program does report the counters, a reader that finds nothing returns
nothing, and the run is refused as before.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from perfbench.lib import peaks, trace

PHASE_PREFIX = "phase_"
# the scheduler waits for the device in these; in ``park`` it has no work
DEVICE_WAITS = ("phase_decode_wait_s", "phase_prefill_wait_s")
PARK = "phase_park_s"
# matrix multiplications over the [S, S] score matrix in one call of each
# kernel (ray_tpu/ops/flash_attention.py): forward QK^T and PV; dQ
# recomputes QK^T, then dO V^T and dS K; dK/dV recomputes QK^T, then
# P^T dO, dO V^T and dS^T Q
FLASH_MATMULS = {"flash_attention_fwd": 2, "flash_attention_bwd_dq": 3,
                 "flash_attention_bwd_dkv": 4}


def predates_phase_clock(ctx: Dict[str, Any]) -> bool:
    """True for a serving program that reports no phase clock: the parent
    of PR 24, whose counters and program names these readers cannot find."""
    return PARK not in ctx["counters"].get("end", {})


def phase_seconds(ctx: Dict[str, Any]) -> Dict[str, float]:
    """The scheduler thread's seconds in each phase over the window."""
    return {k: v for k, v in ctx["counters"].get("delta", {}).items()
            if k.startswith(PHASE_PREFIX)}


def mean_ms(ctx: Dict[str, Any], total_key: str,
            count_key: str) -> Optional[float]:
    """d``total_key`` (seconds) over d``count_key``, in ms, over the window;
    0 where nothing was counted."""
    if predates_phase_clock(ctx):
        return 0.0
    d = ctx["counters"].get("delta", {})
    if total_key not in d or count_key not in d:
        return None
    return 1e3 * d[total_key] / d[count_key] if d[count_key] else 0.0


def program(ctx: Dict[str, Any], name: str) -> Optional[Dict[str, Any]]:
    """The traced window's executions of the program ``jit_<name>`` on the
    first device: ``count``, ``sum_s``, ``median_s``; None if there are
    none (or no trace)."""
    t = ctx.get("trace")
    return trace.find(t["programs"], name) if t else None


def flash_flops_per_call(sizes: Dict[str, Any], batch: int, seq_len: int,
                         devices: int) -> Dict[str, float]:
    """Operations one call of each flash kernel REQUIRES on one device:
    a matmul over the causal half of the [S, S] scores of every head is
    ``2 * S * S * D / 2`` operations, times the kernel's matmuls, times the
    device's share of ``batch * heads`` (the mesh shards batch and heads
    only; sequence and head size stay whole)."""
    one = (batch * sizes["num_heads"] / devices
           * seq_len * seq_len * sizes["head_dim"])
    return {kernel: n * one for kernel, n in FLASH_MATMULS.items()}


def flash_roofline_percent(ops: Dict[str, Any], sizes: Dict[str, Any],
                           batch: int, seq_len: int, devices: int,
                           device_kind: str) -> float:
    """Least time for the flash calls the trace shows, over their time."""
    per_call = flash_flops_per_call(sizes, batch, seq_len, devices)
    flops, seconds = 0.0, 0.0
    for kernel, each in per_call.items():
        # the forward's name is no part of the backward kernels' names
        hit = trace.find(ops, kernel)
        if hit:
            flops += hit["count"] * each
            seconds += hit["sum_s"]
    if not seconds:
        return 0.0
    return 100.0 * flops / peaks.peak(device_kind)["flops_bf16"] / seconds
