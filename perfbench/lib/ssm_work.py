"""The operations and bytes the state-space (Mamba-2) mixer of a
Nemotron-H-shaped model REQUIRES, and the bytes one of its decode steps must
move, computed from the configuration's own keys (``configs/<name>.json``:
the source's ``config.json`` plus what it assumes) — the arithmetic the
``kernel.ssm_*_roofline``, ``ssm.decode_step_roofline``, ``step.ssm_share``
and ``moe.held_route_share`` per-layer metrics rest on, kept with the
benchmark.

A head's state is ``[mamba_head_dim, ssm_state_size]`` float32, H of them a
layer (2,097,152 B at the published sizes); beside it a sequence carries the
convolution's last ``conv_kernel - 1`` inputs over the joined x, B, C
(73,728 B), which the scan's kernels never see.

  step   a live row's scan state, every head of a layer, read once and
         written once (the kernel ``ssm_step``)
  chunk  the larger of its operations at the peak rate and its bytes at the
         memory's bandwidth (the kernel ``ssm_chunk_scan``). Operations, a
         real token and layer: the state read by C and updated by B x,
         ``2 P N`` each a head, and inside a block of ``chunk_size`` tokens
         the causal half of the scores (a group) and of their product with
         the values (a head), ``2 N L / 2`` and ``2 P L / 2``. Bytes: a
         token's x, B and C in and y out in the served type, and the slot's
         scan state read and written once a layer-call
  decode step  the weights that are multiplied — the Mamba-2 layers' and the
         attention layers' projections whole, of an expert layer the router,
         the shared expert and the experts that received a row (the window's
         mean a layer-call, of the experts HELD), the output head — and the
         live rows' two states read and written, and the keys and values of
         the live contexts read, once

The counts come from the program's counters (``scheduler_stats()``:
``ssm_step_rows`` = live rows x layers, ``ssm_chunk_calls`` = layer-calls,
``ssm_chunk_tokens`` = their real tokens, ``moe_experts_hit`` /
``moe_layer_calls``, ``moe_rows_routed`` / ``moe_routes_chosen``), summed
over the window and brought to the traced part of it as
``sala_work.traced_share`` brings the other state kinds'. A program without
the counters (another model, or one from before them) reads nothing, and
nothing here raises on it.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from perfbench.lib import peaks, sala_work, trace, turn_work

STEP, CHUNK = "ssm_step", "ssm_chunk_scan"
KERNELS = (STEP, CHUNK)


def inner(hp: Dict[str, Any]) -> int:
    return hp["mamba_num_heads"] * hp["mamba_head_dim"]


def conv_width(hp: Dict[str, Any]) -> int:
    return inner(hp) + 2 * hp["n_groups"] * hp["ssm_state_size"]


def scan_state_bytes(hp: Dict[str, Any]) -> int:
    """One row's scan state of ONE layer, float32."""
    return inner(hp) * hp["ssm_state_size"] * 4


def conv_state_bytes(hp: Dict[str, Any]) -> int:
    """The inputs one row's convolution carries in ONE layer, float32."""
    return (hp["conv_kernel"] - 1) * conv_width(hp) * 4


def chunk_flops_per_token(hp: Dict[str, Any]) -> float:
    """Operations of ONE layer's scan for one real token of a chunk."""
    n, block = hp["ssm_state_size"], hp["chunk_size"]
    across = 2 * 2 * inner(hp) * n
    within = hp["n_groups"] * n * block + inner(hp) * block
    return across + within


def chunk_bytes_per_token(hp: Dict[str, Any], itemsize: int = 2) -> int:
    """x, B and C in and y out, ONE layer and token, in the served type."""
    return (conv_width(hp) + inner(hp)) * itemsize


def layers_of(hp: Dict[str, Any], symbol: str) -> int:
    return hp["hybrid_override_pattern"].count(symbol)


def window_counters(ctx: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """The window's deltas of the program's counters for the mixer, or None
    where the program reports none."""
    d = ctx.get("counters", {}).get("delta", {})
    if not d.get("ssm_step_rows") and not d.get("ssm_chunk_calls"):
        return None
    return d


def step_least_seconds(ctx: Dict[str, Any]) -> Optional[float]:
    """The traced window's live scan states read and written once."""
    d = window_counters(ctx)
    if d is None:
        return None
    rows = d.get("ssm_step_rows", 0) * sala_work.traced_share(ctx, "step", d)
    return (rows * 2 * scan_state_bytes(ctx["config"])
            / peaks.peak(ctx["device"]["kind"])["hbm_bytes_per_s"])


def chunk_least_seconds(ctx: Dict[str, Any]) -> Optional[float]:
    """The traced window's chunks: the larger of their operations at the
    peak rate and their bytes at the memory's bandwidth."""
    d = window_counters(ctx)
    if d is None:
        return None
    hp, p = ctx["config"], peaks.peak(ctx["device"]["kind"])
    tokens, calls = d.get("ssm_chunk_tokens", 0), d.get("ssm_chunk_calls", 0)
    flops = tokens * chunk_flops_per_token(hp)
    moved = (tokens * chunk_bytes_per_token(hp)
             + calls * 2 * scan_state_bytes(hp))
    return sala_work.traced_share(ctx, "chunk", d) * max(
        flops / p["flops_bf16"], moved / p["hbm_bytes_per_s"])


def kernel_seconds_at_most(ctx: Dict[str, Any], kernel: str) -> float:
    """The most device time the traced events named ``kernel`` can have
    taken. The trace's table gives an op the time none of its children
    covers (``trace.self_times``), and XLA's asynchronous copies and slices
    (``copy-done``, ``slice-done``) can fall INSIDE a kernel's event and
    count as its children: the kernel's own column is then too short (a
    fourteen-layer cut read 974 GB/s so, over the memory's 819: PERF.md 6,
    PR 59). A custom call has no other child, so its events' whole time is
    at most its own column plus every ``-done`` op of the programs it ran
    in; the summary holds no event, so which of them were inside is not
    known, and all are taken."""
    t = ctx["trace"]
    spent = 0.0
    for ops in (t.get("ops_by_program") or {"": t.get("ops", {})}).values():
        hit = trace.find(ops, kernel)
        if hit:
            spent += hit["sum_s"] + sum(
                row["sum_s"] for name, row in ops.items() if "-done" in name)
    return spent


def kernel_roofline_percent(ctx: Dict[str, Any], kernel: str,
                            least) -> Optional[float]:
    """``least(ctx)`` seconds over ``kernel_seconds_at_most``: AT LEAST this
    share, never more than the kernel reached; nothing without a trace, the
    counters or the kernel."""
    if not ctx.get("trace"):
        return None
    needed = least(ctx)
    spent = kernel_seconds_at_most(ctx, kernel)
    if not needed or not spent:
        return None
    return 100.0 * needed / spent


def ssm_share_percent(ctx: Dict[str, Any]) -> Optional[float]:
    t = ctx.get("trace")
    if not t or not t.get("busy_s"):
        return None
    spent = sala_work.kernel_seconds(ctx, *KERNELS)
    return 100.0 * spent / t["busy_s"] if spent else None


def held_route_share_percent(ctx: Dict[str, Any]) -> Optional[float]:
    """The routes that landed on experts held here over every route the live
    rows chose: 50 where two chips share a layer and routing is even."""
    d = ctx.get("counters", {}).get("delta", {})
    if not d.get("moe_routes_chosen"):
        return None
    return 100.0 * d.get("moe_rows_routed", 0) / d["moe_routes_chosen"]


def expert_bytes(hp: Dict[str, Any], itemsize: int = 2) -> int:
    """One two-matrix expert."""
    return 2 * hp["hidden_size"] * hp["moe_intermediate_size"] * itemsize


def dense_weight_bytes(hp: Dict[str, Any], itemsize: int = 2) -> int:
    """What every step reads whatever the routing: the Mamba-2 layers' in-
    and out-projections, convolution and head constants, the attention
    layers' four projections, an expert layer's router and shared expert,
    every layer's norm, the final norm and the output head."""
    d, n_in = hp["hidden_size"], inner(hp)
    mamba = (d * (n_in + conv_width(hp) + hp["mamba_num_heads"])
             + (hp["conv_kernel"] + 1) * conv_width(hp)
             + 3 * hp["mamba_num_heads"] + n_in + n_in * d + d)
    q = hp["num_attention_heads"] * hp["head_dim"]
    kv = hp["num_key_value_heads"] * hp["head_dim"]
    attn = d * (q + 2 * kv) + q * d + d
    experts = (d * hp["n_routed_experts"] + hp["n_routed_experts"] + d
               + hp["n_shared_experts"] * 2 * d
               * hp["moe_shared_expert_intermediate_size"])
    head = d * hp["vocab_size"] + d
    return itemsize * (layers_of(hp, "M") * mamba + layers_of(hp, "*") * attn
                       + layers_of(hp, "E") * experts + head)


def kv_bytes_per_token(hp: Dict[str, Any], itemsize: int = 2) -> int:
    """Keys and values one cached token holds over the attention layers."""
    return (2 * layers_of(hp, "*") * hp["num_key_value_heads"]
            * hp["head_dim"] * itemsize)


def decode_step_least_seconds(ctx: Dict[str, Any]) -> Optional[float]:
    """The least time of ONE decode step of the window: the weights it
    touches, the mean step's live states read and written, and the keys and
    values of the live contexts, at the memory's bandwidth."""
    d = window_counters(ctx)
    if d is None or not d.get("decode_steps"):
        return None
    hp = ctx["config"]
    hit = (d["moe_experts_hit"] / d["moe_layer_calls"]
           if d.get("moe_layer_calls") else 0.0)
    rows = d.get("ssm_step_rows", 0) / d["decode_steps"]  # x layers
    states = rows * 2 * (scan_state_bytes(hp) + conv_state_bytes(hp))
    carried = turn_work.row_carrying_runs(ctx)
    work = ctx["counters"].get("trace_window") or {}
    keys = (work.get("decode_context_tokens", 0) / max(carried, 1)
            * kv_bytes_per_token(hp))
    moved = (dense_weight_bytes(hp)
             + layers_of(hp, "E") * hit * expert_bytes(hp) + states + keys)
    return moved / peaks.peak(ctx["device"]["kind"])["hbm_bytes_per_s"]


def decode_step_roofline_percent(ctx: Dict[str, Any]) -> Optional[float]:
    """``decode_step_least_seconds`` over the median device time of the
    program that ran the window's steps: the plain step's, or the chunk
    program's where every traced turn carried a chunk (a lower reading of
    the same thing), as ``retention.decode_step_roofline`` reads."""
    if not ctx.get("trace"):
        return None
    least = decode_step_least_seconds(ctx)
    hit = turn_work.runs(ctx)
    ran = hit["step"] or (hit["chunk"] if turn_work.row_carrying_runs(ctx)
                          else None)
    if not least or not ran or not ran.get("median_s"):
        return None
    return 100.0 * least / ran["median_s"]
