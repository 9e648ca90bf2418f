"""The operations and bytes a LOOPED model's serving REQUIRES (an Ouro-shaped
model: a stack of Llama-shaped layers that every token goes through
``total_ut_steps`` times, a (K, V) a (pass, layer) and token), computed from
the configuration's own keys (``configs/<name>.json``, the source's
``config.json``) and the client's records, never from the program's own
count of itself — the arithmetic the ``loop.decode_step_roofline``,
``kernel.paged_attn_roofline.loop`` and ``step.loop_attn_share`` per-layer
metrics rest on, kept with the benchmark.

A decode step over a handful of rows is bound by the memory's bandwidth: the
least it can take is the time to read the stack's weights ONCE A PASS (the
same bytes four times: nothing of 4.93 GB stays on the chip between passes),
the head, and the live rows' contexts in every (pass, layer) pool. ``peaks
.kv_bytes_per_token`` counts a layer once and is not edited: a token's bytes
here are its own, times the passes.

A configuration without ``total_ut_steps`` (another model) has nothing to
read here, and every function says so with None.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from perfbench.lib import peaks, trace, turn_work

KERNEL = "paged_attention"


def passes(hp: Dict[str, Any]) -> Optional[int]:
    return hp.get("total_ut_steps")


def layer_params(hp: Dict[str, Any]) -> int:
    """One layer's weights: the four attention projections (heads of the
    configuration's own ``head_dim``), the SwiGLU feed-forward's three
    matrices and the FOUR RMSNorm scales (two behind the sublayers)."""
    d, f = hp["hidden_size"], hp["intermediate_size"]
    q = hp["num_attention_heads"] * hp["head_dim"]
    kv = hp["num_key_value_heads"] * hp["head_dim"]
    return d * (q + 2 * kv) + q * d + 3 * d * f + 4 * d


def head_params(hp: Dict[str, Any]) -> int:
    """The output head (the untied input embedding is only looked up)."""
    return hp["hidden_size"] * hp["vocab_size"]


def token_bytes(hp: Dict[str, Any], itemsize: int = 2) -> int:
    """Keys and values one cached token holds: every K/V head, every layer,
    EVERY PASS."""
    return (2 * hp["num_key_value_heads"] * hp["head_dim"] * itemsize
            * hp["num_hidden_layers"] * passes(hp))


def pair_flops(hp: Dict[str, Any]) -> float:
    """Operations of one (query, key) pair over every head, layer and pass:
    the score and the value's share, ``2 x head_dim`` each (no causal
    discount: ``peaks.attention_least_seconds``' convention)."""
    return (4.0 * hp["num_attention_heads"] * hp["head_dim"]
            * hp["num_hidden_layers"] * passes(hp))


def step_least_seconds(hp: Dict[str, Any], rows: float,
                       context_tokens: float, device_kind: str,
                       itemsize: int = 2) -> float:
    """The least time of ONE plain decode step of ``rows`` live rows whose
    contexts add up to ``context_tokens``: the larger of its bytes at the
    memory's bandwidth — the stack AS MANY TIMES AS THE CONFIGURATION HAS
    PASSES, the final norm a pass, the exit gate, the head, the contexts in
    every (pass, layer) pool — and its operations at the peak rate: two a
    weight and row (a pass), and the rows' (query, key) pairs."""
    p, d = peaks.peak(device_kind), hp["hidden_size"]
    stack = hp["num_hidden_layers"] * layer_params(hp)
    moved = (itemsize * (passes(hp) * (stack + d) + d + 1 + head_params(hp))
             + context_tokens * token_bytes(hp, itemsize))
    flops = (2.0 * rows * (passes(hp) * stack + head_params(hp))
             + context_tokens * pair_flops(hp))
    return max(moved / p["hbm_bytes_per_s"], flops / p["flops_bf16"])


def attention_least_seconds(ctx: Dict[str, Any]) -> Optional[float]:
    """``kernel.paged_attn_roofline``'s arithmetic with a token's bytes
    times the passes: for every token delivered in the traced window its
    whole context read once in every (pass, layer) pool, and for every
    prefill chunk of the window's new requests the larger of its queries'
    operations over its context and that context's bytes."""
    hp, work = ctx["config"], ctx["counters"].get("trace_window")
    if not passes(hp) or not work:
        return None
    p = peaks.peak(ctx["device"]["kind"])
    least = (work["decode_context_tokens"] * token_bytes(hp)
             / p["hbm_bytes_per_s"])
    return least + sum(
        max(q * c * pair_flops(hp) / p["flops_bf16"],
            c * token_bytes(hp) / p["hbm_bytes_per_s"])
        for q, c in work["prefill_chunks"])


def kernel_seconds(ctx: Dict[str, Any]) -> Optional[float]:
    """The summed device time of the events named ``paged_attention``."""
    t = ctx.get("trace")
    hit = trace.find(t["ops"], KERNEL) if t else None
    return hit["sum_s"] if hit and hit["sum_s"] else None


def attention_roofline_percent(ctx: Dict[str, Any]) -> Optional[float]:
    least, spent = attention_least_seconds(ctx), kernel_seconds(ctx)
    return 100.0 * least / spent if least and spent else None


def attention_share_percent(ctx: Dict[str, Any]) -> Optional[float]:
    t, spent = ctx.get("trace"), kernel_seconds(ctx)
    if not passes(ctx["config"]) or not spent or not t.get("busy_s"):
        return None
    return 100.0 * spent / t["busy_s"]


def decode_step_roofline_percent(ctx: Dict[str, Any]) -> Optional[float]:
    """The mean plain step's least time (the contexts of the tokens
    delivered in the traced window, from the client's records, over the
    traced runs that carried decode rows; its rows the cell's slots, every
    one live: the operations' side, which 8 rows leave a thirtieth of the
    bytes') over the step program's median device time; where every traced
    turn carried a chunk, over the chunk program's if it carried rows — a
    lower reading of the same thing, as ``moe.decode_step_roofline`` has
    it."""
    hp, work = ctx["config"], ctx["counters"].get("trace_window")
    if not passes(hp) or not work or not ctx.get("trace"):
        return None
    carried = turn_work.row_carrying_runs(ctx)
    hit = turn_work.runs(ctx)
    ran = hit["step"] or (hit["chunk"] if carried else None)
    if not carried or not ran or not ran.get("median_s"):
        return None
    least = step_least_seconds(
        hp, ctx["cell"]["deployment"]["slots"],
        work["decode_context_tokens"] / carried, ctx["device"]["kind"])
    return 100.0 * least / ran["median_s"]
