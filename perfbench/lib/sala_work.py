"""The operations and bytes the two mixers of a MiniCPM-SALA-shaped model
REQUIRE, computed from the configuration's own keys
(``configs/<name>.json``, the source's ``config.json`` plus the sizes it
assumes) — the arithmetic the ``kernel.linear_attn_roofline`` and
``kernel.sparse_attn_roofline`` per-layer metrics rest on, kept with the
benchmark.

Decayed linear attention (``lightning-attn``): a chunk of ``C`` tokens in
blocks of ``B`` is, a head and a block, four matrix products of ``2 B B d``
or ``2 B d d`` operations (scores, their product with the values, the
queries against the state, the keys' update of the state); a step reads and
writes each live row's ``[d, d]`` float32 state a head once.

Block-selected attention (``minicpm4``): a step must read, a layer, the keys
and values of the tokens its rows attend (once a K/V head: each K/V group
chooses its own blocks) and the pooled keys of its rows' contexts once; a
chunk's queries score the pooled keys before them and attend their chosen
tokens, at the peak rate.

The counts come from the program's counters (``scheduler_stats()``:
``linear_step_rows``, ``sparse_step_*``, ``sparse_tokens_*``), summed over
the window and brought to the traced part of it by the share of the
window's programs the trace holds.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from perfbench.lib import peaks, trace

BLOCK = 128  # tokens of one block of the chunked scan (ops/linear_attention)
KERNELS = ("linear_attention_chunk", "linear_attention_step",
           "sparse_select", "sparse_paged_attention")


def linear_chunk_flops(hp: Dict[str, Any], chunk: int) -> float:
    """Operations of ONE lightning-attn layer over one chunk."""
    d, heads = hp["lightning_head_dim"], hp["lightning_nh"]
    blocks = -(-chunk // BLOCK)
    return blocks * heads * (2 * 2 * BLOCK * BLOCK * d + 2 * 2 * BLOCK * d * d)


def linear_state_bytes(hp: Dict[str, Any]) -> int:
    """One row's state of ONE lightning-attn layer, float32."""
    d = hp["lightning_head_dim"]
    return hp["lightning_nh"] * d * d * 4


def kv_bytes_per_token(hp: Dict[str, Any], itemsize: int = 2) -> int:
    """Keys and values of one token in ONE minicpm4 layer, every K/V head."""
    return 2 * hp["num_key_value_heads"] * hp["head_dim"] * itemsize


def pooled_bytes_per_token(hp: Dict[str, Any]) -> float:
    """Pooled key rows (float32, one every kernel_stride tokens) of one
    token of context in ONE minicpm4 layer."""
    return (hp["num_key_value_heads"] * hp["head_dim"] * 4
            / hp["sparse_config"]["kernel_stride"])


def sparse_flops(hp: Dict[str, Any], attended: float, context: float):
    """Operations of queries that attend ``attended`` tokens in all and
    have ``context`` tokens of context in all: scores and values over what
    they attend, scores over the pooled keys of their contexts."""
    hd = hp["num_attention_heads"] * hp["head_dim"]
    return (4.0 * hd * attended
            + 2.0 * hd * context / hp["sparse_config"]["kernel_stride"])


def window_counters(ctx: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """The window's deltas of the program's counters for the two mixers, or
    None where the program reports none (another model, or a program from
    before them)."""
    d = ctx["counters"].get("delta", {})
    if not d.get("sparse_rows") or not d.get("decode_steps"):
        return None
    return d


def traced_share(ctx: Dict[str, Any], program: str, counter: str,
                 d: Dict[str, float]) -> float:
    """The share of the window's runs of ``program`` that the trace holds."""
    hit = trace.find(ctx["trace"]["programs"], program)
    return hit["count"] / d[counter] if hit and d.get(counter) else 0.0


def linear_least_seconds(ctx: Dict[str, Any]) -> Optional[float]:
    """The least time of the traced window's linear-attention work: its
    chunks' operations at the peak rate, and its steps' live states read
    and written once at the memory's bandwidth."""
    d = window_counters(ctx)
    if d is None:
        return None
    hp, p = ctx["config"], peaks.peak(ctx["device"]["kind"])
    chunk = int(ctx["cell"]["deployment"]["prefill_chunk"])
    chunks = d.get("linear_chunk_calls", 0) * traced_share(
        ctx, "paged_prefill_chunk", "prefill_chunks", d)
    rows = d.get("linear_step_rows", 0) * traced_share(
        ctx, "paged_decode_step", "decode_steps", d)
    return (chunks * linear_chunk_flops(hp, chunk) / p["flops_bf16"]
            + rows * 2 * linear_state_bytes(hp) / p["hbm_bytes_per_s"])


def sparse_least_seconds(ctx: Dict[str, Any]) -> Optional[float]:
    """The least time of the traced window's block-selected attention: a
    step's chosen keys and values and its contexts' pooled keys read once,
    a chunk's scores and values at the peak rate."""
    d = window_counters(ctx)
    if d is None:
        return None
    hp, p = ctx["config"], peaks.peak(ctx["device"]["kind"])
    step_att = d.get("sparse_step_tokens_attended", 0)
    step_ctx = d.get("sparse_step_tokens_context", 0)
    moved = (step_att * kv_bytes_per_token(hp)
             + step_ctx * pooled_bytes_per_token(hp))
    flops = sparse_flops(hp, d["sparse_tokens_attended"] - step_att,
                         d["sparse_tokens_context"] - step_ctx)
    return (moved / p["hbm_bytes_per_s"] * traced_share(
        ctx, "paged_decode_step", "decode_steps", d)
        + flops / p["flops_bf16"] * traced_share(
            ctx, "paged_prefill_chunk", "prefill_chunks", d))


def kernel_seconds(ctx: Dict[str, Any], *names: str) -> float:
    """The summed device time of the traced events of the named kernels."""
    hits = (trace.find(ctx["trace"]["ops"], n) for n in names)
    return sum(h["sum_s"] for h in hits if h)
