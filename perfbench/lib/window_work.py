"""The operations and bytes the attention of a model with window layers beside
full ones REQUIRES (a Mellum-2-shaped model: ``layer_types`` of
``sliding_attention`` and ``full_attention``, experts in every layer),
computed from the configuration's own keys (``configs/<name>.json``, the
source's ``config.json``: ``head_dim`` is a key of its own there, and an
expert's width is ``moe_intermediate_size``) — the arithmetic the
``kernel.window_attn_roofline``, ``kernel.global_attn_roofline``,
``window.decode_step_roofline`` and ``paging.window_held_share`` per-layer
metrics rest on, kept with the benchmark.

What the mask ADMITS is counted by the program (``scheduler_stats()``), by
kind of layer and by work, summed over rows and layers, from the cursors
alone:

  ``window_attn_step_keys`` / ``full_attn_step_keys``    the keys a decode
      row reads: ``min(context, window)`` a window layer, the context a full
      one. A step is bound by the memory: their keys AND values read once.
  ``window_attn_chunk_pairs`` / ``full_attn_chunk_pairs``  the (query, key)
      pairs of a chunk's real rows. A chunk of 512 is bound by compute:
      ``4 x head_dim`` operations a pair and query head (scores and values),
      as ``peaks.attention_least_seconds`` reckons.

Summed over the window and brought to the traced part of it as
``sala_work.traced_share`` brings the SALA mixers' (a chunk's work by the
chunk program's traced runs over d``prefill_chunks``, a step's by the traced
runs that CARRIED decode rows over d``decode_steps``), so the fused turn
keeps both halves. The kernels' time is found by their names: the window
layers' call is ``window_attention``, the full layers' ``paged_attention``.

A program without the counters (another model, or a program from before
them) has nothing to read here, and every function says so with None.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from perfbench.lib import peaks, sala_work

# by the reader's word for the kind: the counters' prefix, the kernel's name
KERNELS = {"window": "window_attention", "full": "paged_attention"}
LAYER_TYPES = {"window": "sliding_attention", "full": "full_attention"}


def layers_of(hp: Dict[str, Any], kind: str) -> int:
    return list(hp["layer_types"]).count(LAYER_TYPES[kind])


def kv_bytes_per_token(hp: Dict[str, Any], itemsize: int = 2) -> int:
    """Keys and values of one token in ONE layer, every K/V head."""
    return 2 * hp["num_key_value_heads"] * hp["head_dim"] * itemsize


def pair_flops(hp: Dict[str, Any]) -> float:
    """Operations of one (query, key) pair in ONE layer, every query head:
    the score and the value's share, ``2 x head_dim`` each."""
    return 4.0 * hp["num_attention_heads"] * hp["head_dim"]


def expert_bytes(hp: Dict[str, Any], itemsize: int = 2) -> int:
    """One SwiGLU expert's weights: gate, up and down projections."""
    return 3 * hp["hidden_size"] * hp["moe_intermediate_size"] * itemsize


def layer_dense_bytes(hp: Dict[str, Any], itemsize: int = 2) -> int:
    """What every step reads of a layer whatever the routing: the four
    attention projections (heads of ``head_dim``), the router and the two
    RMSNorm scales."""
    d = hp["hidden_size"]
    q = hp["num_attention_heads"] * hp["head_dim"]
    kv = hp["num_key_value_heads"] * hp["head_dim"]
    return (d * (q + 2 * kv) + q * d + d * hp["num_experts"]
            + 2 * d) * itemsize


def head_bytes(hp: Dict[str, Any], itemsize: int = 2) -> int:
    """The output head and the final norm (the untied input embedding is
    only looked up)."""
    return (hp["hidden_size"] * hp["vocab_size"]
            + hp["hidden_size"]) * itemsize


def model_bytes(hp: Dict[str, Any], itemsize: int = 2) -> int:
    """All weights as held on the device."""
    layer = (layer_dense_bytes(hp, itemsize)
             + hp["num_experts"] * expert_bytes(hp, itemsize))
    embed = hp["hidden_size"] * hp["vocab_size"] * itemsize
    return (hp["num_hidden_layers"] * layer + head_bytes(hp, itemsize)
            + (0 if hp.get("tie_word_embeddings") else embed))


def window_counters(ctx: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """The window's deltas of the program's counters for the two kinds, or
    None where the program reports none."""
    d = ctx["counters"].get("delta", {})
    if "window_attn_step_keys" not in ctx["counters"].get("end", {}):
        return None
    return d


def attention_least_seconds(ctx: Dict[str, Any],
                            kind: str) -> Optional[float]:
    """The least time of the traced window's attention in the layers of
    ``kind`` ('window' | 'full'): its steps' keys and values read once at
    the memory's bandwidth, its chunks' pairs at the peak rate."""
    d = window_counters(ctx)
    if d is None or not ctx.get("trace"):
        return None
    hp, p = ctx["config"], peaks.peak(ctx["device"]["kind"])
    moved = d.get(f"{kind}_attn_step_keys", 0) * kv_bytes_per_token(hp)
    flops = d.get(f"{kind}_attn_chunk_pairs", 0) * pair_flops(hp)
    return (moved / p["hbm_bytes_per_s"]
            * sala_work.traced_share(ctx, "step", d)
            + flops / p["flops_bf16"]
            * sala_work.traced_share(ctx, "chunk", d))


def attention_roofline_percent(ctx: Dict[str, Any],
                               kind: str) -> Optional[float]:
    """``attention_least_seconds`` over the summed device time of the
    kind's kernel."""
    least = attention_least_seconds(ctx, kind)
    if not least:
        return None
    spent = sala_work.kernel_seconds(ctx, KERNELS[kind])
    return 100.0 * least / spent if spent else None


def decode_step_least_seconds(ctx: Dict[str, Any]) -> Optional[float]:
    """The least time of ONE decode step of the window: every layer's dense
    weights and the experts that received a row (the mean a layer-call,
    ``moe_experts_hit`` / ``moe_layer_calls``), the head, and the mean
    step's keys and values (the full layers' at the live contexts, the
    window layers' at ``min(context, window)``: the two ``*_step_keys``
    over d``decode_steps``), each read once at the memory's bandwidth."""
    d = window_counters(ctx)
    if d is None or not d.get("decode_steps") or not d.get(
            "moe_layer_calls"):
        return None
    hp = ctx["config"]
    experts_hit = d["moe_experts_hit"] / d["moe_layer_calls"]
    keys = (d.get("window_attn_step_keys", 0)
            + d.get("full_attn_step_keys", 0)) / d["decode_steps"]
    moved = (hp["num_hidden_layers"] * (
        layer_dense_bytes(hp) + experts_hit * expert_bytes(hp))
        + head_bytes(hp) + keys * kv_bytes_per_token(hp))
    return moved / peaks.peak(ctx["device"]["kind"])["hbm_bytes_per_s"]


def held_share_percent(ctx: Dict[str, Any]) -> Optional[float]:
    """The tokens the window layers' pool held over those it would have held
    of the same sequences with nothing released, both sampled a turn
    (``window_tokens_held`` / ``window_tokens_unreleased``)."""
    d = window_counters(ctx)
    if d is None or not d.get("window_tokens_unreleased"):
        return None
    return (100.0 * d.get("window_tokens_held", 0)
            / d["window_tokens_unreleased"])
