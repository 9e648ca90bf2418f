"""The bytes an expert (MoE) model's decode step REQUIRES, computed from the
configuration's own keys (``configs/<name>.json``, the source's
``config.json``) — the arithmetic the ``moe.*`` per-layer metrics rest on,
kept with the benchmark.

A decode step over a few dozen rows is bound by the memory's bandwidth: the
least it can take is the time to read once every weight the step touches
and the keys and values of the live contexts. Of a layer's experts only
those that received a row have to be read, which is what the program's
counters say (``moe_experts_hit`` / ``moe_layer_calls``).
"""

from __future__ import annotations

from typing import Any, Dict

from perfbench.lib import peaks


def expert_bytes(hp: Dict[str, Any], itemsize: int = 2) -> int:
    """One SwiGLU expert's weights: gate, up and down projections."""
    return 3 * hp["hidden_size"] * hp["intermediate_size"] * itemsize


def layer_dense_bytes(hp: Dict[str, Any], itemsize: int = 2) -> int:
    """What every step reads of a layer whatever the routing: the four
    attention projections, the router, the two RMSNorm scales and the
    q/k norm scales."""
    d = hp["hidden_size"]
    head = d // hp["num_attention_heads"]
    q, kv = hp["num_attention_heads"] * head, hp["num_key_value_heads"] * head
    attn = d * (q + 2 * kv) + q * d
    norms = 2 * d + q + kv
    return (attn + d * hp["num_experts"] + norms) * itemsize


def head_bytes(hp: Dict[str, Any], itemsize: int = 2) -> int:
    """The output head and the final norm (an untied input embedding is
    only looked up: a few rows, left out)."""
    return (hp["hidden_size"] * hp["vocab_size"] + hp["hidden_size"]) * itemsize


def model_bytes(hp: Dict[str, Any], itemsize: int = 2) -> int:
    """All weights as held on the device."""
    layer = layer_dense_bytes(hp, itemsize) + hp["num_experts"] * expert_bytes(
        hp, itemsize)
    embed = hp["hidden_size"] * hp["vocab_size"] * itemsize
    tied = bool(hp.get("tie_word_embeddings"))
    return (hp["num_hidden_layers"] * layer + head_bytes(hp, itemsize)
            + (0 if tied else embed))


def decode_step_least_seconds(hp: Dict[str, Any], experts_hit: float,
                              kv_bytes: float, device_kind: str) -> float:
    """The least time of one decode step: every layer's dense weights and
    ``experts_hit`` experts (the mean a layer), the head, and ``kv_bytes``
    of cached keys and values (the step's live contexts, all layers), each
    read once at the memory's bandwidth."""
    moved = (hp["num_hidden_layers"] * (
        layer_dense_bytes(hp) + experts_hit * expert_bytes(hp))
        + head_bytes(hp) + kv_bytes)
    return moved / peaks.peak(device_kind)["hbm_bytes_per_s"]


def counters(ctx: Dict[str, Any]):
    """The window's deltas of the program's expert counters, or None where
    the program reports none (a dense model, or a program from before them)."""
    d = ctx["counters"].get("delta", {})
    if not d.get("moe_layer_calls"):
        return None
    return d
