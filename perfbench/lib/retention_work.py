"""The operations and bytes the power-retention mixer of a Brumby-shaped
model REQUIRES, computed from the configuration's own keys
(``configs/<name>.json``: the source's ``config.json`` plus what it assumes)
— the arithmetic the ``kernel.retention_*_roofline``,
``retention.decode_step_roofline`` and ``step.retention_share`` per-layer
metrics rest on, kept with the benchmark.

A K/V head's state is the symmetric half of ``k k^T`` times ``v^T``:
``d (d + 1) / 2`` rows (8256 for ``d`` = 128) of ``d`` float32 values, and a
normaliser of as many values beside it. The arithmetic counts THOSE rows,
whatever a kernel pads them to (the program keeps 8320: the share it reads
is then the lower, as it should be).

  step   a live row's state and normaliser, every K/V head of a layer, read
         once and written once
  chunk  a token's key updates its K/V head's state and each query head
         reads it: ``2 rows d`` operations each, ``(G + H)`` times a token
         and layer; inside a block of ``BLOCK`` tokens the causal half of
         the scores and of their product with the values, ``2 d BLOCK`` a
         query head and token; and the slot's state read and written once a
         layer-call
  decode step  the weights that are multiplied (every layer's projections,
         gate and feed-forward, the output head; the embedding table is
         only looked up) and the live rows' states, read (the states also
         written) once

The counts come from the program's counters (``scheduler_stats()``:
``retention_step_rows`` = live rows x layers, ``retention_chunk_calls`` =
layer-calls, ``retention_chunk_tokens`` = their real tokens), summed over
the window and brought to the traced part of it as ``sala_work.traced_share``
brings the two SALA mixers' (a chunk's work by the chunk program's traced
runs over d``prefill_chunks``, a step's by the traced runs that CARRIED
decode rows over d``decode_steps``), so a turn that is fused one day keeps
both halves.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from perfbench.lib import peaks, sala_work

BLOCK = 128  # tokens of one block of the chunked scan (ops/power_retention)
STEP, CHUNK = "power_retention_step", "power_retention_chunk"
KERNELS = (STEP, CHUNK)


def half_rows(hp: Dict[str, Any]) -> int:
    """Rows of one K/V head's state: the pairs ``a <= b`` of a head."""
    d = hp["head_dim"]
    return d * (d + 1) // 2


def state_bytes(hp: Dict[str, Any]) -> int:
    """One row's state and normaliser of ONE layer, float32."""
    return (hp["num_key_value_heads"] * half_rows(hp)
            * (hp["head_dim"] + 1) * 4)


def chunk_flops_per_token(hp: Dict[str, Any]) -> float:
    """Operations of ONE layer for one real token of a chunk."""
    d, heads = hp["head_dim"], hp["num_attention_heads"]
    across = (hp["num_key_value_heads"] + heads) * 2 * half_rows(hp) * d
    return across + heads * 2 * d * BLOCK


def weight_bytes(ctx: Dict[str, Any]) -> float:
    """The multiplied parameters a program run reads once, in the type the
    configuration serves them in."""
    hp, sizes = ctx["config"], ctx["sizes"]
    itemsize = 4 if hp.get("program", {}).get("param_dtype") == "float32" \
        else 2
    gates = (sizes["num_layers"] * sizes["embed_dim"]
             * hp["num_key_value_heads"])
    return itemsize * (peaks.matmul_params(sizes) + gates)


def window_counters(ctx: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """The window's deltas of the program's counters for the mixer, or None
    where the program reports none (another model, or a program from before
    them)."""
    d = ctx["counters"].get("delta", {})
    if not d.get("retention_step_rows") and not d.get(
            "retention_chunk_calls"):
        return None
    return d


def step_least_seconds(ctx: Dict[str, Any]) -> Optional[float]:
    """The traced window's live states read and written once."""
    d = window_counters(ctx)
    if d is None:
        return None
    rows = d.get("retention_step_rows", 0) * sala_work.traced_share(
        ctx, "step", d)
    return (rows * 2 * state_bytes(ctx["config"])
            / peaks.peak(ctx["device"]["kind"])["hbm_bytes_per_s"])


def chunk_least_seconds(ctx: Dict[str, Any]) -> Optional[float]:
    """The traced window's chunks: their real tokens' operations at the
    peak rate, and a slot's state read and written once a layer-call."""
    d = window_counters(ctx)
    if d is None:
        return None
    hp, p = ctx["config"], peaks.peak(ctx["device"]["kind"])
    share = sala_work.traced_share(ctx, "chunk", d)
    return share * (
        d.get("retention_chunk_tokens", 0) * chunk_flops_per_token(hp)
        / p["flops_bf16"]
        + d.get("retention_chunk_calls", 0) * 2 * state_bytes(hp)
        / p["hbm_bytes_per_s"])


def decode_step_least_seconds(ctx: Dict[str, Any]) -> Optional[float]:
    """The least time of ONE decode step of the window: the weights and the
    mean step's live states, at the memory's bandwidth."""
    d = window_counters(ctx)
    if d is None or not d.get("decode_steps"):
        return None
    states = (d.get("retention_step_rows", 0) / d["decode_steps"]
              * 2 * state_bytes(ctx["config"]))
    return ((weight_bytes(ctx) + states)
            / peaks.peak(ctx["device"]["kind"])["hbm_bytes_per_s"])
