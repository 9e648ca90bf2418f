"""Published peaks of the chips, and the operations and bytes an algorithm
needs — the arithmetic every utilization and roofline share rests on.

Copied from ``bench.py`` (``PEAK_FLOPS``, ``_mfu_record``) so that a PR
which claims a gain cannot change it; the originals are listed in PERF.md
for a later PR to delete. A device kind that is not in the table is an
error, never a default.
"""

from __future__ import annotations

from typing import Any, Dict

# One chip. Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 16 GB HBM2e at 819 GB/s). Keyed by jax's ``device_kind``.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
    "TPU v5e": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> Dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}: add it to "
            "perfbench/lib/peaks.py with its source") from None


def matmul_params(hp: Dict[str, Any]) -> int:
    """Parameters that take part in a matrix multiplication for every token
    (``hp``: the program-side sizes, see ``configs.program_sizes``): the
    blocks' projections and the output head. An embedding table that is only
    looked up (untied input embeddings, learned positions) does no
    multiplication and is left out; a tied table counts once, as the head."""
    d, f = hp["embed_dim"], hp["mlp_dim"]
    h, kv, hd = hp["num_heads"], hp["num_kv_heads"], hp["head_dim"]
    attn = d * (h + 2 * kv) * hd + h * hd * d
    mlp = (3 if hp["mlp"] == "swiglu" else 2) * d * f
    return hp["num_layers"] * (attn + mlp) + d * hp["vocab_size"]


def train_flops_per_token(hp: Dict[str, Any], seq_len: int) -> float:
    """Operations the forward and backward passes REQUIRE per token:
    6 per multiplied parameter plus attention's 12 * L * S * E (scores and
    values, forward and backward, no causal discount — bench.py's and
    PaLM's convention). Recomputation (remat) is not counted."""
    e = hp["num_heads"] * hp["head_dim"]
    return 6.0 * matmul_params(hp) + 12.0 * hp["num_layers"] * seq_len * e


def mfu_percent(tokens_per_s: float, hp: Dict[str, Any], seq_len: int,
                chips: int, device_kind: str) -> float:
    return (100.0 * tokens_per_s * train_flops_per_token(hp, seq_len)
            / (chips * peak(device_kind)["flops_bf16"]))


def kv_bytes_per_token(hp: Dict[str, Any], itemsize: int = 2) -> int:
    """Bytes of keys and values one cached token holds over all layers."""
    return (2 * hp["num_layers"] * hp["num_kv_heads"] * hp["head_dim"]
            * itemsize)


def attention_least_seconds(hp: Dict[str, Any], query_tokens: int,
                            context_tokens: int, device_kind: str,
                            itemsize: int = 2) -> float:
    """The least time the chip could take for one sequence's attention over
    all layers: ``query_tokens`` queries over ``context_tokens`` cached
    tokens. The larger of the operations (scores and values: 4 per query,
    key, head and head dimension) over the peak rate and the keys and values
    read once over the memory bandwidth — the roofline."""
    p = peak(device_kind)
    ops = (4.0 * query_tokens * context_tokens * hp["num_heads"]
           * hp["head_dim"] * hp["num_layers"])
    moved = context_tokens * kv_bytes_per_token(hp, itemsize)
    return max(ops / p["flops_bf16"], moved / p["hbm_bytes_per_s"])
