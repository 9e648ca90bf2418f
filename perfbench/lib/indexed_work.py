"""The operations and bytes the token-selected attention of a Keye-VL-2.0-
shaped model REQUIRES (``indexed_attention`` layers: a learned indexer scores
every token of a query's context from one cached index key a token, and the
query attends the ``topk`` best), computed from the configuration's own keys
(``configs/<name>.json``, the source's ``config.json`` with its ``sa_config``)
— the arithmetic the ``kernel.index_score_roofline``,
``kernel.indexed_attn_roofline`` and ``indexed.turn_roofline`` per-layer
metrics rest on, kept with the benchmark.

Index scores: a (query, token) pair is ``Hi`` dot products of ``Di`` values,
``2 Hi Di`` operations. A step's row reads its context's index keys (``Di``
values of 2 bytes a token) and writes a float32 score a token, at the
memory's bandwidth; a chunk's 512 queries share the keys they read, and
their pairs go at the peak rate.

Attention over the selection: a step reads the keys and values of the tokens
its rows attend (once: every K/V head shares the choice) at the memory's
bandwidth; a chunk's (query, attended token) pairs cost ``4 H D`` operations
(scores and values) at the peak rate. The chunk's kernel runs over the
slot's whole context with the choice as a mask, so its share of THIS least
time is low by construction (PERF.md 7).

The counts come from the program's counters (``scheduler_stats()``:
``indexed_tokens_scored``, ``indexed_tokens_attended``,
``indexed_tokens_context`` and a step's share of them,
``indexed_step_tokens_*``), summed over the window and brought to the traced
part of it as ``sala_work.traced_share`` brings them. A program without the
counters reads nothing.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from perfbench.lib import peaks, sala_work, turn_work, window_work

SCORE, SELECT = "index_score", "indexed_select"
CHUNK, STEP = "indexed_chunk_attention", "indexed_step_attention"
KERNELS = (SCORE, SELECT, CHUNK, STEP)


def window_counters(ctx: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """The window's deltas of the program's counters for the kind, or None
    where the program reports none (another model, or a program from before
    the kind)."""
    d = ctx.get("counters", {}).get("delta", {})
    if not d.get("indexed_tokens_context"):
        return None
    return d


def score_pair_flops(hp: Dict[str, Any]) -> float:
    """Operations of one (query, token) pair's index score, ONE layer."""
    sa = hp["sa_config"]
    return 2.0 * sa["indexer_num_heads"] * sa["indexer_head_dim"]


def index_key_bytes(hp: Dict[str, Any], itemsize: int = 2) -> int:
    """One token's index key in ONE layer."""
    return hp["sa_config"]["indexer_head_dim"] * itemsize


def attended_pair_flops(hp: Dict[str, Any]) -> float:
    """Operations of one (query, attended token) pair, ONE layer: every
    head's score and its product with the value."""
    return 4.0 * hp["num_attention_heads"] * hp["head_dim"]


def split(d: Dict[str, float], what: str, total: str = ""):
    """(a step's, a chunk's) share of the window's ``indexed_tokens_<what>``
    ('context' or 'attended'; ``total``: the counter of both where it goes
    by another name — a query scores its whole context, so
    ``indexed_tokens_scored`` splits by the step's ``_context``)."""
    step = d.get("indexed_step_tokens_" + what, 0)
    return step, d.get("indexed_tokens_" + (total or what), 0) - step


def score_least_seconds(ctx: Dict[str, Any]) -> Optional[float]:
    d = window_counters(ctx)
    if d is None or not ctx.get("trace"):
        return None
    hp, p = ctx["config"], peaks.peak(ctx["device"]["kind"])
    step, chunk = split(d, "context", "scored")
    return (step * (index_key_bytes(hp) + 4) / p["hbm_bytes_per_s"]
            * sala_work.traced_share(ctx, "step", d)
            + chunk * score_pair_flops(hp) / p["flops_bf16"]
            * sala_work.traced_share(ctx, "chunk", d))


def attention_least_seconds(ctx: Dict[str, Any]) -> Optional[float]:
    d = window_counters(ctx)
    if d is None or not ctx.get("trace"):
        return None
    hp, p = ctx["config"], peaks.peak(ctx["device"]["kind"])
    step, chunk = split(d, "attended")
    return (step * window_work.kv_bytes_per_token(hp) / p["hbm_bytes_per_s"]
            * sala_work.traced_share(ctx, "step", d)
            + chunk * attended_pair_flops(hp) / p["flops_bf16"]
            * sala_work.traced_share(ctx, "chunk", d))


def roofline_percent(ctx: Dict[str, Any], least: Optional[float],
                     *kernels: str) -> Optional[float]:
    if not least:
        return None
    spent = sala_work.kernel_seconds(ctx, *kernels)
    return 100.0 * least / spent if spent else None


def indexer_bytes(hp: Dict[str, Any], itemsize: int = 2) -> int:
    """The indexer's weights of ONE layer: its queries', key's and heads'
    weights' projections and the key's LayerNorm."""
    sa, d = hp["sa_config"], hp["hidden_size"]
    hi, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return (d * (hi * di + di + hi) + 2 * di) * itemsize


def turn_least_seconds(ctx: Dict[str, Any]) -> Optional[float]:
    """The least time of ONE run of the program the window's turns ran, a
    prefill chunk with the live decode rows along: the larger of its bytes
    at the memory's bandwidth — every layer's dense weights, indexer and all
    experts (a chunk's 4096 (row, expert) pairs leave none of 128 out), the
    head, the index keys its rows score and the keys and values its STEP
    rows attend — and its operations at the peak rate — its rows through
    the dense weights and their top-k experts, its chunk's index scores and
    attended pairs. By window: the mean such run (the window's totals over
    d``prefill_chunks``; of a step's work the fused turns' share)."""
    d = window_counters(ctx)
    if d is None or not d.get("prefill_chunks"):
        return None
    hp, p = ctx["config"], peaks.peak(ctx["device"]["kind"])
    runs, layers = d["prefill_chunks"], hp["num_hidden_layers"]
    fused = (d.get("fused_turns", 0) / d["decode_steps"]
             if d.get("decode_steps") else 0.0)
    step_scored, chunk_scored = split(d, "context", "scored")
    step_att, chunk_att = split(d, "attended")
    rows = (d.get("prefill_tokens", 0) + d.get("fused_step_rows", 0)) / runs
    dense = window_work.layer_dense_bytes(hp) + indexer_bytes(hp)
    expert = window_work.expert_bytes(hp)
    moved = (layers * (dense + hp["num_experts"] * expert)
             + window_work.head_bytes(hp)
             + (step_scored * fused * index_key_bytes(hp)
                + step_att * fused * window_work.kv_bytes_per_token(hp))
             / runs)
    flops = (rows * layers * (dense + hp["num_experts_per_tok"] * expert)
             + (chunk_scored * score_pair_flops(hp)
                + chunk_att * attended_pair_flops(hp)) / runs)
    # a weight of 2 bytes does 2 operations a row: bytes ARE the operations
    return max(moved / p["hbm_bytes_per_s"], flops / p["flops_bf16"])


def turn_roofline_percent(ctx: Dict[str, Any]) -> Optional[float]:
    if not ctx.get("trace"):
        return None
    least = turn_least_seconds(ctx)
    ran = turn_work.runs(ctx)["chunk"]
    if not least or not ran or not ran.get("median_s"):
        return None
    return 100.0 * least / ran["median_s"]
