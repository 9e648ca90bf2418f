"""The operations and bytes the latent attention of a GLM-4.7-Flash-shaped
model REQUIRES (``latent_attention`` layers: multi-head latent attention
whose cache is ONE latent of ``kv_lora_rank`` values and ONE rotated key of
``qk_rope_head_dim`` values a token, attended with the keys' and values'
up-projections absorbed), computed from the configuration's own keys
(``configs/<name>.json``, the source's ``config.json``) — the arithmetic the
``kernel.latent_attn_roofline``, ``latent.turn_roofline`` and
``step.latent_share`` per-layer metrics rest on, kept with the benchmark.

Absorbed, a (query, key) pair costs every head one score over ``rank + rope``
values and one product with the ``rank`` values of the latent: ``H x (2 (rank
+ rope) + 2 rank)`` operations (43,520 at 20 heads of 512 + 64); a cached
token is ``(rank + rope) x 2`` bytes a layer (1,152 B; the pool holds it in a
row padded to whole lane tiles, which is the kernel's cost and not the
work's). A decode row at context N reads N tokens and does N pairs: 37.8
operations a byte against the v5e's 240, so a STEP's least time is its bytes
at the memory's bandwidth; a CHUNK's 512 queries share the tokens they read,
and its pairs go at the peak rate. (Expanded — keys and values rebuilt a
token — a pair costs ``2 (nope + rope) + 2 v`` = 1,024 a head and a context
token ``2 rank H (nope + v)`` = 9.2M more a chunk: 19.7M a context token a
chunk against the absorbed 22.3M. The program runs absorbed, and this file
counts what it runs: PERF.md 6, PR 55.)

The counts come from the program's counters (``scheduler_stats()``:
``latent_tokens_context``, ``latent_step_tokens_context``,
``latent_chunk_pairs``, already summed over the kind's layers), over the
window and brought to the traced part of it as ``sala_work.traced_share``
brings them. A program without the counters reads nothing.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from perfbench.lib import peaks, sala_work, turn_work

STEP, CHUNK = "latent_step_attention", "latent_chunk_attention"
KERNELS = (STEP, CHUNK)


def window_counters(ctx: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """The window's deltas of the program's counters for the kind, or None
    where the program reports none (another model, or a program from before
    the kind)."""
    d = ctx.get("counters", {}).get("delta", {})
    if not d.get("latent_tokens_context"):
        return None
    return d


def pair_flops(hp: Dict[str, Any]) -> float:
    """Operations of one (query, key) pair in ONE layer, absorbed: every
    head's score over the latent and the rotated key, and its product with
    the latent."""
    rank, rope = hp["kv_lora_rank"], hp["qk_rope_head_dim"]
    return hp["num_attention_heads"] * (2.0 * (rank + rope) + 2.0 * rank)


def token_bytes(hp: Dict[str, Any], itemsize: int = 2) -> int:
    """What one token leaves in the cache of ONE layer: its latent and the
    one rotated key."""
    return (hp["kv_lora_rank"] + hp["qk_rope_head_dim"]) * itemsize


def attention_params(hp: Dict[str, Any]) -> int:
    """A layer's attention: the query's bottleneck and up-projection, the
    projection to latent and shared key, the keys' and values'
    up-projection, the output projection, the two norms."""
    d, h = hp["hidden_size"], hp["num_attention_heads"]
    rq, r = hp["q_lora_rank"], hp["kv_lora_rank"]
    n, e, v = (hp["qk_nope_head_dim"], hp["qk_rope_head_dim"],
               hp["v_head_dim"])
    return (d * rq + rq + rq * h * (n + e) + d * (r + e) + r
            + r * h * (n + v) + h * v * d)


def expert_params(hp: Dict[str, Any]) -> int:
    return 3 * hp["hidden_size"] * hp["moe_intermediate_size"]


def dense_mlp_params(hp: Dict[str, Any]) -> int:
    return 3 * hp["hidden_size"] * hp["intermediate_size"]


def layer_params(hp: Dict[str, Any], i: int, experts: Optional[int] = None):
    """Layer ``i``'s parameters with ``experts`` of its routed experts
    counted (None: all): attention, the block's two norms, and a dense
    SwiGLU (a leading layer) or router, bias, routed and shared experts."""
    d = hp["hidden_size"]
    own = attention_params(hp) + 2 * d
    if i < hp["first_k_dense_replace"]:
        return own + dense_mlp_params(hp)
    n = hp["n_routed_experts"]
    return (own + d * n + n + expert_params(hp) * (
        (n if experts is None else experts) + hp["n_shared_experts"]))


def model_params(hp: Dict[str, Any]) -> int:
    """All weights as held on the device: the layers, the untied embedding
    and head, the final norm."""
    d = hp["hidden_size"]
    return (sum(layer_params(hp, i) for i in range(hp["num_hidden_layers"]))
            + 2 * d * hp["vocab_size"] + d)


def attention_least_seconds(ctx: Dict[str, Any]) -> Optional[float]:
    """The least time of the traced window's latent attention: a step's
    rows read the latents and keys of their contexts once at the memory's
    bandwidth (or do their pairs at the peak rate, whichever is longer: the
    bytes, at 37.8 operations a byte), a chunk's pairs go at the peak rate
    (or its context's bytes once a query, never the longer)."""
    d = window_counters(ctx)
    if d is None or not ctx.get("trace"):
        return None
    hp, p = ctx["config"], peaks.peak(ctx["device"]["kind"])
    step = d.get("latent_step_tokens_context", 0)
    pairs = d.get("latent_chunk_pairs", 0)
    return (max(step * token_bytes(hp) / p["hbm_bytes_per_s"],
                step * pair_flops(hp) / p["flops_bf16"])
            * sala_work.traced_share(ctx, "step", d)
            + pairs * pair_flops(hp) / p["flops_bf16"]
            * sala_work.traced_share(ctx, "chunk", d))


def attention_roofline_percent(ctx: Dict[str, Any]) -> Optional[float]:
    least = attention_least_seconds(ctx)
    if not least:
        return None
    spent = sala_work.kernel_seconds(ctx, *KERNELS)
    return 100.0 * least / spent if spent else None


def latent_share_percent(ctx: Dict[str, Any]) -> Optional[float]:
    t = ctx.get("trace")
    if not t or not t.get("busy_s"):
        return None
    spent = sala_work.kernel_seconds(ctx, *KERNELS)
    return 100.0 * spent / t["busy_s"] if spent else None


def turn_least_seconds(ctx: Dict[str, Any]) -> Optional[float]:
    """The least time of ONE run of the program the window's turns ran, a
    prefill chunk with the live decode rows along: the larger of its bytes
    at the memory's bandwidth — every layer's weights (a chunk's 2048 (row,
    expert) pairs leave none of 64 experts out), the head, and the latents
    and keys its STEP rows attend — and its operations at the peak rate —
    its rows through each layer's attention, dense SwiGLU or top-k and
    shared experts, and its chunk's (query, key) pairs. By window: the mean
    such run (the window's totals over d``prefill_chunks``; of a step's work
    the fused turns' share)."""
    d = window_counters(ctx)
    if d is None or not d.get("prefill_chunks"):
        return None
    hp, p = ctx["config"], peaks.peak(ctx["device"]["kind"])
    runs, layers = d["prefill_chunks"], range(hp["num_hidden_layers"])
    fused = (d.get("fused_turns", 0) / d["decode_steps"]
             if d.get("decode_steps") else 0.0)
    rows = (d.get("prefill_tokens", 0) + d.get("fused_step_rows", 0)) / runs
    head = hp["hidden_size"] * hp["vocab_size"] + hp["hidden_size"]
    moved = (2 * (sum(layer_params(hp, i) for i in layers) + head)
             + d.get("latent_step_tokens_context", 0) * fused
             * token_bytes(hp) / runs)
    # a weight does 2 operations a row; the head sees the sampled rows
    flops = (2 * rows * sum(layer_params(hp, i, hp["num_experts_per_tok"])
                            for i in layers)
             + 2 * (1 + d.get("fused_step_rows", 0) / runs) * head
             + (d.get("latent_chunk_pairs", 0)
                + d.get("latent_step_tokens_context", 0) * fused)
             * pair_flops(hp) / runs)
    return max(moved / p["hbm_bytes_per_s"], flops / p["flops_bf16"])


def turn_roofline_percent(ctx: Dict[str, Any]) -> Optional[float]:
    if not ctx.get("trace"):
        return None
    least = turn_least_seconds(ctx)
    ran = turn_work.runs(ctx)["chunk"]
    if not least or not ran or not ran.get("median_s"):
        return None
    return 100.0 * least / ran["median_s"]
