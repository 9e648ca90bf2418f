"""The operations and bytes the picked latent attention of a DeepSeek-V3.2-
shaped model REQUIRES (``indexed_latent_attention`` layers: latent attention
whose queries attend the ``index_topk`` latents a learned indexer picks out
of one cached index key a token), computed from the configuration's own keys
(``configs/<name>.json``, the source's ``config.json``) — the arithmetic the
``kernel.picked_latent_attn_roofline``, ``kernel.index_score_roofline.latent``,
``step.picked_share``, ``attn.picked_share`` and ``picked.turn_roofline``
per-layer metrics rest on, kept with the benchmark. It reads the same work
whatever implements it: counters by position, kernels by name.

Index scores: a (query, token) pair is ``Hi`` dot products of ``Di`` values,
``2 Hi Di`` operations (16,384 at 64 heads of 128). A step's row reads its
context's index keys (``Di`` values of 2 bytes a token) and writes a float32
score a token, at the memory's bandwidth; a chunk's queries share the keys
they read, and their pairs go at the peak rate.

Attention over the choice, absorbed: a chosen (query, key) pair costs every
head one score over ``rank + rope`` values and one product with the ``rank``
values of the latent, ``H (2 (rank + rope) + 2 rank)`` operations (278,528
at 128 heads of 512 + 64), and its row ``(rank + rope) x 2`` bytes (1,152)
ONCE a query, whatever the heads: 242 operations a byte, the v5e's knee, so
the least time is the larger of the two, a step's row and a chunk's alike.
The rows' being brought together (a gather, copies) is the implementation's
cost, not the work's: the kernel's time is its own events', and the gather's
op is read into ``step.picked_share`` by its name.

The counts come from the program's counters (``scheduler_stats()``:
``picked_index_pairs``, ``picked_chosen_pairs``, a step's share of both,
``picked_latent_bytes``, ``picked_index_key_bytes``, summed over the kind's
layers), over the window and brought to the traced part of it as
``sala_work.traced_share`` brings them. A program without the counters reads
nothing.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from perfbench.lib import latent_work, peaks, sala_work, ssm_work, turn_work

SCORE, SELECT = "index_score", "indexed_select"
STEP, CHUNK = "picked_latent_step_attention", "picked_latent_chunk_attention"
# what XLA names the op that brings a query block's chosen rows together
GATHER = "gather"
KERNELS = (SCORE, SELECT, STEP, CHUNK)


def window_counters(ctx: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """The window's deltas of the program's counters for the kind, or None
    where the program reports none (another model, or a program from before
    the kind)."""
    d = ctx.get("counters", {}).get("delta", {})
    if not d.get("picked_index_pairs"):
        return None
    return d


def score_pair_flops(hp: Dict[str, Any]) -> float:
    """Operations of one (query, token) pair's index score, ONE layer."""
    return 2.0 * hp["index_n_heads"] * hp["index_head_dim"]


def index_key_bytes(hp: Dict[str, Any], itemsize: int = 2) -> int:
    """One token's index key in ONE layer."""
    return hp["index_head_dim"] * itemsize


def split(d: Dict[str, float], what: str):
    """(a step's, a chunk's) share of the window's ``picked_<what>_pairs``
    ('index' or 'chosen')."""
    step = d.get(f"picked_step_{what}_pairs", 0)
    return step, d.get(f"picked_{what}_pairs", 0) - step


def _traced(ctx, d, step: float, chunk: float) -> float:
    return (step * sala_work.traced_share(ctx, "step", d)
            + chunk * sala_work.traced_share(ctx, "chunk", d))


def score_least_seconds(ctx: Dict[str, Any]) -> Optional[float]:
    d = window_counters(ctx)
    if d is None or not ctx.get("trace"):
        return None
    hp, p = ctx["config"], peaks.peak(ctx["device"]["kind"])
    step, chunk = split(d, "index")
    return _traced(ctx, d,
                   step * (index_key_bytes(hp) + 4) / p["hbm_bytes_per_s"],
                   chunk * score_pair_flops(hp) / p["flops_bf16"])


def attention_least_seconds(ctx: Dict[str, Any]) -> Optional[float]:
    """The least time of the traced window's attention over the chosen
    latents: a chosen pair's row once a query at the memory's bandwidth or
    its heads' operations at the peak rate, whichever is longer."""
    d = window_counters(ctx)
    if d is None or not ctx.get("trace"):
        return None
    hp, p = ctx["config"], peaks.peak(ctx["device"]["kind"])
    pair = max(latent_work.token_bytes(hp) / p["hbm_bytes_per_s"],
               latent_work.pair_flops(hp) / p["flops_bf16"])
    step, chunk = split(d, "chosen")
    return _traced(ctx, d, step * pair, chunk * pair)


def roofline_percent(ctx: Dict[str, Any], least: Optional[float],
                     *kernels: str) -> Optional[float]:
    """``least`` seconds over the MOST the named kernels' events can have
    taken (``ssm_work.kernel_seconds_at_most``): at least this share."""
    if not least or not ctx.get("trace"):
        return None
    spent = sum(ssm_work.kernel_seconds_at_most(ctx, k) for k in kernels)
    return 100.0 * least / spent if spent else None


def picked_share_percent(ctx: Dict[str, Any]) -> Optional[float]:
    """The indexer's two kernels, the picked attention's two and the gathers
    between them, of the device's busy time."""
    t = ctx.get("trace")
    if not t or not t.get("busy_s"):
        return None
    if not sala_work.kernel_seconds(ctx, STEP, CHUNK):
        return None
    spent = sala_work.kernel_seconds(ctx, *KERNELS, GATHER)
    return 100.0 * spent / t["busy_s"]


def chosen_share_percent(ctx: Dict[str, Any]) -> Optional[float]:
    d = window_counters(ctx)
    if d is None:
        return None
    return 100.0 * d.get("picked_chosen_pairs", 0) / d["picked_index_pairs"]


def indexer_params(hp: Dict[str, Any]) -> int:
    """The indexer's weights of ONE layer: its queries' projection out of
    the query's bottleneck, its key's and heads' weights' out of the hidden
    state, the key's LayerNorm."""
    hi, di = hp["index_n_heads"], hp["index_head_dim"]
    return (hp["q_lora_rank"] * hi * di + hp["hidden_size"] * (di + hi)
            + 2 * di)


def layer_params(hp: Dict[str, Any], i: int, experts: Optional[int] = None):
    """Layer ``i``'s parameters AS HELD (``n_routed_experts`` the experts
    held, ``router_experts`` the router's width) with ``experts`` of the
    held routed experts counted (None: all)."""
    d = hp["hidden_size"]
    own = latent_work.attention_params(hp) + indexer_params(hp) + 2 * d
    if i < hp["first_k_dense_replace"]:
        return own + latent_work.dense_mlp_params(hp)
    held = hp["n_routed_experts"]
    width = hp.get("router_experts", held)
    return (own + d * width + width + latent_work.expert_params(hp) * (
        (held if experts is None else experts) + hp["n_shared_experts"]))


def model_params(hp: Dict[str, Any]) -> int:
    """All weights as held on the device."""
    d = hp["hidden_size"]
    return (sum(layer_params(hp, i) for i in range(hp["num_hidden_layers"]))
            + 2 * d * hp["vocab_size"] + d)


def turn_least_seconds(ctx: Dict[str, Any]) -> Optional[float]:
    """The least time of ONE run of the program the window's turns ran, a
    prefill chunk with the live decode rows along: the larger of its bytes
    at the memory's bandwidth — every layer's weights as held once, the
    head, the index keys its rows score once, the chosen rows once a query —
    and its operations at the peak rate — its rows through each layer's
    weights (of the held experts the share of top-k that lands here), its
    index pairs and its chosen pairs. By window: the mean such run (the
    window's totals over d``prefill_chunks``; of a step's work the fused
    turns' share)."""
    d = window_counters(ctx)
    if d is None or not d.get("prefill_chunks"):
        return None
    hp, p = ctx["config"], peaks.peak(ctx["device"]["kind"])
    runs, layers = d["prefill_chunks"], range(hp["num_hidden_layers"])
    fused = (d.get("fused_turns", 0) / d["decode_steps"]
             if d.get("decode_steps") else 0.0)
    rows = (d.get("prefill_tokens", 0) + d.get("fused_step_rows", 0)) / runs
    head = hp["hidden_size"] * hp["vocab_size"] + hp["hidden_size"]
    of_run = lambda what: sum(
        part * share for part, share in zip(split(d, what), (fused, 1.0))
    ) / runs
    # a step's rows read their own contexts' keys, a chunk its context once
    step_keys = split(d, "index")[0] * index_key_bytes(hp)
    keys = (d.get("picked_index_key_bytes", 0) - step_keys
            + step_keys * fused) / runs
    moved = (2 * (sum(layer_params(hp, i) for i in layers) + head) + keys
             + of_run("chosen") * latent_work.token_bytes(hp))
    taken = (hp["num_experts_per_tok"] * hp["n_routed_experts"]
             / hp.get("router_experts", hp["n_routed_experts"]))
    flops = (2 * rows * sum(layer_params(hp, i, taken) for i in layers)
             + 2 * (1 + d.get("fused_step_rows", 0) / runs) * head
             + of_run("index") * score_pair_flops(hp)
             + of_run("chosen") * latent_work.pair_flops(hp))
    return max(moved / p["hbm_bytes_per_s"], flops / p["flops_bf16"])


def turn_roofline_percent(ctx: Dict[str, Any]) -> Optional[float]:
    if not ctx.get("trace"):
        return None
    least = turn_least_seconds(ctx)
    ran = turn_work.runs(ctx)["chunk"]
    if not least or not ran or not ran.get("median_s"):
        return None
    return 100.0 * least / ran["median_s"]
