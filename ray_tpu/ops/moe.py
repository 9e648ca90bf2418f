"""Mixture-of-Experts layer: dropless top-k routing over grouped matmuls.

One implementation for training and serving (OLMoE / Mixtral style, the
reference has no MoE at all — SURVEY §5): the router scores every row in
float32, each row takes its top-k experts, the (row, expert) pairs are
sorted by expert and the three SwiGLU matmuls run as GROUPED matmuls over
the ragged per-expert groups (``jax.lax.ragged_dot``), then the pairs are
un-sorted and summed under the router's weights. No capacity, so no row is
ever dropped, and nothing of size [rows, experts, capacity] exists.

Rows that are not live (a slot without a sequence, a chunk's padding) are
marked by ``valid``: they sort behind every group, cost no expert a row,
are not counted and come out as zeros.

Expert parallelism: the expert weights carry the logical axis ``expert``
(``ep`` on a mesh); under plain jit GSPMD partitions or gathers as it sees
fit. How experts exchange rows across chips is not decided here.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp


def init_moe_params(key, embed_dim: int, hidden_dim: int, num_experts: int,
                    param_dtype=jnp.float32) -> Dict[str, Any]:
    """SwiGLU experts: router [d,E] + per-expert gate/up [E,d,f], down [E,f,d]."""
    ks = jax.random.split(key, 4)
    init = jax.nn.initializers.normal(0.02, param_dtype)
    return {
        "w_router": init(ks[0], (embed_dim, num_experts)),
        "w_gate": init(ks[1], (num_experts, embed_dim, hidden_dim)),
        "w_up": init(ks[2], (num_experts, embed_dim, hidden_dim)),
        "w_down": init(ks[3], (num_experts, hidden_dim, embed_dim)),
    }


def moe_logical_axes() -> Dict[str, Tuple[Optional[str], ...]]:
    return {
        "w_router": ("embed", None),
        "w_gate": ("expert", "embed", "mlp"),
        "w_up": ("expert", "embed", "mlp"),
        "w_down": ("expert", "mlp", "embed"),
    }


def moe_layer(p: Dict[str, Any], x, *, num_experts: int, top_k: int = 2,
              renormalize: bool = True, dtype=jnp.bfloat16, valid=None,
              layer: Optional[int] = None):
    """x: [B, S, d] -> (y [B, S, d], aux_loss, counts [E], routes [B, S, k]).

    ``renormalize``: divide the k router probabilities by their sum
    (Mixtral, GShard); OLMoE weights by the raw probabilities.
    ``valid``: optional bool [B, S]; a row marked False is routed nowhere.
    ``counts``: int32 rows each expert received (valid rows only; they sum
    to valid rows x k: the dropless witness). ``routes``: the experts each
    row chose, best first (also for rows that are not valid).
    ``layer``: ``p`` holds ALL layers' weights stacked on a leading axis and
    this is the layer to apply. The grouped matmuls then take the stack
    whole, as layers x experts groups of which only this layer's have rows:
    a layer sliced off the stack would first be copied (on the v5e 805 MB a
    call at OLMoE's widths, 4.2 ms against 1.8; empty groups cost 0.03).

    aux_loss is the Switch load-balancing loss over the valid rows
    (E * sum_e fraction_of_rows_whose_first_choice_is_e * mean_prob_e); add
    it to the task loss scaled by ~1e-2.
    """
    b, s, d = x.shape
    n = b * s
    xt = x.reshape(n, d).astype(dtype)
    w_router = p["w_router"] if layer is None else p["w_router"][layer]
    logits = jnp.einsum("nd,de->ne", xt, w_router.astype(dtype),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # float32 [N, E]
    gate_vals, gate_idx = jax.lax.top_k(probs, top_k)  # [N, k]
    if renormalize:
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
    live = (jnp.ones((n,), bool) if valid is None
            else valid.reshape(n).astype(bool))

    # (row, choice) pairs sorted by expert; pairs of rows that are not live
    # carry the expert id E, so they sort behind every group
    pair_expert = jnp.where(live[:, None], gate_idx, num_experts).reshape(-1)
    order = jnp.argsort(pair_expert, stable=True)
    counts = jnp.zeros((num_experts,), jnp.int32).at[pair_expert].add(
        1, mode="drop")
    xs = xt[order // top_k]  # [N*k, d]
    groups, w = counts, {k: p[k] for k in ("w_gate", "w_up", "w_down")}
    if layer is not None:
        stack = p["w_gate"].shape[0] * num_experts
        groups = jnp.zeros((stack,), jnp.int32).at[
            layer * num_experts:(layer + 1) * num_experts].set(counts)
        w = {k: a.reshape(stack, *a.shape[2:]) for k, a in w.items()}
    gate = jax.lax.ragged_dot(xs, w["w_gate"].astype(dtype), groups)
    up = jax.lax.ragged_dot(xs, w["w_up"].astype(dtype), groups)
    out = jax.lax.ragged_dot(jax.nn.silu(gate) * up,
                             w["w_down"].astype(dtype), groups)
    # back to (row, choice) order; the weighted sum over a row's k experts
    # accumulates in float32. Pairs past the last group hold nothing
    # defined: they are masked, not multiplied by 0
    out = out[jnp.argsort(order)].reshape(n, top_k, d)
    y = jnp.einsum("nkd,nk->nd",
                   jnp.where(live[:, None, None], out, 0).astype(jnp.float32),
                   gate_vals).astype(dtype)

    # Switch aux loss: encourage uniform routing
    rows = jnp.maximum(live.sum(), 1).astype(jnp.float32)
    top1 = jax.nn.one_hot(gate_idx[:, 0], num_experts, dtype=jnp.float32)
    fraction = (top1 * live[:, None]).sum(0) / rows
    mean_prob = (probs * live[:, None]).sum(0) / rows
    aux = num_experts * jnp.sum(fraction * mean_prob)
    return (y.reshape(b, s, d), aux, counts,
            gate_idx.reshape(b, s, top_k))
