"""Plain reference of the OLMoE forward pass (Muennighoff et al. 2024,
arXiv:2409.02060; ``modeling_olmoe.py`` of the released OLMoE-1B-7B-0125):
pre-norm blocks of RMSNorm, rotary attention whose projected queries and
keys pass an RMSNorm of their own, and a layer of SwiGLU experts of which
every token takes the ``num_experts_per_tok`` the router scores highest,
weighted by the router's softmax probabilities as they are (``norm_topk_prob``
false: not divided by their sum); no token is dropped, there is no shared
expert; final RMSNorm, untied output head.

    h = RMSNorm(x; ln1)      q = RMSNorm(h Wq; q_norm)   k = RMSNorm(h Wk; k_norm)   v = h Wv
    x = x + Attn(RoPE(q), RoPE(k), v) Wo
    h = RMSNorm(x; ln2)      p = softmax(h Wr)           S = the k experts of largest p
    x = x + sum_{e in S} p_e (silu(h Wg_e) * (h Wu_e)) Wd_e
    logits = RMSNorm(x; final) Wout

``q_norm`` / ``k_norm`` hold one scale for each of the H*D (Hkv*D) projected
values and normalize over all of them, before the split into heads and
before the rotation. Every expert is computed densely for all tokens and
masked by the token's weight for it (0 where the token did not take it):
no sort, no groups, no capacity.

Departures from the published code, none of which changes a value beyond
rounding: everything is float32 (the release computes in bfloat16 and only
the router's softmax in float32); ``clip_qkv`` is null in the release and is
not implemented; attention has no biases (``attention_bias`` false).

Top-k is discontinuous: two implementations whose router probabilities
differ by rounding may take a different k-th expert. So ``forward`` takes
``routes`` (the experts another implementation chose, int [L, B, S, k]): it
then computes ITS OWN probabilities and weights for the experts it is GIVEN,
and ``routing_margin`` says how far from this reference's own choice the
given ones were.

``hp`` is the configuration file's object (the source's own keys); ``params``
is the system's weight tree, read by layout only: ``embed.table [V,d]``,
``blocks.{attn.wq [d,H,D], attn.wk, attn.wv [d,Hkv,D], attn.wo [H,D,d],
attn.q_norm [H*D], attn.k_norm [Hkv*D], ln1.scale, ln2.scale [d],
mlp.w_router [d,E], mlp.w_gate, mlp.w_up [E,d,f], mlp.w_down [E,f,d]}``,
``final_norm.scale``, ``lm_head.kernel [d,V]``; the layers either stacked on
a leading axis of every leaf of ``blocks`` or kept apart as
``blocks["0"]``, ``blocks["1"]``, ... (the system has both layouts).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference import common
from perfbench.reference.mistral import rms_norm, rotate

F32 = common.F32


def attend_and_score(x, w, eps, theta):
    """The attention half of a layer and the router: (x after attention,
    the expert layer's input h, router probabilities [B,S,E])."""
    h = rms_norm(x, w["ln1"]["scale"], eps)
    q = jnp.einsum("bsd,dhk->bshk", h, w["attn"]["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, w["attn"]["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, w["attn"]["wv"])
    q = rms_norm(q.reshape(*q.shape[:2], -1), w["attn"]["q_norm"],
                 eps).reshape(q.shape)
    k = rms_norm(k.reshape(*k.shape[:2], -1), w["attn"]["k_norm"],
                 eps).reshape(k.shape)
    o = common.causal_attention(rotate(q, theta), rotate(k, theta), v)
    x = x + jnp.einsum("bshk,hkd->bsd", o, w["attn"]["wo"])
    h = rms_norm(x, w["ln2"]["scale"], eps)
    return x, h, jax.nn.softmax(h @ w["mlp"]["w_router"], axis=-1)


def token_weights(probs, routes, top_k: int, renormalize: bool):
    """[B,S,E]: a token's weight for every expert: its router probability
    for the experts it takes (``routes`` [B,S,k], or its own ``top_k``
    largest), 0 for the others."""
    if routes is None:
        routes = jax.lax.top_k(probs, top_k)[1]
    taken = jax.nn.one_hot(routes, probs.shape[-1], dtype=F32).sum(-2)
    weights = probs * taken
    if renormalize:
        weights = weights / weights.sum(-1, keepdims=True)
    return weights


def add_expert(y, h, weights, mlp, where):
    """y + (this expert's SwiGLU of every token) x (the token's weight for
    it). ``mlp`` holds the experts in the system's own type (a layer's, or
    all layers' stacked); ``where`` is the expert's index there, ``(expert,)``
    or ``(layer, expert)``: one expert is sliced off and cast at a time."""
    wg, wu, wd = (mlp[name][where].astype(F32)
                  for name in ("w_gate", "w_up", "w_down"))
    out = (jax.nn.silu(h @ wg) * (h @ wu)) @ wd
    return y + out * weights[..., where[-1], None]


def _run(params, tokens, hp, routes):
    """(logits [B,S,V], router probabilities [L,B,S,E])."""
    eps, theta = hp["rms_norm_eps"], hp["rope_theta"]
    top_k, renorm = hp["num_experts_per_tok"], bool(hp["norm_topk_prob"])
    blocks = params["blocks"]
    stacked = "attn" in blocks
    attend = jax.jit(lambda x, w: attend_and_score(
        x, common.to_f32(w), eps, theta))
    weigh = jax.jit(lambda p, r: token_weights(p, r, top_k, renorm))
    add = jax.jit(add_expert)
    x = params["embed"]["table"][tokens].astype(F32)
    all_probs = []
    for i in range(hp["num_hidden_layers"]):
        block = blocks if stacked else blocks[str(i)]
        w = {"attn": block["attn"], "ln1": block["ln1"], "ln2": block["ln2"],
             "mlp": {"w_router": block["mlp"]["w_router"]}}
        if stacked:
            w = jax.tree.map(lambda a, i=i: a[i], w)
        x, h, probs = attend(x, w)
        weights = weigh(probs, None if routes is None else routes[i])
        y = jnp.zeros_like(x)
        for e in range(hp["num_experts"]):
            y = add(y, h, weights, block["mlp"],
                    (i, e) if stacked else (e,))
        x = x + y
        all_probs.append(probs)
    x = rms_norm(x, params["final_norm"]["scale"].astype(F32), eps)
    return x @ params["lm_head"]["kernel"].astype(F32), jnp.stack(all_probs)


@common.highest
def forward(params, tokens, hp, routes=None):
    """tokens [B,S] int32 -> logits [B,S,V] float32. ``routes``: None (each
    token takes the experts this reference scores highest) or int
    [L,B,S,k], the experts each token is given."""
    return _run(params, tokens, hp, routes)[0]


@common.highest
def forward_and_router(params, tokens, hp, routes=None):
    """``forward`` and the router probabilities [L,B,S,E] it computed."""
    return _run(params, tokens, hp, routes)


def loss(params, tokens, hp, aux_weight: float = 0.0):
    """Mean next-token cross-entropy, plus ``aux_weight`` times the Switch
    load-balancing term summed over layers (E x sum over experts of the
    share of tokens whose first choice it is x its mean probability)."""
    logits, probs = forward_and_router(params, tokens, hp)
    first = jax.nn.one_hot(probs.argmax(-1), probs.shape[-1], dtype=F32)
    aux = probs.shape[-1] * jnp.sum(
        first.mean((1, 2)) * probs.mean((1, 2)))
    return common.next_token_loss(logits, tokens) + aux_weight * aux


def routing_margin(probs, routes):
    """How another implementation's choices (``routes`` [L,B,S,k]) sit in
    this reference's probabilities (``probs`` [L,B,S,E], computed along
    those routes): (the share of (layer, token) pairs whose set of experts
    differs from the reference's own top k, and the largest amount by which
    the reference's probability of an expert taken instead falls short of
    its k-th largest)."""
    k = routes.shape[-1]
    kth = jax.lax.top_k(probs, k)[0][..., -1]
    given = jnp.take_along_axis(probs, routes, axis=-1)
    short = jnp.maximum(kth[..., None] - given, 0.0)
    return float((short > 0).any(-1).mean()), float(short.max())
