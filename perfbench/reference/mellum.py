"""Plain reference of the Mellum-2 forward pass (JetBrains/
Mellum2-12B-A2.5B-Instruct, ``config.json``: ``model_type`` ``mellum``):
pre-norm blocks of RMSNorm, grouped-query rotary attention whose head size is
a key of its own (``head_dim``, not ``hidden_size / num_attention_heads``)
and whose layers are of two kinds (``layer_types``), and a layer of SwiGLU
experts of which every token takes the ``num_experts_per_tok`` the router
scores highest, weighted by the router's softmax probabilities divided by
their sum over the experts taken (``norm_topk_prob`` true); no token is
dropped, no shared expert; final RMSNorm, untied output head.

    h = RMSNorm(x; ln1)    q = h Wq [S,H,D]   k = h Wk [S,G,D]   v = h Wv [S,G,D]    no bias, no q/k norm
    q, k = rope_t(q, k)                                   t = the layer's kind
    a_ij = softmax_j(q_i . k_j / sqrt(D))  over j <= i                   full_attention
                                           over i - W < j <= i           sliding_attention, W = sliding_window
    x = x + concat_h(a v) Wo                     query head n reads K/V head n // (H / G)
    h = RMSNorm(x; ln2)    p = softmax(h Wr)    S = the k experts of largest p    g_e = p_e / sum_S p
    x = x + sum_{e in S} g_e (silu(h Wg_e) * (h Wu_e)) Wd_e
    logits = RMSNorm(x; final) Wout

``rope_t`` is read from ``rope_parameters[t]``, half-rotation layout (pair i
with i + D/2): ``default`` rotates pair i by ``pos * theta^(-2i/D)``;
``yarn`` (Peng et al. 2023, arXiv:2309.00071, as the public ``transformers``
rule computes it) divides by ``factor`` the frequencies that turn fewer than
``beta_slow`` times over ``original_max_position_embeddings``, keeps those
that turn more than ``beta_fast`` times, ramps linearly over the pair indices
between (``low = floor(dim(beta_fast))``, ``high = ceil(dim(beta_slow))``,
``dim(r) = D ln(original / (2 pi r)) / (2 ln theta)``, clipped to 0 .. D-1),
and multiplies cos and sin by ``attention_factor``.

Assumed (``config.json`` has no key for any of it; the configuration file
lists the same): no RMSNorm on q or k; NO multi-token-prediction head (the
catalog's description names one, the config has no key for it: the model
ends in the one head); ``truncate`` true in the YaRN rule; ties in the top k
go to the lower index (``jax.lax.top_k``). ``intermediate_size`` is used by
no layer: every entry of ``mlp_layer_types`` is ``sparse``. Departures:
everything is float32; every expert is computed densely for all tokens and
masked by the token's weight for it (one expert at a time, in a loop inside
one compiled call a layer).

Top-k is discontinuous, so ``forward`` takes ``routes`` (the experts another
implementation chose, int [L, B, S, k]) exactly as ``reference/olmoe.py``
does: it computes ITS OWN probabilities and weights for the experts it is
GIVEN.

``hp`` is the configuration file's object; ``params`` is the system's weight
tree, read by layout only: ``embed.table [V, d]``, per layer ``attn.wq [d,
H, D], attn.wk, attn.wv [d, G, D], attn.wo [H, D, d], ln1.scale, ln2.scale
[d], mlp.w_router [d, E], mlp.w_gate, mlp.w_up [E, d, f], mlp.w_down [E, f,
d]``, ``final_norm.scale``, ``lm_head.kernel [d, V]``; the layers kept apart
(``blocks["0"]``, ...), all stacked on a leading axis, or — the system's
layout for a pattern of kinds — stacked by their place in the pattern's
period (``blocks["p0"]`` holds layers 0, P, 2P, ...). Attention runs in
blocks of query rows and the logits come back as a HOST array, the head
computed in blocks of rows: at the cell's 4.4k-token check they are 1.7 GB,
beside a float32 copy of the head of 0.9 GB.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import common
from perfbench.reference.mistral import rms_norm
from perfbench.reference.olmoe import token_weights

F32 = common.F32
QUERY_ROWS = 512   # query rows of one block of attention: [H, 512, S] scores
HEAD_ROWS = 512    # rows of one block of the head


def frequencies(head_dim: int, rule) -> tuple:
    """(inverse frequencies [D/2] float32, the factor of cos and sin) of one
    entry of ``rope_parameters``."""
    theta = float(rule["rope_theta"])
    i = np.arange(head_dim // 2, dtype=np.float64)
    base = theta ** (-2.0 * i / head_dim)
    if rule.get("rope_type", "default") == "default":
        return base.astype(np.float32), 1.0
    assert rule["rope_type"] == "yarn", rule
    original = float(rule["original_max_position_embeddings"])

    def dim(turns):
        return (head_dim * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low, high = dim(float(rule["beta_fast"])), dim(float(rule["beta_slow"]))
    if rule.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, head_dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = (1.0 - ramp) * base + ramp * base / float(rule["factor"])
    factor = rule.get("attention_factor")
    if factor is None:
        factor = 0.1 * math.log(float(rule["factor"])) + 1.0
    return inv_freq.astype(np.float32), float(factor)


def rotate(x, inv_freq, factor):
    """x [B,S,H,D] at positions 0..S-1: pairs (i, i + D/2) turned by
    ``position * inv_freq[i]``, cos and sin times ``factor``."""
    s, d = x.shape[1], x.shape[-1]
    angle = jnp.arange(s, dtype=F32)[:, None] * jnp.asarray(inv_freq)[None]
    cos = (jnp.cos(angle) * factor)[None, :, None]
    sin = (jnp.sin(angle) * factor)[None, :, None]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(q, k, v, window):
    """q [B,S,H,D], k/v [B,S,G,D] -> [B,S,H,D]: causal softmax attention,
    under ``window`` (an int, or None) over the last ``window`` positions,
    the query's own among them; a block of query rows at a time."""
    b, s, h, d = q.shape
    group = h // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    j = jnp.arange(s)[None, :]

    def block(q_rows, first):
        i = first + jnp.arange(q_rows.shape[1])[:, None]
        seen = j <= i
        if window is not None:
            seen = jnp.logical_and(seen, j > i - window)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_rows, k) / jnp.sqrt(F32(d))
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd",
                          jax.nn.softmax(scores, axis=-1), v)

    return jnp.concatenate([block(q[:, lo:lo + QUERY_ROWS], lo)
                            for lo in range(0, s, QUERY_ROWS)], axis=1)


def attend_and_score(x, w, eps, rope, window):
    """The attention half of a layer and the router: (x after attention,
    the expert layer's input h, router probabilities [B,S,E])."""
    h = rms_norm(x, w["ln1"]["scale"], eps)
    q = jnp.einsum("bsd,dhk->bshk", h, w["attn"]["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, w["attn"]["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, w["attn"]["wv"])
    o = attention(rotate(q, *rope), rotate(k, *rope), v, window)
    x = x + jnp.einsum("bshk,hkd->bsd", o, w["attn"]["wo"])
    h = rms_norm(x, w["ln2"]["scale"], eps)
    return x, h, jax.nn.softmax(h @ w["mlp"]["w_router"], axis=-1)


def experts(h, weights, mlp, where):
    """sum over the experts e of (e's SwiGLU of every token) x (the token's
    weight for e, 0 where it did not take it): every expert computed densely,
    one at a time — its three matrices sliced off the system's stack
    (``mlp[name][where + (e,)]``, the system's own type) and cast to float32
    inside the loop, so no layer's 64 experts ever exist in float32."""
    def add(e, y):
        wg, wu, wd = (mlp[name][where + (e,)].astype(F32)
                      for name in ("w_gate", "w_up", "w_down"))
        out = (jax.nn.silu(h @ wg) * (h @ wu)) @ wd
        return y + out * jnp.take(weights, e, axis=-1)[..., None]

    return jax.lax.fori_loop(0, mlp["w_gate"].shape[len(where)], add,
                             jnp.zeros_like(h))


def layer_of(blocks, i: int):
    """(layer i's weights but the experts — attn, norms, router — sliced off
    whatever stack holds them; the experts' stack as it is; where in it the
    layer's experts start: ``()`` or ``(index,)``)."""
    if str(i) in blocks:
        block, where = blocks[str(i)], ()
    elif "attn" in blocks:
        block, where = blocks, (i,)
    else:
        period = sum(1 for name in blocks if name.startswith("p"))
        block, where = blocks[f"p{i % period}"], (i // period,)
    w = {"attn": block["attn"], "ln1": block["ln1"], "ln2": block["ln2"],
         "mlp": {"w_router": block["mlp"]["w_router"]}}
    if where:
        w = jax.tree.map(lambda a: a[where[0]], w)
    return w, block["mlp"], where


def _run(params, tokens, hp, routes):
    """(final hidden state [B,S,d] after the last norm, router probabilities
    [L,B,S,E])."""
    eps = hp["rms_norm_eps"]
    top_k, renorm = hp["num_experts_per_tok"], bool(hp["norm_topk_prob"])
    ropes = {kind: frequencies(hp["head_dim"], rule)
             for kind, rule in hp["rope_parameters"].items()}
    windows = {"full_attention": None,
               "sliding_attention": int(hp["sliding_window"])}
    attend = {kind: jax.jit(lambda x, w, kind=kind: attend_and_score(
        x, common.to_f32(w), eps, ropes[kind], windows[kind]))
        for kind in windows}
    weigh = jax.jit(lambda p, r: token_weights(p, r, top_k, renorm))
    mix = jax.jit(experts)
    x = params["embed"]["table"][tokens].astype(F32)
    all_probs = []
    for i in range(hp["num_hidden_layers"]):
        assert hp["mlp_layer_types"][i] == "sparse", hp["mlp_layer_types"]
        w, stack, where = layer_of(params["blocks"], i)
        x, h, probs = attend[hp["layer_types"][i]](x, w)
        weights = weigh(probs, None if routes is None else routes[i])
        x = x + mix(h, weights, stack, tuple(jnp.int32(j) for j in where))
        all_probs.append(probs)
    x = rms_norm(x, params["final_norm"]["scale"].astype(F32), eps)
    return x, jnp.stack(all_probs)


def _head(params, x) -> np.ndarray:
    """x [B,S,d] -> logits [B,S,V] on the host, a block of rows at a time."""
    kernel = params["lm_head"]["kernel"].astype(F32)
    rows = jax.jit(jnp.matmul)  # the head an ARGUMENT: closed over, a constant
    return np.concatenate([np.asarray(rows(x[:, lo:lo + HEAD_ROWS], kernel))
                           for lo in range(0, x.shape[1], HEAD_ROWS)], axis=1)


@common.highest
def forward(params, tokens, hp, routes=None):
    """tokens [B,S] int32 -> logits [B,S,V] float32, a host array.
    ``routes``: None (each token takes the experts this reference scores
    highest) or int [L,B,S,k], the experts each token is given."""
    return _head(params, _run(params, tokens, hp, routes)[0])


@common.highest
def forward_and_router(params, tokens, hp, routes=None):
    """``forward`` and the router probabilities [L,B,S,E] it computed."""
    x, probs = _run(params, tokens, hp, routes)
    return _head(params, x), probs
