"""Plain reference of GLM-4.7-Flash's forward pass (zai-org/GLM-4.7-Flash,
``config.json``: ``model_type`` ``glm4_moe_lite``): pre-norm blocks of
RMSNorm, multi-head LATENT attention (MLA), a leading dense SwiGLU layer and
then layers of SwiGLU experts behind a sigmoid router whose choice a bias
corrects, beside one shared expert; final RMSNorm, untied output head. With
``x`` the residual [S, d], H heads, and the sizes under their keys
(``q_lora_rank`` rq, ``kv_lora_rank`` r, ``qk_nope_head_dim`` n,
``qk_rope_head_dim`` e, ``v_head_dim`` v):

  1. h   = RMSNorm(x; ln1)
     c_q = RMSNorm(h Wq_a; q_a_norm)                                [S, rq]
     q   = c_q Wq_b -> [S, H, n + e] = [q_nope | q_rope]; q_rope = rope(q_rope)
     [c | kr] = h Wkv_a                                             [S, r + e]
     c   = RMSNorm(c; kv_norm);   kr = rope(kr)   (ONE rotated key a token,
                                                   shared by all H heads)
     [k_nope | v] a head = c Wkv_b                                [S, H, n + v]
     score[t, s, j] = (q_nope[t,j] . k_nope[s,j] + q_rope[t,j] . kr[s])
                      / sqrt(n + e)          for s <= t;  softmax over s
     o[t, j] = sum_s p[t,s,j] v[s,j]  [S, H, v];   x = x + concat_j(o) Wo
     rope: e/2 frequency pairs of ``rope_theta``, half-rotation layout (pair
     i with i + e/2), plain (``rope_scaling`` null, ``partial_rotary_factor``
     1: the whole rotated part turns).
  2. h2 = RMSNorm(x; ln2)
     layer i < ``first_k_dense_replace``:  x = x + (silu(h2 Wg) * (h2 Wu)) Wd
     every later layer:  s = sigmoid(h2 Wr)                    [S, E] float32
       E_t = the k experts of largest s + b   (``e_score_correction_bias``;
             ``topk_method`` noaux_tc with ``n_group`` 1 and ``topk_group``
             1: the group step is the identity)
       w_e = s_e / (sum_{E_t} s + 1e-20)   (``norm_topk_prob``: the UNBIASED
             scores of the chosen)   times ``routed_scaling_factor``
       x = x + sum_{e in E_t} w_e SwiGLU_e(h2) + SwiGLU_shared(h2)
  logits = RMSNorm(x; final) Wout

This is the UNABSORBED form only: every token's keys and values are rebuilt
from its latent; the program's cached forwards attend the latents with the
up-projections absorbed, and are held against something they are not.
Everything is float32; scores are dense and masked, a block of query rows at
a time; every expert is computed densely for all tokens and masked by the
token's weight for it.

Assumed (``config.json`` does not settle it; the configuration file lists
the same): RoPE's pairs half-split and not interleaved (a fixed permutation
of Wq_b's and Wkv_a's rotated columns maps one onto the other: weights made
from a seed cannot tell them apart); ties in the top k go to the lower
expert index (``jax.lax.top_k``); the 1e-20 under the renormalization; the
bias's values are the program's seeded ones (normal, spread 0.1: sigmoid
scores of seeded weights lie tenths apart, so the biased choice differs from
the unbiased for a measurable share of tokens); no multi-token-prediction
block (``num_nextn_predict_layers``: plain generation never evaluates it).

Top-k is discontinuous, so ``forward`` takes ``routes`` (the experts another
implementation chose, int [L, B, S, k], a row an EXPERT layer — the dense
layer chooses nothing): it computes ITS OWN scores and weighs the experts it
is GIVEN by them.

``hp`` is the configuration file's object; ``params`` is the system's
weight tree, read by layout only: ``embed.table [V, d]``, per layer
``attn.wq_a [d, rq], attn.q_a_norm [rq], attn.wq_b [rq, H, n + e],
attn.wkv_a [d, r + e], attn.kv_norm [r], attn.wkv_b [r, H, n + v], attn.wo
[H, v, d], ln1.scale, ln2.scale [d]``, a dense layer's ``mlp.w_gate,
mlp.w_up [d, f], mlp.w_down [f, d]``, an expert layer's ``mlp.w_router [d,
E], mlp.e_bias [E], mlp.w_gate, mlp.w_up [E, d, f], mlp.w_down [E, f, d],
mlp.ws_gate, mlp.ws_up [d, fs], mlp.ws_down [fs, d]``, ``final_norm.scale``,
``lm_head.kernel [d, V]``; the layers kept apart (``blocks["0"]`` ...) or
stacked, the leading dense ones under ``blocks["lead"]`` and the rest under
``blocks["body"]`` (``layer_of``). The logits come back as a HOST array, the
head computed in blocks of rows and of the vocabulary.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import common
from perfbench.reference.mellum import experts
from perfbench.reference.mistral import rms_norm, rotate

F32 = common.F32
QUERY_ROWS = 256    # query rows of one block of scores: [H, 256, S] float32
HEAD_ROWS = 512     # rows of one block of the head
HEAD_COLUMNS = 16384  # columns of the vocabulary of one block of it


def layer_of(blocks, i: int, dense: int):
    """(layer i's weights but an expert layer's experts — attn, norms,
    router, bias, shared expert, a dense layer's whole mlp — sliced off
    whatever stack holds them; the experts' stack as it is, None for a dense
    layer; where in it the layer's experts start: ``()`` or ``(index,)``)."""
    if str(i) in blocks:
        block, where = blocks[str(i)], ()
    elif i < dense:
        block, where = blocks["lead"], (i,)
    else:
        block, where = blocks["body"], (i - dense,)
    stacked = ("w_gate", "w_up", "w_down") if i >= dense else ()
    w = {"attn": block["attn"], "ln1": block["ln1"], "ln2": block["ln2"],
         "mlp": {k: a for k, a in block["mlp"].items() if k not in stacked}}
    if where:
        w = jax.tree.map(lambda a: a[where[0]], w)
    return w, (block["mlp"] if stacked else None), where


def attend(q, k, v):
    """q, k [B,S,H,D], v [B,S,H,V] -> [B,S,H,V]: causal softmax attention a
    block of query rows at a time, scaled by 1/sqrt(D)."""
    s = q.shape[1]
    scale = 1.0 / jnp.sqrt(F32(q.shape[-1]))

    def block(rows, lo):
        scores = jnp.einsum("brhd,bshd->bhrs", rows, k) * scale
        seen = (jnp.arange(s)[None, :]
                <= lo + jnp.arange(rows.shape[1])[:, None])
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        return jnp.einsum("bhrs,bshv->brhv",
                          jax.nn.softmax(scores, axis=-1), v)

    return jnp.concatenate([block(q[:, lo:lo + QUERY_ROWS], lo)
                            for lo in range(0, s, QUERY_ROWS)], axis=1)


def mix_tokens(x, w, hp):
    """Step 1 of a layer and the feed-forward's input: (x after attention,
    h2)."""
    eps, theta = hp["rms_norm_eps"], float(hp["rope_theta"])
    n, r = hp["qk_nope_head_dim"], hp["kv_lora_rank"]
    a = w["attn"]
    h = rms_norm(x, w["ln1"]["scale"], eps)
    q = jnp.einsum("bsr,rhk->bshk",
                   rms_norm(h @ a["wq_a"], a["q_a_norm"], eps), a["wq_b"])
    q = jnp.concatenate([q[..., :n], rotate(q[..., n:], theta)], axis=-1)
    ckr = h @ a["wkv_a"]
    c = rms_norm(ckr[..., :r], a["kv_norm"], eps)
    kr = rotate(ckr[..., None, r:], theta)                      # [B,S,1,e]
    kv = jnp.einsum("bsr,rhk->bshk", c, a["wkv_b"])             # [B,S,H,n+v]
    k = jnp.concatenate([kv[..., :n], jnp.broadcast_to(
        kr, kv.shape[:3] + kr.shape[3:])], axis=-1)
    x = x + jnp.einsum("bshv,hvd->bsd", attend(q, k, kv[..., n:]), a["wo"])
    return x, rms_norm(x, w["ln2"]["scale"], eps)


def swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ gate) * (h @ up)) @ down


def token_weights(scores, bias, routes, hp):
    """[B,S,E]: a token's weight for every expert: for the experts it takes
    (``routes`` [B,S,k], or the k of largest ``scores + bias``) its unbiased
    score over their sum, times the scaling factor; 0 for the others."""
    if routes is None:
        routes = jax.lax.top_k(scores + bias, hp["num_experts_per_tok"])[1]
    taken = jax.nn.one_hot(routes, scores.shape[-1], dtype=F32).sum(-2)
    weights = scores * taken
    if hp["norm_topk_prob"]:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return weights * hp["routed_scaling_factor"]


def _run(params, tokens, hp, routes):
    """(final hidden state [B,S,d] after the last norm, the routers' scores
    [L,B,S,E], a row an expert layer)."""
    eps, dense = hp["rms_norm_eps"], hp["first_k_dense_replace"]
    mix = jax.jit(lambda x, w: mix_tokens(x, common.to_f32(w), hp))
    score = jax.jit(lambda h, w: jax.nn.sigmoid(h @ w.astype(F32)))
    weigh = jax.jit(lambda s, b, r: token_weights(s, b.astype(F32), r, hp))
    dense_ff = jax.jit(lambda h, m: swiglu(
        h, *(m[k].astype(F32) for k in ("w_gate", "w_up", "w_down"))))
    shared_ff = jax.jit(lambda h, m: swiglu(
        h, *(m[k].astype(F32) for k in ("ws_gate", "ws_up", "ws_down"))))
    feed = jax.jit(experts)
    x = params["embed"]["table"][tokens].astype(F32)
    all_scores = []
    for i in range(hp["num_hidden_layers"]):
        w, stack, where = layer_of(params["blocks"], i, dense)
        x, h = mix(x, w)
        if stack is None:
            x = x + dense_ff(h, w["mlp"])
            continue
        scores = score(h, w["mlp"]["w_router"])
        weights = weigh(scores, w["mlp"]["e_bias"],
                        None if routes is None else routes[i - dense])
        x = x + feed(h, weights, stack, tuple(jnp.int32(j) for j in where))
        if hp["n_shared_experts"]:
            x = x + shared_ff(h, w["mlp"])
        all_scores.append(scores)
    x = rms_norm(x, params["final_norm"]["scale"].astype(F32), eps)
    return x, jnp.stack(all_scores)


def _head(params, x) -> np.ndarray:
    """x [B,S,d] -> logits [B,S,V] on the host, a block of rows and of the
    vocabulary at a time (the head's float32 copy would be 1.3 GB whole)."""
    kernel = params["lm_head"]["kernel"]
    block = jax.jit(lambda rows, columns: rows @ columns.astype(F32))
    out = np.empty(x.shape[:2] + (kernel.shape[1],), np.float32)
    for lo in range(0, kernel.shape[1], HEAD_COLUMNS):
        columns = kernel[:, lo:lo + HEAD_COLUMNS]
        for r in range(0, x.shape[1], HEAD_ROWS):
            out[:, r:r + HEAD_ROWS, lo:lo + HEAD_COLUMNS] = np.asarray(
                block(x[:, r:r + HEAD_ROWS], columns))
    return out


@common.highest
def forward(params, tokens, hp, routes=None):
    """tokens [B,S] int32 -> logits [B,S,V] float32, a host array.
    ``routes``: None (each token takes the experts this reference's biased
    scores rank highest) or int [L,B,S,k], the experts each token is given,
    a row an expert layer."""
    return _head(params, _run(params, tokens, hp, routes)[0])


@common.highest
def forward_and_router(params, tokens, hp, routes=None):
    """``forward`` and the routers' sigmoid scores [L,B,S,E] it computed."""
    x, scores = _run(params, tokens, hp, routes)
    return _head(params, x), scores
