"""Plain reference of the Keye-VL-2.0 LANGUAGE MODEL's forward pass
(Kwai-Keye/Keye-VL-2.0-30B-A3B, ``config.json``: ``model_type``
``KeyeVL2``): pre-norm blocks of RMSNorm, grouped-query attention with an
RMSNorm over each head's q and k, RoPE in three position streams, a learned
INDEXER beside the attention that picks the ``sa_config.topk`` tokens a query
attends (DeepSeek Sparse Attention), and a layer of SwiGLU experts of which
every token takes the ``num_experts_per_tok`` the router scores highest,
weighted by the router's softmax probabilities divided by their sum over the
experts taken (``norm_topk_prob`` true); no token is dropped, no shared
expert; final RMSNorm, untied output head. With ``x_t`` the residual:

  1. h = RMSNorm(x; ln1)   q = h Wq [S,H,D]   k = h Wk [S,G,D]   v = h Wv
     q, k = RMSNorm over each head's D values (one scale of D each)
     q, k = rope(q, k): D/2 frequency pairs of ``rope_theta``, half-rotation
     layout (pair i with i + D/2); pair i is turned by the TEMPORAL position
     for i < m0, the HEIGHT position for m0 <= i < m0 + m1, the WIDTH
     position after (``rope_scaling.mrope_section`` = [m0, m1, m2]). For
     text the three positions are equal and the rule is plain RoPE.
  2. the indexer, from the same h:  qI_j = rope(h WIq)_j  (Hi heads of Di)
     kI = rope(LayerNorm(h WIk))  (one head)   w_j = (h WIw)_j Hi^-1/2 Di^-1/2
     I[t,s] = sum_j w[t,j] relu(qI[t,j] . kI[s])             for s <= t
     (rope over the whole index head: its Di/2 pairs take the three streams
     in the sections' proportion, pair i standing where pair i D/Di of the
     full head stands)
  3. S_t = every s <= t where t + 1 <= topk; else the topk positions of
     largest I[t,s], ties to the lower s
  4. o_t,n = sum_{s in S_t} softmax_{s in S_t}(q_t,n . k_s,g(n) / sqrt(D)) v_s,g(n)
     x = x + concat_n(o) Wo                  query head n reads K/V head n // (H / G)
  5. h = RMSNorm(x; ln2)   p = softmax(h Wr)   E_t = the k experts of largest p
     g_e = p_e / sum_{E_t} p     x = x + sum_{e in E_t} g_e (silu(h Wg_e) * (h Wu_e)) Wd_e
  logits = RMSNorm(x; final) Wout

Assumed (``config.json`` has no key for any of it; the configuration file
lists the same): the q/k RMSNorm a head (the config's keys are Qwen3-MoE's,
whose public modelling code has it); the indexer's three projections read
the layer's normed input (the model has no low-rank query to read);
LayerNorm on the index key, RoPE over the whole index head with the layer's
rule, and the two scale factors, all as DeepSeek-V3.2-Exp's public
``inference/model.py: Indexer`` has them; selection is a token's, not a
block's (``q_chunk_size`` / ``kv_chunk_size`` are tile sizes of the published
kernel and change no result); no multi-token-prediction head; ties in the
top k go to the lower index (``jax.lax.top_k``), of experts and of tokens
alike. DEPARTURES: the published indexer's Hadamard rotation and FP8 index
keys are LEFT OUT (an orthogonal rotation of both qI and kI leaves every qI .
kI as it was; index keys are the model's own type here); the VISION TOWER is
not part of this reference (the source's config holds the language model's
keys only): it takes token ids. Everything is float32; scores are dense and
masked; every expert is computed densely for all tokens and masked by the
token's weight for it.

Top-k is discontinuous, twice over, so ``forward`` takes ``routes`` (the
experts another implementation chose, int [L, B, S, k]) and ``selected`` (the
tokens its queries attended, bool [L, B, S, >= S]): it computes ITS OWN
probabilities and scores and uses the choices it is GIVEN. ``routes`` stands
first: a flipped route swaps an eighth of a token's renormalized expert
output, a flipped token moves 1/topk of a query's attention mass, and the
harness hands a reference the first keyword the program can fill.

``hp`` is the configuration file's object; ``params`` is the system's weight
tree, read by layout only: ``embed.table [V, d]``, per layer ``attn.wq [d,
H, D], attn.wk, attn.wv [d, G, D], attn.wo [H, D, d], attn.q_norm,
attn.k_norm [D], attn.wi_q [d, Hi, Di], attn.wi_k [d, Di], attn.wi_w [d,
Hi], attn.ik_scale, attn.ik_bias [Di], ln1.scale, ln2.scale [d],
mlp.w_router [d, E], mlp.w_gate, mlp.w_up [E, d, f], mlp.w_down [E, f, d]``,
``final_norm.scale``, ``lm_head.kernel [d, V]``; the layers kept apart or
stacked on a leading axis (``reference/mellum.py: layer_of``). Scores and
attention run in blocks of query rows and the logits come back as a HOST
array, the head computed in blocks of rows and of the vocabulary.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import common
from perfbench.reference.mellum import experts, layer_of
from perfbench.reference.mistral import rms_norm
from perfbench.reference.olmoe import token_weights

F32 = common.F32
QUERY_ROWS = 256    # query rows of one block of scores: [H, 256, S] float32
HEAD_ROWS = 512     # rows of one block of the head
HEAD_COLUMNS = 16384  # columns of the vocabulary of one block of it


def layer_norm(x, scale, bias, eps):
    mean = x.mean(axis=-1, keepdims=True)
    var = jnp.square(x - mean).mean(axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + bias


def relu(x):
    return jnp.maximum(x, 0.0)


def stream_positions(positions, batch: int, length: int):
    """[3, B, S] float32: the temporal, height and width position of every
    token; ``positions`` None is text, the three equal to the index."""
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(length)[None, None],
                                     (3, batch, length))
    return jnp.asarray(positions, F32)


def rotate(x, streams, theta: float, sections, full_dim: int):
    """x [B,S,heads,d] by ``streams`` [3,B,S]: pair i of the d/2 (with i +
    d/2) turned by ``stream * theta^(-2i/d)``, the stream being that of the
    section in which pair ``i * full_dim / d`` of a full head lies."""
    d = x.shape[-1]
    pairs = d // 2
    inv_freq = theta ** (-2.0 * np.arange(pairs, dtype=np.float64) / d)
    edges = np.cumsum(sections)
    assert edges[-1] == full_dim // 2, (sections, full_dim)
    stream = np.searchsorted(edges, np.arange(pairs) * (full_dim // d),
                             side="right")
    pos = jnp.moveaxis(streams, 0, -1)[..., stream]            # [B,S,pairs]
    angle = pos * jnp.asarray(inv_freq, F32)
    cos, sin = jnp.cos(angle)[:, :, None], jnp.sin(angle)[:, :, None]
    a, b = x[..., :pairs], x[..., pairs:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def head_weights(h, wi_w, heads: int, dim: int):
    """w [B,S,Hi]: the index heads' weights of every query."""
    return (h @ wi_w) * heads ** -0.5 * dim ** -0.5


def score_block(qi, w, ki):
    """qi [B,R,Hi,Di], w [B,R,Hi], ki [B,S,Di] -> I [B,R,S]."""
    return jnp.einsum("brh,brhs->brs", w,
                      relu(jnp.einsum("brhd,bsd->brhs", qi, ki)))


def select_block(scores, first, topk: int):
    """scores [B,R,S] of the queries at positions ``first`` .. -> bool
    [B,R,S]: every s <= t while t + 1 <= topk, else the topk largest, ties
    to the lower s."""
    b, r, s = scores.shape
    t = first + jnp.arange(r)[:, None]
    seen = jnp.arange(s)[None, :] <= t
    masked = jnp.where(seen[None], scores, -jnp.inf)
    best = jax.lax.top_k(masked, min(topk, s))[1]              # [B,R,k]
    taken = jnp.zeros((b, r, s), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(r)[None, :, None],
        best].set(True)
    return jnp.logical_and(taken, seen[None])


def attend_block(q, k, v, taken):
    """q [B,R,H,D], k/v [B,S,G,D], taken [B,R,S] -> [B,R,H,D]."""
    group = q.shape[2] // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("brhd,bshd->bhrs", q, k) / jnp.sqrt(F32(q.shape[-1]))
    scores = jnp.where(taken[:, None], scores, -jnp.inf)
    return jnp.einsum("bhrs,bshd->brhd", jax.nn.softmax(scores, axis=-1), v)


def topk_of(hp) -> int:
    return int(hp["sa_config"]["topk"])


def mix_tokens(x, w, hp, streams, selected):
    """Steps 1 to 4 of a layer and the router's input: (x after attention,
    the expert layer's input h, router probabilities [B,S,E], the tokens
    every query attended, bool [B,S,S])."""
    eps = hp["rms_norm_eps"]
    sa, a = hp["sa_config"], w["attn"]
    theta, sections = float(hp["rope_theta"]), hp["rope_scaling"][
        "mrope_section"]
    dim = hp["head_dim"]
    turn = lambda y: rotate(y, streams, theta, sections, dim)
    h = rms_norm(x, w["ln1"]["scale"], eps)
    q = jnp.einsum("bsd,dhk->bshk", h, a["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, a["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, a["wv"])
    q = turn(rms_norm(q, a["q_norm"], eps))
    k = turn(rms_norm(k, a["k_norm"], eps))
    qi = turn(jnp.einsum("bsd,dhk->bshk", h, a["wi_q"]))
    ki = turn(layer_norm(h @ a["wi_k"], a["ik_scale"], a["ik_bias"],
                         eps)[:, :, None])[:, :, 0]
    wt = head_weights(h, a["wi_w"], sa["indexer_num_heads"],
                      sa["indexer_head_dim"])
    s = x.shape[1]
    outs, takens = [], []
    for lo in range(0, s, QUERY_ROWS):
        rows = slice(lo, lo + QUERY_ROWS)
        if selected is None:
            taken = select_block(score_block(qi[:, rows], wt[:, rows], ki),
                                 lo, topk_of(hp))
        else:
            taken = selected[:, rows, :s]
        outs.append(attend_block(q[:, rows], k, v, taken))
        takens.append(taken)
    x = x + jnp.einsum("bshk,hkd->bsd", jnp.concatenate(outs, axis=1),
                       a["wo"])
    h = rms_norm(x, w["ln2"]["scale"], eps)
    return (x, h, jax.nn.softmax(h @ w["mlp"]["w_router"], axis=-1),
            jnp.concatenate(takens, axis=1))


def _run(params, tokens, hp, routes, selected, positions, keep_choice):
    """(final hidden state [B,S,d] after the last norm, router probabilities
    [L,B,S,E], and with ``keep_choice`` the tokens attended [L,B,S,S])."""
    eps = hp["rms_norm_eps"]
    top_k, renorm = hp["num_experts_per_tok"], bool(hp["norm_topk_prob"])
    streams = stream_positions(positions, *tokens.shape)
    mix = jax.jit(lambda x, w, taken: mix_tokens(
        x, common.to_f32(w), hp, streams, taken))
    weigh = jax.jit(lambda p, r: token_weights(p, r, top_k, renorm))
    feed = jax.jit(experts)
    x = params["embed"]["table"][tokens].astype(F32)
    all_probs, all_taken = [], []
    for i in range(hp["num_hidden_layers"]):
        w, stack, where = layer_of(params["blocks"], i)
        x, h, probs, taken = mix(
            x, w, None if selected is None else jnp.asarray(selected[i]))
        weights = weigh(probs, None if routes is None else routes[i])
        x = x + feed(h, weights, stack, tuple(jnp.int32(j) for j in where))
        all_probs.append(probs)
        if keep_choice:
            all_taken.append(np.asarray(taken))
    x = rms_norm(x, params["final_norm"]["scale"].astype(F32), eps)
    return x, jnp.stack(all_probs), all_taken


def _head(params, x) -> np.ndarray:
    """x [B,S,d] -> logits [B,S,V] on the host, a block of rows and of the
    vocabulary at a time (the head's float32 copy would be 1.2 GB whole)."""
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
def forward(params, tokens, hp, routes=None, selected=None, positions=None):
    """tokens [B,S] int32 -> logits [B,S,V] float32, a host array.
    ``routes``: None (each token takes the experts this reference scores
    highest) or int [L,B,S,k], the experts each token is given.
    ``selected``: None (each query attends the tokens this reference's
    indexer scores highest) or bool [L,B,S,>=S], the tokens each query is
    given. ``positions``: None (text) or [3,B,S], the three streams."""
    return _head(params, _run(params, tokens, hp, routes, selected,
                              positions, False)[0])


@common.highest
def forward_and_choices(params, tokens, hp, routes=None, selected=None,
                        positions=None):
    """``forward``, the router probabilities [L,B,S,E] it computed and the
    tokens every query attended, bool [L,B,S,S]."""
    x, probs, taken = _run(params, tokens, hp, routes, selected, positions,
                           True)
    return _head(params, x), probs, np.stack(taken)
