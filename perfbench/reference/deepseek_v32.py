"""Plain reference of DeepSeek-V3.2-Exp's forward pass
(deepseek-ai/DeepSeek-V3.2-Exp, ``config.json``: ``model_type``
``deepseek_v32``): pre-norm blocks of RMSNorm, multi-head LATENT attention
whose queries attend the ``index_topk`` tokens a learned INDEXER picks
(DeepSeek Sparse Attention), leading dense SwiGLU layers and then layers of
SwiGLU experts behind a sigmoid router that chooses among the best
``topk_group`` of ``n_group`` GROUPS of experts, beside one shared expert;
final RMSNorm, untied output head. With ``x`` the residual [S, d], H heads
and the sizes under their keys (``q_lora_rank`` rq, ``kv_lora_rank`` r,
``qk_nope_head_dim`` n, ``qk_rope_head_dim`` e, ``v_head_dim`` v,
``index_n_heads`` Hi, ``index_head_dim`` Di):

  1. h   = RMSNorm(x; ln1)
     c_q = RMSNorm(h Wq_a; q_a_norm)                                [S, rq]
     q   = c_q Wq_b -> [S, H, n + e] = [q_nope | q_rope]; q_rope = rope(q_rope)
     [c | kr] = h Wkv_a;  c = RMSNorm(c; kv_norm);  kr = rope(kr)  (ONE rotated
                                             key a token, shared by the heads)
     [k_nope | v] a head = c Wkv_b                                [S, H, n + v]
  2. the indexer:  qI = c_q WIq [S, Hi, Di]  (OUT OF THE QUERY'S BOTTLENECK)
     kI = LayerNorm(h WIk; scale, bias) [S, Di]  (one a token)
     the FIRST e values of every qI head and of kI = rope(those), the other
     Di - e as they are;   w = (h WIw) Hi^-1/2 Di^-1/2 [S, Hi]
     I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])             for s <= t
  3. S_t = every s <= t where t + 1 <= index_topk; else the index_topk
     positions of largest I[t, s], ties to the lower s (a STABLE sort)
  4. score[t, s, j] = (q_nope[t,j] . k_nope[s,j] + q_rope[t,j] . kr[s]) * sc
     for s in S_t, softmax over them;  sc = (n + e)^-1/2 * m^2,  m = 0.1
     ``mscale_all_dim`` ln(``factor``) + 1 (``rope_scaling`` of ``type`` yarn)
     o[t, j] = sum_s p[t,s,j] v[s,j];   x = x + concat_j(o) Wo
     rope: e/2 frequency pairs, half-rotation layout (pair i with i + e/2),
     YaRN frequencies — pair i's ``rope_theta^(-2i/e)`` divided by ``factor``
     where it turns fewer than ``beta_slow`` times over
     ``original_max_position_embeddings``, kept where more than
     ``beta_fast``, a linear ramp between (bounds floored and ceiled) — and
     cos and sin times ``mscale``'s factor over ``mscale_all_dim``'s (1 when
     the two are equal).
  5. h2 = RMSNorm(x; ln2)
     layer i < ``first_k_dense_replace``:  x = x + (silu(h2 Wg) * (h2 Wu)) Wd
     every later layer:  s = sigmoid(h2 Wr)  [S, E] float32, E the ROUTER's
       width;  b = s + ``e_score_correction_bias``
       a group's score (``n_group`` groups of E / n_group consecutive
       experts) = the sum of its TWO largest b; the ``topk_group`` best
       groups stay (ties to the lower group);  E_t = the k experts of
       largest b among theirs (ties to the lower index)
       w_e = s_e / (sum_{E_t} s + 1e-20)  (the UNBIASED scores, over ALL k
       chosen)  times ``routed_scaling_factor``
       x = x + sum_{e in E_t, e HELD} w_e SwiGLU_e(h2) + SwiGLU_shared(h2)
       (``held`` = (first, count): the experts whose weights ``params``
       holds, a chip's share of the layer; what the others would add is
       left out, as in the program)
  logits = RMSNorm(x; final) Wout        (over the vocabulary ``params`` holds)

This is the UNABSORBED form only: every token's keys and values are rebuilt
from its latent, the index scores are dense [S, S]; the program's forwards
attend gathered latents with the up-projections absorbed. Everything is
float32, a block of query rows at a time; every held expert is computed
densely for all tokens, one matrix cast to float32 at a time, and masked by
the token's weight for it. Nothing here imports ``ray_tpu``.

Assumed (``config.json`` does not settle it; the configuration file lists
the same): RoPE's pairs half-split; ties to the lower index, of tokens,
groups and experts; the 1e-20; the seeded bias; bf16 index keys with neither
the Hadamard rotation (orthogonal: no score changes) nor FP8 scales; no
multi-token-prediction block.

Top-k is discontinuous, twice over, so ``forward`` takes ``routes`` (the
experts another implementation chose, int [L, B, S, k], a row an EXPERT
layer) and ``selected`` (the tokens its queries attended, bool [L, B, S,
>= S]): it computes ITS OWN scores and uses the choices it is GIVEN.
``routes`` stands first, as in ``reference/keye.py``.

``hp`` is the configuration file's object (``n_routed_experts`` the experts
HELD, ``router_experts`` the router's width where that is more,
``experts_held_first`` the first held); ``params`` is the system's weight
tree, read by layout only: ``reference/glm_moe_lite.py``'s, and beside it
per layer ``attn.wi_q [rq, Hi, Di], attn.wi_k [d, Di], attn.wi_w [d, Hi],
attn.ik_scale, attn.ik_bias [Di]``; the experts' stacks hold the HELD
experts. The logits come back as a HOST array.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import common
from perfbench.reference.keye import layer_norm
from perfbench.reference.mistral import rms_norm

F32 = common.F32
QUERY_ROWS = 256    # query rows of one block of scores: [H, 256, S] float32
HEAD_ROWS = 512     # rows of one block of the head
HEAD_COLUMNS = 16384  # columns of the vocabulary of one block of it
FF_COLUMNS = 4608   # hidden columns of one block of a dense feed-forward
HEADS = 32          # heads whose keys and values are rebuilt at once


def layer_of(blocks, i: int, dense: int):
    """(layer i's mixer and norms, sliced off whatever stack holds them; the
    layer's feed-forward as its stack holds it — a leading layer's three
    matrices are 0.8 GB of bf16 and are never sliced whole —; where in that
    stack the layer lies: ``()`` or ``(index,)``)."""
    if str(i) in blocks:
        block, where = blocks[str(i)], ()
    elif i < dense:
        block, where = blocks["lead"], (i,)
    else:
        block, where = blocks["body"], (i - dense,)
    w = {k: block[k] for k in ("attn", "ln1", "ln2")}
    if where:
        w = jax.tree.map(lambda a: a[where[0]], w)
    return w, block["mlp"], where


def yarn(dim: int, hp):
    """(inverse frequencies [dim / 2] float64, what cos and sin are
    multiplied by, what the softmax scale is multiplied by) of
    ``rope_scaling``; plain ``rope_theta`` where it is null."""
    theta, rs = float(hp["rope_theta"]), hp.get("rope_scaling")
    base = theta ** (-2.0 * np.arange(dim // 2, dtype=np.float64) / dim)
    if not rs:
        return base, 1.0, 1.0
    assert rs["type"] == "yarn", rs
    factor, original = float(rs["factor"]), float(
        rs["original_max_position_embeddings"])
    pair = lambda turns: (dim * math.log(original / (turns * 2 * math.pi))
                          / (2 * math.log(theta)))
    low = max(math.floor(pair(float(rs["beta_fast"]))), 0)
    high = min(math.ceil(pair(float(rs["beta_slow"]))), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    mscale = lambda m: 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1
    every = mscale(float(rs.get("mscale_all_dim", 0)))
    return ((1 - ramp) * base + ramp * base / factor,
            mscale(float(rs.get("mscale", 1))) / every, every * every)


def rotate(x, inv_freq, factor: float = 1.0):
    """x [B,S,H,D] at positions 0 .. S-1: pair i (with i + D/2) turned by
    ``position * inv_freq[i]``, cos and sin times ``factor``."""
    half = x.shape[-1] // 2
    angle = jnp.arange(x.shape[1], dtype=F32)[:, None] * jnp.asarray(
        inv_freq, F32)[None]
    cos = (jnp.cos(angle) * factor)[None, :, None]
    sin = (jnp.sin(angle) * factor)[None, :, None]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def score_block(qi, w, ki):
    """qi [B,R,Hi,Di], w [B,R,Hi], ki [B,S,Di] -> I [B,R,S]."""
    return jnp.einsum("brh,brhs->brs", w, jnp.maximum(
        jnp.einsum("brhd,bsd->brhs", qi, ki), 0.0))


def select_block(scores, first, topk: int):
    """scores [B,R,S] of the queries at positions ``first`` .. -> bool
    [B,R,S]: every s <= t while t + 1 <= topk, else the topk largest, ties
    to the lower s (a stable sort of the negated scores)."""
    b, r, s = scores.shape
    t = first + jnp.arange(r)[:, None]
    seen = jnp.arange(s)[None, :] <= t
    order = jnp.argsort(-jnp.where(seen[None], scores + 0.0, -jnp.inf),
                        axis=-1, stable=True)[..., :min(topk, s)]
    taken = jnp.zeros((b, r, s), bool).at[
        jnp.arange(b)[:, None, None], jnp.arange(r)[None, :, None],
        order].set(True)
    return jnp.logical_and(taken, seen[None])


def attend_block(q, k, v, taken, scale):
    """q, k [B,.,H,D], v [B,S,H,V], taken [B,R,S] -> [B,R,H,V]."""
    scores = jnp.einsum("brhd,bshd->bhrs", q, k) * scale
    scores = jnp.where(taken[:, None], scores, -jnp.inf)
    return jnp.einsum("bhrs,bshv->brhv", jax.nn.softmax(scores, axis=-1), v)


def index_source(cq, h):
    """What the index queries are projected from: the QUERY'S BOTTLENECK
    after its norm, not the layer's input."""
    return cq


def index_rope_dim(hp) -> int:
    """The values of an index head (and of the index key) that turn: the
    first ``qk_rope_head_dim``, not the whole head."""
    return hp["qk_rope_head_dim"]


def turned(y, n: int, hp):
    """y [B,S,H,D] with its first ``n`` values rotated, the rest as they
    are."""
    inv_freq, factor, _ = yarn(n, hp)
    return jnp.concatenate([rotate(y[..., :n], inv_freq, factor), y[..., n:]],
                           axis=-1)


def project(x, w, hp):
    """Steps 1 and 2 of a layer but the heads' own projections: (the query's
    bottleneck cq [B,S,rq], the latent c [B,S,r], the shared rotated key kr
    [B,S,1,e], the index queries qI [B,S,Hi,Di], the index keys kI [B,S,Di],
    the index heads' weights [B,S,Hi])."""
    eps, r, e = hp["rms_norm_eps"], hp["kv_lora_rank"], hp["qk_rope_head_dim"]
    heads, dim = hp["index_n_heads"], hp["index_head_dim"]
    a = w["attn"]
    h = rms_norm(x, w["ln1"]["scale"], eps)
    cq = rms_norm(h @ a["wq_a"], a["q_a_norm"], eps)
    ckr = h @ a["wkv_a"]
    c = rms_norm(ckr[..., :r], a["kv_norm"], eps)
    kr = turned(ckr[..., None, r:], e, hp)
    n_rot = index_rope_dim(hp)
    qi = turned(jnp.einsum("bsr,rhk->bshk", index_source(cq, h), a["wi_q"]),
                n_rot, hp)
    ki = turned(layer_norm(h @ a["wi_k"], a["ik_scale"], a["ik_bias"],
                           eps)[:, :, None], n_rot, hp)[:, :, 0]
    return cq, c, kr, qi, ki, (h @ a["wi_w"]) * heads ** -0.5 * dim ** -0.5


def attend_heads(cq, c, kr, taken, wq_b, wkv_b, wo, hp):
    """Steps 1 (a group of heads' own part) and 4 for that group: its
    queries out of cq, its keys and values REBUILT from every token's
    latent, attention over the tokens ``taken`` [B,S,S] a block of query
    rows at a time, and the group's part of ``Wo``'s product [B,S,d]."""
    n, e = hp["qk_nope_head_dim"], hp["qk_rope_head_dim"]
    q = jnp.einsum("bsr,rhk->bshk", cq, wq_b)
    q = jnp.concatenate([q[..., :n], turned(q[..., n:], e, hp)], axis=-1)
    kv = jnp.einsum("bsr,rhk->bshk", c, wkv_b)                  # [B,S,G,n+v]
    k = jnp.concatenate([kv[..., :n], jnp.broadcast_to(
        kr, kv.shape[:3] + kr.shape[3:])], axis=-1)
    scale = (n + e) ** -0.5 * yarn(e, hp)[2]
    outs = [attend_block(q[:, lo:lo + QUERY_ROWS], k, kv[..., n:],
                         taken[:, lo:lo + QUERY_ROWS], scale)
            for lo in range(0, q.shape[1], QUERY_ROWS)]
    return jnp.einsum("bshv,hvd->bsd", jnp.concatenate(outs, axis=1), wo)


def make_mix(hp):
    """``mix(x, w, selected)`` -> (x after attention, the feed-forward's
    input h2, the tokens every query attended, bool [B,S,S]): steps 1 to 4
    of a layer, the heads ``HEADS`` at a time so that no more than a group's
    rebuilt keys and values and its three matrices in float32 exist at
    once."""
    eps, topk = hp["rms_norm_eps"], hp["index_topk"]
    prepare = jax.jit(lambda x, w: project(x, common.to_f32(w), hp))
    pick = jax.jit(lambda qi, wt, ki, lo: select_block(
        score_block(qi, wt, ki), lo, topk))
    group = jax.jit(lambda cq, c, kr, taken, *ws: attend_heads(
        cq, c, kr, taken, *common.to_f32(ws), hp))
    norm2 = jax.jit(lambda x, scale: rms_norm(x, scale.astype(F32), eps))

    def mix(x, w, selected):
        small = {"ln1": w["ln1"], "attn": {k: v for k, v in w["attn"].items()
                                           if k not in ("wq_b", "wkv_b",
                                                        "wo")}}
        cq, c, kr, qi, ki, wt = prepare(x, small)
        s = x.shape[1]
        if selected is None:
            taken = jnp.concatenate([
                pick(qi[:, lo:lo + QUERY_ROWS], wt[:, lo:lo + QUERY_ROWS],
                     ki, jnp.int32(lo)) for lo in range(0, s, QUERY_ROWS)],
                axis=1)
        else:
            taken = selected[:, :s, :s]
        del qi, ki, wt
        a = w["attn"]
        for lo in range(0, a["wo"].shape[0], HEADS):
            heads = slice(lo, lo + HEADS)
            x = x + group(cq, c, kr, taken, a["wq_b"][:, heads],
                          a["wkv_b"][:, heads], a["wo"][heads])
        return x, norm2(x, w["ln2"]["scale"]), taken

    return mix


def group_scores(by_group):
    """by_group [..., groups, size] -> a group's score: the sum of its TWO
    largest biased scores."""
    return -jnp.sort(-by_group, axis=-1)[..., :2].sum(-1)


def choose(scores, bias, hp):
    """The k experts [B,S,k] a token takes by its biased sigmoid scores
    [B,S,E], group-limited, best first."""
    biased = scores + bias
    groups, kept = int(hp.get("n_group") or 1), int(hp.get("topk_group") or 1)
    if groups > 1:
        by_group = biased.reshape(*biased.shape[:-1], groups, -1)
        best = jnp.argsort(-group_scores(by_group), axis=-1,
                           stable=True)[..., :kept]
        keep = (best[..., None] == jnp.arange(groups)).any(axis=-2)
        biased = jnp.where(jnp.repeat(keep, by_group.shape[-1], axis=-1),
                           biased, -jnp.inf)
    return jnp.argsort(-biased, axis=-1, stable=True)[
        ..., :hp["num_experts_per_tok"]]


def token_weights(scores, routes, hp):
    """[B,S,E]: a token's weight for every expert the router scores: for the
    k it takes (``routes``) its unbiased score over their sum (+1e-20),
    times the scaling factor; 0 for the others."""
    taken = jax.nn.one_hot(routes, scores.shape[-1], dtype=F32).sum(-2)
    weights = scores * taken
    if hp["norm_topk_prob"]:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return weights * hp["routed_scaling_factor"]


def held_experts(h, weights, mlp, where, first: int):
    """sum over the HELD experts e of (e's SwiGLU of every token) x (the
    token's weight for expert ``first + e``): one expert at a time, ONE of
    its matrices in float32 at a time."""
    cast = jax.jit(lambda m: m.astype(F32))
    up = jax.jit(lambda h, wg, wu: jax.nn.silu(h @ wg) * (h @ wu))
    down = jax.jit(lambda y, hidden, wd, wt: y + (hidden @ wd) * wt[..., None])
    y = jnp.zeros_like(h)
    for e in range(mlp["w_gate"].shape[len(where)]):
        at = where + (e,)
        hidden = up(h, cast(mlp["w_gate"][at]), cast(mlp["w_up"][at]))
        y = down(y, hidden, cast(mlp["w_down"][at]), weights[..., first + e])
    return y


def swiglu(h, mlp, where, names=("w_gate", "w_up", "w_down")):
    """A dense SwiGLU of the layer at ``where`` of its stack, a block of
    hidden columns at a time (a leading layer's three matrices are 0.5 GB
    each in float32)."""
    gate, up, down = (mlp[k] for k in names)
    block = jax.jit(lambda h, g, u, d: (
        jax.nn.silu(h @ g.astype(F32)) * (h @ u.astype(F32))) @ d.astype(F32))
    y = jnp.zeros_like(h)
    for lo in range(0, gate.shape[-1], FF_COLUMNS):
        cols = slice(lo, lo + FF_COLUMNS)
        y = y + block(h, gate[where + (slice(None), cols)],
                      up[where + (slice(None), cols)], down[where + (cols,)])
    return y


def first_held(hp) -> int:
    """The first expert ``params`` holds (their count is the stacks' own):
    0 unless the file says otherwise."""
    return int(hp.get("experts_held_first", 0))


def expert_layer(h, mlp, where, hp, routes=None):
    """Step 5 of an expert layer on its input h [B,S,d], the layer at
    ``where`` of the stack ``mlp``: (what is added to the residual — this
    share's part of the routed sum plus the shared expert —, the experts
    each token took [B,S,k])."""
    scores = jax.nn.sigmoid(h @ mlp["w_router"][where].astype(F32))
    if routes is None:
        routes = choose(scores, mlp["e_bias"][where].astype(F32), hp)
    y = held_experts(h, token_weights(scores, routes, hp), mlp, where,
                     first_held(hp))
    if hp["n_shared_experts"]:
        y = y + swiglu(h, mlp, where, ("ws_gate", "ws_up", "ws_down"))
    return y, routes


def _run(params, tokens, hp, routes, selected, keep_choice):
    """(final hidden state [B,S,d] after the last norm, the experts taken
    [L,B,S,k], and with ``keep_choice`` the tokens attended [L,B,S,S])."""
    eps, dense = hp["rms_norm_eps"], hp["first_k_dense_replace"]
    mix = make_mix(hp)
    x = params["embed"]["table"][tokens].astype(F32)
    all_routes, all_taken = [], []
    for i in range(hp["num_hidden_layers"]):
        w, mlp, where = layer_of(params["blocks"], i, dense)
        x, h, taken = mix(
            x, w, None if selected is None else jnp.asarray(selected[i]))
        if keep_choice:
            all_taken.append(np.asarray(taken))
        del taken, w
        if i < dense:
            x = x + swiglu(h, mlp, where)
            continue
        y, took = expert_layer(
            h, mlp, where, hp,
            None if routes is None else jnp.asarray(routes[i - dense]))
        x = x + y
        all_routes.append(took)
    x = rms_norm(x, params["final_norm"]["scale"].astype(F32), eps)
    return x, jnp.stack(all_routes), all_taken


def _head(params, x) -> np.ndarray:
    """x [B,S,d] -> logits [B,S,V] on the host, a block of rows and of the
    vocabulary at a time."""
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
def forward(params, tokens, hp, routes=None, selected=None):
    """tokens [B,S] int32 -> logits [B,S,V] float32, a host array.
    ``routes``: None (each token takes the experts this reference's rule
    picks) or int [L,B,S,k], the experts each token is given, a row an
    expert layer. ``selected``: None (each query attends the tokens this
    reference's indexer scores highest) or bool [L,B,S,>=S], the tokens each
    query is given."""
    return _head(params, _run(params, tokens, hp, routes, selected,
                              False)[0])


@common.highest
def forward_and_choices(params, tokens, hp, routes=None, selected=None):
    """``forward``, the experts every token took, int [L,B,S,k], and the
    tokens every query attended, bool [L,B,S,S]."""
    x, took, taken = _run(params, tokens, hp, routes, selected, True)
    return _head(params, x), np.asarray(took), np.stack(taken)
