"""Plain reference of the MiniCPM-SALA forward pass (openbmb/MiniCPM-SALA,
``config.json``: ``mixer_types`` of ``minicpm4`` and ``lightning-attn``
layers, the MiniCPM scales): pre-norm blocks whose mixer is one of two
kinds, a SwiGLU feed-forward, final RMSNorm, untied output head. ``d`` is a
head's width, RMS an RMSNorm with ``rms_norm_eps``.

    h0 = scale_emb * embed[ids]
    h  = h + r * mixer(RMS(h; ln1))        r = scale_depth / sqrt(published depth)
    h  = h + r * swiglu(RMS(h; ln2))
    logits = (RMS(h; final) / (hidden_size / dim_model_base)) Wout

``lightning-attn`` (decayed linear attention, token by token):
    q, k, v = x Wq, x Wk, x Wv            (lightning_nh heads each)
    q, k = RoPE(RMS_head(q)), RoPE(RMS_head(k))
    S_t = l_h S_(t-1) + k_t^T v_t         o_t = q_t S_t / sqrt(d)
    y = (RMS(o; o_norm) * sigmoid(x Wg)) Wo
with ``l_h = exp(-2^(-e (h + 1) / H))``, ``e`` the file's
``lightning_slope_exponent``.

``minicpm4`` (InfLLM-v2 block-selected attention, no RoPE):
    q (H heads), k, v (Hkv heads); q, k = RMS_head(q), RMS_head(k)
    kc_j = mean(k[stride j : stride j + kernel])           pooled keys
    p(h, j) = softmax_j(q_h . kc_j / sqrt(d)) over the j with stride j + kernel - 1 <= t
    score(g, b) = max over the pooled keys that overlap block b of sum_{h in group g} p(h, j)
    chosen = first init_blocks blocks, blocks of the last window_size tokens,
             topk best of the others;  every block if t + 1 <= dense_len
    o = causal softmax attention over the tokens of the chosen blocks
    y = (o * sigmoid(x Wg)) Wo
computed densely and then masked: every query scores every key.

Assumed (``config.json`` does not say; the configuration file lists the
same): the decay rule (the Lightning-Attention family's slopes); the
``sparse_config`` sizes (the family's published ones); ``qk_norm`` as one
scale of ``d`` shared by a kind's heads; the output norm over all H * d
values of ``o``; a pooled key is seen by the query at ``t`` once all its
tokens lie at or before ``t``; a tie in the top-k goes to the earlier block.
Departures: everything is float32.

Top-k is discontinuous: ``forward`` takes ``selected`` (the blocks another
implementation chose, bool [sparse layers, B, S, Hkv, >= NB]) and attends
THOSE; ``return_selected`` hands back this reference's own choice and the
scores it was made from.

``hp`` is the configuration file's object; ``params`` is the system's weight
tree, read by layout only: ``embed.table [V, d]``, a layer's ``attn.{wq, wg
[d, H, D], wk, wv [d, Hkv | H, D], wo [H, D, d], q_norm, k_norm [D], o_norm
[H * D] (lightning)}``, ``ln1.scale``, ``ln2.scale``, ``mlp.{w_gate, w_up
[d, f], w_down [f, d]}``, ``final_norm.scale``, ``lm_head.kernel [d, V]``;
layers kept apart as ``blocks["0"]``, ... or stacked by their place in the
pattern's period as ``blocks["p0"]``, ... (the system has both layouts).
The logits come back as a host array, the head computed in row blocks: at
9k tokens they are 2.7 GB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import common
from perfbench.reference.mistral import rms_norm

F32 = common.F32
ROWS = 256  # rows of one block wherever the work is done in row blocks


def in_row_blocks(fn, x, *rest):
    """``fn(block of x's rows, *rest)`` over blocks of ROWS rows."""
    n = x.shape[0]
    pad = -n % ROWS
    blocks = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
        (n + pad) // ROWS, ROWS, *x.shape[1:])
    out = jax.lax.map(lambda b: fn(b, *rest), blocks)
    return jax.tree.map(lambda o: o.reshape(n + pad, *o.shape[2:])[:n], out)


def rotate(x, positions, theta):
    """x [S, H, D]: rotate pairs (i, i + D/2) by position * theta^(-2i/D)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angle = positions.astype(F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[:, None], jnp.sin(angle)[:, None]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def swiglu(h, w):
    return (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def projections(h, w, eps):
    q = jnp.einsum("sd,dhk->shk", h, w["wq"])
    k = jnp.einsum("sd,dhk->shk", h, w["wk"])
    v = jnp.einsum("sd,dhk->shk", h, w["wv"])
    gate = jax.nn.sigmoid(jnp.einsum("sd,dhk->shk", h, w["wg"]))
    return (rms_norm(q, w["q_norm"], eps), rms_norm(k, w["k_norm"], eps), v,
            gate)


def lightning(h, w, hp):
    """One sequence, h [S, d] -> [S, d], token by token."""
    eps = hp["rms_norm_eps"]
    s, heads = h.shape[0], w["wq"].shape[1]
    q, k, v, gate = projections(h, w, eps)
    pos = jnp.arange(s)
    q, k = rotate(q, pos, hp["rope_theta"]), rotate(k, pos, hp["rope_theta"])
    d = q.shape[-1]
    slope = jnp.exp2(-hp["lightning_slope_exponent"]
                     * jnp.arange(1, heads + 1, dtype=F32) / heads)
    decay = jnp.exp(-slope)[:, None, None]

    def step(state, qkv):
        qt, kt, vt = qkv
        state = decay * state + kt[:, :, None] * vt[:, None, :]
        return state, jnp.einsum("hk,hkd->hd", qt, state) / jnp.sqrt(F32(d))

    _, o = jax.lax.scan(step, jnp.zeros((heads, d, d), F32), (q, k, v))
    o = rms_norm(o.reshape(s, -1), w["o_norm"], eps).reshape(o.shape)
    return jnp.einsum("shk,hkd->sd", o * gate, w["wo"])


def pooled_keys(k, sc):
    """k [S, Hkv, D] -> [NP, Hkv, D]: means of kernel_size keys every
    kernel_stride, whole windows only."""
    n = max((k.shape[0] - sc["kernel_size"]) // sc["kernel_stride"] + 1, 0)
    idx = (jnp.arange(n)[:, None] * sc["kernel_stride"]
           + jnp.arange(sc["kernel_size"])[None])
    return k[idx].mean(axis=1)


def choose(q, kc, t, sc, n_blocks):
    """Queries q [R, H, D] at positions t [R] -> (chosen bool [R, Hkv, NB],
    the blocks' scores [R, Hkv, NB], who was a candidate [R, 1, NB])."""
    hkv, d = kc.shape[1], q.shape[-1]
    group = q.shape[1] // hkv
    stride, kernel, block = (sc["kernel_stride"], sc["kernel_size"],
                             sc["block_size"])
    j = jnp.arange(kc.shape[0])
    seen = j[None] * stride + kernel - 1 <= t[:, None]          # [R, NP]
    s = jnp.einsum("rhd,jhd->rhj", q, jnp.repeat(kc, group, axis=1))
    s = jnp.where(seen[:, None], s / jnp.sqrt(F32(d)), -jnp.inf)
    p = jnp.where(seen[:, None], jax.nn.softmax(s, axis=-1), 0.0)
    p = p.reshape(p.shape[0], hkv, group, -1).sum(axis=2)       # [R, Hkv, NP]
    b = jnp.arange(n_blocks)
    overlaps = jnp.logical_and(j[None] * stride < (b[:, None] + 1) * block,
                               j[None] * stride + kernel > b[:, None] * block)
    score = jnp.where(overlaps[None, None], p[:, :, None, :], 0.0).max(-1)
    tt = t[:, None, None]
    seen_b = b <= tt // block
    forced = jnp.logical_and(seen_b, jnp.logical_or(
        b < sc["init_blocks"], b >= (tt - sc["window_size"] + 1) // block))
    cand = jnp.logical_and(seen_b, jnp.logical_not(forced))
    ranked = jnp.where(cand, score, -1.0)
    order = jnp.argsort(-ranked, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    best = jnp.logical_and(rank < sc["topk"], cand)
    chosen = jnp.where(tt + 1 <= sc["dense_len"], seen_b,
                       jnp.logical_or(forced, best))
    return chosen, score, cand


def minicpm4(h, w, hp, selected=None):
    """One sequence, h [S, d] -> ([S, d], (chosen, scores, candidates))."""
    sc, eps = hp["sparse_config"], hp["rms_norm_eps"]
    s = h.shape[0]
    q, k, v, gate = projections(h, w, eps)
    hkv, d = k.shape[1], q.shape[-1]
    group = q.shape[1] // hkv
    n_blocks = -(-s // sc["block_size"])
    kc = pooled_keys(k, sc)
    key_block = jnp.arange(s) // sc["block_size"]

    def rows(args):
        qb, tb, given = args
        chosen, score, cand = choose(qb, kc, tb, sc, n_blocks)
        use = chosen if given is None else given
        att = jnp.einsum("rhd,khd->rhk", qb, jnp.repeat(k, group, axis=1))
        allowed = jnp.logical_and(
            jnp.repeat(use, group, axis=1)[:, :, key_block],
            jnp.arange(s)[None, None] <= tb[:, None, None])
        att = jnp.where(allowed, att / jnp.sqrt(F32(d)), -jnp.inf)
        o = jnp.einsum("rhk,khd->rhd", jax.nn.softmax(att, axis=-1),
                       jnp.repeat(v, group, axis=1))
        return o, chosen, score, cand

    given = None if selected is None else selected[..., :n_blocks]
    pad = -s % ROWS
    cut = lambda a: None if a is None else jnp.pad(
        a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)).reshape(
        (s + pad) // ROWS, ROWS, *a.shape[1:])
    o, chosen, score, cand = jax.lax.map(
        rows, (cut(q), cut(jnp.arange(s)), cut(given)))
    flat = lambda a: a.reshape(s + pad, *a.shape[2:])[:s]
    y = jnp.einsum("shk,hkd->sd", flat(o) * gate, w["wo"])
    return y, (flat(chosen), flat(score), flat(cand))


def layer(x, w, n, selected, hp, kind):
    """x [S, d] -> (x, what a minicpm4 layer chose or None). ``w``: the
    layer's weights, or with ``n`` given a stack of layers of which this is
    the ``n``-th (sliced in here, so that no copy of a layer outlives its
    step beside the weights the system holds)."""
    if n is not None:
        w = jax.tree.map(lambda a: a[n], w)
    w = common.to_f32(w)
    eps = hp["rms_norm_eps"]
    r = hp["scale_depth"] / np.sqrt(hp["published_num_hidden_layers"])
    h = rms_norm(x, w["ln1"]["scale"], eps)
    if kind == "lightning-attn":
        y, picked = lightning(h, w["attn"], hp), None
    else:
        y, picked = minicpm4(h, w["attn"], hp, selected)
    x = x + r * y
    h = rms_norm(x, w["ln2"]["scale"], eps)
    return x + r * in_row_blocks(swiglu, h, w["mlp"]), picked


def layer_weights(params, i: int, period: int):
    """(weights, index in them or None) of layer ``i``."""
    blocks = params["blocks"]
    if "p0" in blocks:
        return blocks[f"p{i % period}"], jnp.int32(i // period)
    return blocks[str(i)], None


HEAD_ROWS = 1024  # rows of one block of the head: [1024, V] leaves at once


@jax.jit
def head(x, kernel):
    return x @ kernel.astype(F32)


def logits_on_the_host(x, params, hp):
    """x [S, d] -> logits [S, V], a host array, a block of rows at a time."""
    x = rms_norm(x, params["final_norm"]["scale"].astype(F32),
                 hp["rms_norm_eps"]) / (hp["hidden_size"]
                                        / hp["dim_model_base"])
    kernel = params["lm_head"]["kernel"]
    return np.concatenate([
        np.asarray(head(x[r:r + HEAD_ROWS], kernel))
        for r in range(0, x.shape[0], HEAD_ROWS)])


@common.highest
def forward(params, tokens, hp, selected=None, return_selected=False):
    """tokens [B, S] int32 -> logits [B, S, V] float32, a host array; with
    ``return_selected`` also, a 'minicpm4' layer, (chosen [B, S, Hkv, NB],
    scores, candidates)."""
    kinds = hp["mixer_types"]
    period = next(p for p in range(1, len(kinds) + 1)
                  if len(kinds) % p == 0
                  and all(k == kinds[i % p] for i, k in enumerate(kinds)))
    steps = {kind: jax.jit(functools.partial(layer, hp=hp, kind=kind))
             for kind in set(kinds)}
    logits, picked = [], []
    for b in range(tokens.shape[0]):
        x = hp["scale_emb"] * params["embed"]["table"][tokens[b]].astype(F32)
        n_sparse = 0
        mine = []
        for i, kind in enumerate(kinds):
            given = None
            if kind == "minicpm4":
                if selected is not None:
                    given = jnp.asarray(selected[n_sparse][b])
                n_sparse += 1
            x, chose = steps[kind](x, *layer_weights(params, i, period),
                                   given)
            if chose is not None:
                mine.append(jax.tree.map(np.asarray, chose))
        picked.append(mine)
        logits.append(logits_on_the_host(x, params, hp))
    out = np.stack(logits)
    if return_selected:
        # [layers][3] of [B, ...]
        return out, [tuple(np.stack([picked[b][l][j]
                                     for b in range(len(picked))])
                           for j in range(3))
                     for l in range(len(picked[0]))]
    return out
