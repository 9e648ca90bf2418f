"""Plain reference of the Brumby-14B-Base forward pass (manifestai/
Brumby-14B-Base, ``config.json``: ``model_type`` ``brumby``; the dense
Qwen3-shaped block it was retrained from, every layer's softmax attention
replaced by power retention): pre-norm blocks of RMSNorm, a power-retention
mixer over grouped keys and values, a SwiGLU feed-forward, final RMSNorm,
untied output head. ``d`` is a head's width (128), ``g(h) = h // (H / G)``
the K/V head of query head ``h``, RMS an RMSNorm with ``rms_norm_eps``, all
sums causal.

    q_t^h = RoPE(RMS_head(x_t Wq^h))   k_t^g = RoPE(RMS_head(x_t Wk^g))   v_t^g = x_t Wv^g
    c_t^g = log sigmoid(x_t Wc^g)                       one log-gate a K/V head
    a_tj^h = (q_t^h . k_j^g(h) / sqrt(d))^2 * exp(c_(j+1) + ... + c_t)       j <= t
    y_t^h  = sum_j a_tj^h v_j / (sum_j a_tj^h + eps)        o_t = concat_h(y_t^h) Wo

computed in THIS form, token against token in blocks of query rows: no
state, no chunks, no kernel. (The system serves the same function as a
recurrence on the symmetric half of ``k k^T``, 8256 rows a K/V head:
``ray_tpu/ops/power_retention.py``. Nothing here imports it.)

Assumed (``config.json`` has no key for any of it; the configuration file
lists the same): degree 2; one gate a K/V head from a bias-free projection
of the layer's normalised input, ``log sigmoid``, float32; the output
normalised by the sum of its weights plus ``retention_eps``; the per-head
q/k RMSNorm (one scale of ``d`` each, shared by the heads) and RoPE kept from
the dense block; no output gate or norm. Departures: everything is float32;
the public inference code keeps keys and values up to a switch-over length
and folds them into the state later, which is the same function.

``hp`` is the configuration file's object; ``params`` is the system's weight
tree, read by layout only: ``embed.table [V, d]``, ``blocks.{attn.wq [L, d,
H, D], attn.wk, attn.wv [L, d, G, D], attn.wc [L, d, G], attn.wo [L, H, D,
d], attn.q_norm, attn.k_norm [L, D], ln1.scale, ln2.scale [L, d],
mlp.w_gate, mlp.w_up [L, d, f], mlp.w_down [L, f, d]}``,
``final_norm.scale``, ``lm_head.kernel [d, V]``. The logits come back as a
host array, the head computed in blocks of rows and of columns: at 8.7k
tokens they are 5.3 GB, and the head's own float32 copy would be 3.1 GB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import common
from perfbench.reference.minicpm_sala import rotate
from perfbench.reference.mistral import rms_norm

F32 = common.F32
QUERY_ROWS = 64    # query rows of one block of the mixer: [64, H, S] at once
MLP_COLUMNS = 8    # blocks the feed-forward's width is cut into
HEAD_ROWS = 1024   # rows and column blocks of the head
HEAD_COLUMNS = 8


def row_blocks(fn, rows: int, *xs):
    """``fn(block of each x's rows)`` over blocks of ``rows`` rows."""
    n = xs[0].shape[0]
    pad = -n % rows
    cut = lambda x: jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)
                            ).reshape((n + pad) // rows, rows, *x.shape[1:])
    out = jax.lax.map(lambda b: fn(*b), tuple(cut(x) for x in xs))
    return out.reshape(n + pad, *out.shape[2:])[:n]


def retention(h, w, hp):
    """One sequence, h [S, d] -> [S, d]: the attention form."""
    eps, theta = hp["rms_norm_eps"], hp["rope_theta"]
    s = h.shape[0]
    w = common.to_f32(w)
    q = jnp.einsum("sd,dhk->shk", h, w["wq"])
    k = jnp.einsum("sd,dhk->shk", h, w["wk"])
    v = jnp.einsum("sd,dhk->shk", h, w["wv"])
    pos = jnp.arange(s)
    q = rotate(rms_norm(q, w["q_norm"], eps), pos, theta)
    k = rotate(rms_norm(k, w["k_norm"], eps), pos, theta)
    d, group = q.shape[-1], q.shape[1] // k.shape[1]
    # the gates' running sum: c_1 + ... + c_t, a K/V head [S, G]
    run = jnp.cumsum(jax.nn.log_sigmoid(h @ w["wc"]), axis=0)
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    run_h = jnp.repeat(run, group, axis=1)                          # [S, H]

    def rows(qb, tb, rb):
        score = jnp.einsum("rhd,khd->rhk", qb, k) / jnp.sqrt(F32(d))
        seen = pos[None, None, :] <= tb[:, None, None]
        decay = jnp.exp(jnp.where(seen, rb[:, :, None] - run_h.T[None],
                                  -jnp.inf))
        a = score * score * decay                                # [R, H, S]
        return (jnp.einsum("rhk,khd->rhd", a, v)
                / (a.sum(-1, keepdims=True) + hp["retention_eps"]))

    y = row_blocks(rows, QUERY_ROWS, q, pos, run_h)
    return jnp.einsum("shk,hkd->sd", y, w["wo"])


def swiglu(h, w):
    """h [S, d] -> [S, d], a block of the width's columns at a time, each
    cast to float32 on its own: a layer's feed-forward is 1.07 GB there."""
    f = w["w_gate"].shape[1]
    cols = f // MLP_COLUMNS

    def block(acc, c):
        cut = lambda m, axis: jax.lax.dynamic_slice_in_dim(
            m, c * cols, cols, axis).astype(F32)
        gate = jax.nn.silu(h @ cut(w["w_gate"], 1)) * (h @ cut(w["w_up"], 1))
        return acc + gate @ cut(w["w_down"], 0), None

    return jax.lax.scan(block, jnp.zeros_like(h), jnp.arange(MLP_COLUMNS))[0]


def layer(x, w, n, hp):
    """x [S, d] -> x; ``w`` the stacked layers, ``n`` the one to apply
    (sliced in here, so that no copy of a layer outlives its step)."""
    w = jax.tree.map(lambda a: a[n], w)
    eps = hp["rms_norm_eps"]
    x = x + retention(rms_norm(x, w["ln1"]["scale"].astype(F32), eps),
                      w["attn"], hp)
    h = rms_norm(x, w["ln2"]["scale"].astype(F32), eps)
    return x + row_blocks(lambda b: swiglu(b, w["mlp"]), HEAD_ROWS, h)


@functools.partial(jax.jit, static_argnames=("cols",))
def head(x, kernel, lo, cols):
    return x @ jax.lax.dynamic_slice_in_dim(kernel, lo, cols, 1).astype(F32)


def logits_on_the_host(x, params, hp):
    """x [S, d] -> logits [S, V], a host array."""
    x = rms_norm(x, params["final_norm"]["scale"].astype(F32),
                 hp["rms_norm_eps"])
    kernel = params["lm_head"]["kernel"]
    vocab = kernel.shape[1]
    cols = -(-vocab // HEAD_COLUMNS)
    out = np.empty((x.shape[0], vocab), np.float32)
    for r in range(0, x.shape[0], HEAD_ROWS):
        for c in range(HEAD_COLUMNS):
            lo = min(c * cols, vocab - cols)  # the last block is whole too
            out[r:r + HEAD_ROWS, lo:lo + cols] = np.asarray(
                head(x[r:r + HEAD_ROWS], kernel, lo, cols))
    return out


@common.highest
def forward(params, tokens, hp):
    """tokens [B, S] int32 -> logits [B, S, V] float32, a host array."""
    step = jax.jit(functools.partial(layer, hp=hp))
    logits = []
    for b in range(tokens.shape[0]):
        x = params["embed"]["table"][tokens[b]].astype(F32)
        for n in range(hp["num_hidden_layers"]):
            x = step(x, params["blocks"], jnp.int32(n))
        logits.append(logits_on_the_host(x, params, hp))
    return np.stack(logits)
