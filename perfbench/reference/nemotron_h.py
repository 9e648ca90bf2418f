"""Plain reference of NVIDIA-Nemotron-3-Nano-30B-A3B's forward pass
(nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, ``config.json``: ``model_type``
``nemotron_h``): a stack of layers that are ONE pre-norm sublayer each,
``x <- x + f(RMSNorm(x))``, with ``f`` what the layer's symbol in
``hybrid_override_pattern`` says; a final RMSNorm and an untied head; the
embedding is not scaled. With ``u = RMSNorm(x)`` [S, d] and the sizes under
their keys (``mamba_num_heads`` H, ``mamba_head_dim`` P, ``n_groups`` G,
``ssm_state_size`` N, ``conv_kernel`` K):

  'M'  Mamba-2. [z | xBC | dt] = u W_in, widths H P | H P + 2 G N | H.
       xBC = silu(conv(xBC)): a causal depthwise convolution of K taps a
       channel with bias, token t sees its own input and the K - 1 before
       it (zeros before the first). xBC -> x [H, P], B [G, N], C [G, N]; head
       h reads group h // (H / G). dt = softplus(dt + dt_bias) [H],
       A = -exp(A_log) [H]. The state of head h, [P, N], from zero:
         S_t = exp(dt_t A) S_(t-1) + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t
       TOKEN BY TOKEN (a ``lax.scan`` over the sequence, whatever its
       length: the state is 2 MB and nothing of the past is kept, so the
       long check fits as it is and no blocked form exists here).
       y = y * silu(z)   (the gate FIRST), then an RMSNorm with weight over
       each group of H P / G values, then W_out.
  '*'  attention. q = u W_q [H_q, D], k, v = u W_k, u W_v [H_kv, D]; causal
       softmax of q . k / sqrt(D), a K/V head shared by H_q / H_kv query
       heads; W_o. NO positional rotation, no bias, no q/k norm, no window.
  'E'  experts. s = sigmoid(u W_r) [E] float32; E_t = the k experts of
       largest s + b (``e_score_correction_bias``; ``n_group`` 1 and
       ``topk_group`` 1: the group step is the identity); w_e = s_e /
       (sum_{E_t} s + 1e-20) times ``routed_scaling_factor``;
       f(u) = sum_{e in E_t} w_e W_down,e relu(u W_up,e)^2
              + W_down,s relu(u W_up,s)^2   (the shared expert, unweighted)
  logits = RMSNorm(x; final) W_out

THE CHIP'S SHARE. Where the weight tree holds fewer experts than the router
scores (``num_experts`` of ``n_routed_experts``, from ``experts_held_first``
on: the chip's share of a layer that lies over several), the weights are
still those over ALL k chosen, and the sum runs over the chosen experts that
are HELD: what the absent experts would add is left out, here as in the
program, and that partial result goes on to the next layer.

Everything is float32; every held expert is computed densely for all tokens
and masked by the token's weight for it.

Departures and assumptions (the configuration file lists the same): no
rotation in attention (the public ``nemotron_h`` modelling code applies
none; ``rope_theta`` and ``partial_rotary_factor`` are in the config and
unused); ties in the top k go to the lower index (``jax.lax.top_k``); the
1e-20 under the renormalization (DeepSeek-V3's public code's, as in
``glm_moe_lite.py``); the bias's, the convolution's, ``dt_bias``'s and
``A_log``'s values are the program's seeded ones.

Top-k is discontinuous, so ``forward`` takes ``routes`` (the experts another
implementation chose, int [L, B, S, k], a row an EXPERT layer): it computes
ITS OWN scores and weighs the experts it is GIVEN by them.

``hp`` is the configuration file's object; ``params`` is the system's
weight tree, read by layout only: ``embed.table [V, d]``; a layer holds what
it has — an 'M' layer ``ln1.scale`` and ``attn.{w_in [d, 2 H P + 2 G N + H],
conv_w [K, H P + 2 G N], conv_b, dt_bias [H], a_log [H], d_skip [H], norm [H
P], w_out [H P, d]}``, a '*' layer ``ln1.scale`` and ``attn.{wq [d, H_q, D],
wk, wv [d, H_kv, D], wo [H_q, D, d]}``, an 'E' layer ``ln2.scale`` and
``mlp.{w_router [d, E], e_bias [E], w_up_t [held, f, d] (the up-projection,
hidden-major), w_down [held, f, d], ws_up [d, fs], ws_down [fs, d]}`` —,
``final_norm.scale``, ``lm_head.kernel [d, V]``; the layers kept apart
(``blocks["0"]`` ...) or stacked by their place in the pattern's period
(``blocks["p3"]``: ``layer_of``). The logits come back as a HOST array, the
head computed in blocks of rows and of the vocabulary.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import common
from perfbench.reference.glm_moe_lite import token_weights
from perfbench.reference.mistral import rms_norm

F32 = common.F32
HEAD_ROWS = 512       # rows of one block of the head
HEAD_COLUMNS = 16384  # columns of the vocabulary of one block of it


def layer_of(blocks, i: int):
    """(layer i's weights but an expert layer's experts, sliced off whatever
    stack holds them; the experts' stack as it is, None for a layer without;
    where in it the layer's experts start: ``()`` or ``(index,)``)."""
    if str(i) in blocks:
        block, where = blocks[str(i)], ()
    else:
        period = sum(1 for name in blocks if name.startswith("p"))
        block, where = blocks[f"p{i % period}"], (i // period,)
    stacked = ("w_up_t", "w_down")
    w = {part: ({k: a for k, a in leaves.items() if k not in stacked}
                if part == "mlp" else leaves)
         for part, leaves in block.items()}
    if where:
        w = jax.tree.map(lambda a: a[where[0]], w)
    return w, block.get("mlp"), where


def causal_conv(x, weight, bias):
    """x [B, S, W] -> silu of the depthwise causal convolution: token t sees
    its own input (the last tap) and the K - 1 before it, zeros before the
    first; weight [K, W], bias [W]."""
    taps, s = weight.shape[0], x.shape[1]
    before = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(weight[k] * before[:, k:k + s]
                           for k in range(taps)) + bias)


def gated_norm(y, z, scale, groups: int, eps):
    """The gate FIRST, then an RMSNorm with weight over each of ``groups``
    runs of the width."""
    y = y * jax.nn.silu(z)
    normed = rms_norm(y.reshape(*y.shape[:2], groups, -1),
                      scale.reshape(groups, -1), eps)
    return normed.reshape(y.shape)


def mamba(x, w, hp):
    """An 'M' layer: x [B, S, d] -> x + the Mamba-2 mixer of RMSNorm(x)."""
    a = w["attn"]
    b, s, _ = x.shape
    H, P = hp["mamba_num_heads"], hp["mamba_head_dim"]
    G, N = hp["n_groups"], hp["ssm_state_size"]
    inner = H * P
    u = rms_norm(x, w["ln1"]["scale"], hp["norm_eps"])
    zxd = u @ a["w_in"]
    z, xbc, dt = (zxd[..., :inner], zxd[..., inner:2 * inner + 2 * G * N],
                  zxd[..., 2 * inner + 2 * G * N:])
    xbc = causal_conv(xbc, a["conv_w"], a["conv_b"])
    xs = xbc[..., :inner].reshape(b, s, H, P)
    # head h reads group h // (H / G)
    bm, cm = (jnp.repeat(xbc[..., inner + j * G * N:inner + (j + 1) * G * N]
                         .reshape(b, s, G, N), H // G, axis=2)
              for j in range(2))
    dt = jax.nn.softplus(dt + a["dt_bias"])                       # [B,S,H]
    decay = jnp.exp(dt * -jnp.exp(a["a_log"]))

    def token(state, t):
        x_t, b_t, c_t, dt_t, decay_t = t
        state = (decay_t[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None])
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    _, y = jax.lax.scan(token, jnp.zeros((b, H, P, N), F32), tuple(
        jnp.moveaxis(t, 1, 0) for t in (xs, bm, cm, dt, decay)))
    y = jnp.moveaxis(y, 0, 1) + a["d_skip"][:, None] * xs
    y = gated_norm(y.reshape(b, s, inner), z, a["norm"], G, hp["norm_eps"])
    return x + y @ a["w_out"]


def attention(x, w, hp):
    """A '*' layer: plain grouped causal attention, nothing rotated."""
    a = w["attn"]
    u = rms_norm(x, w["ln1"]["scale"], hp["norm_eps"])
    q, k, v = (jnp.einsum("bsd,dhk->bshk", u, a[name])
               for name in ("wq", "wk", "wv"))
    return x + jnp.einsum("bshk,hkd->bsd",
                          common.causal_attention(q, k, v), a["wo"])


def relu2(h, up, down):
    return jnp.square(jax.nn.relu(h @ up)) @ down


def held_experts(h, weights, mlp, where, first: int):
    """sum over the experts e HELD in ``mlp``'s stack of (e's output for
    every token) x (the token's weight for expert ``first + e``, 0 where it
    did not take it): one expert at a time, its two matrices sliced off the
    system's stack and cast to float32 inside the loop."""
    def add(e, y):
        up, down = (mlp[name][where + (e,)].astype(F32)
                    for name in ("w_up_t", "w_down"))
        return y + relu2(h, up.T, down) * jnp.take(
            weights, first + e, axis=-1)[..., None]

    return jax.lax.fori_loop(0, mlp["w_down"].shape[len(where)], add,
                             jnp.zeros_like(h))


_KEYS = ("hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim",
         "n_groups", "ssm_state_size", "norm_eps", "num_experts_per_tok",
         "norm_topk_prob", "routed_scaling_factor", "n_shared_experts",
         "experts_held_first")


@functools.lru_cache(maxsize=8)
def _pieces(sizes):
    """The jitted pieces of ``_run`` for one configuration (``sizes``: its
    ``_KEYS`` as a tuple), made once so that a second call at the same
    shapes compiles nothing."""
    hp = dict(zip(_KEYS, sizes))
    eps = hp["norm_eps"]
    return {
        "M": jax.jit(lambda x, w: mamba(x, common.to_f32(w), hp)),
        "*": jax.jit(lambda x, w: attention(x, common.to_f32(w), hp)),
        "norm": jax.jit(lambda x, scale: rms_norm(x, scale.astype(F32), eps)),
        "score": jax.jit(lambda h, w: jax.nn.sigmoid(h @ w.astype(F32))),
        "weigh": jax.jit(lambda s, b, r: token_weights(s, b.astype(F32), r,
                                                       hp)),
        "shared": jax.jit(lambda h, m: relu2(h, m["ws_up"].astype(F32),
                                             m["ws_down"].astype(F32))),
        "feed": jax.jit(held_experts, static_argnums=(4,)),
    }


def _run(params, tokens, hp, routes):
    """(final hidden state [B,S,d] after the last norm, the routers' scores
    [L,B,S,E], a row an expert layer)."""
    first = int(hp.get("experts_held_first", 0))
    f = _pieces(tuple(hp.get(key, 0) for key in _KEYS))
    x = params["embed"]["table"][tokens].astype(F32)
    all_scores = []
    for i, symbol in enumerate(hp["hybrid_override_pattern"]):
        w, stack, where = layer_of(params["blocks"], i)
        if symbol in ("M", "*"):
            x = f[symbol](x, w)
            continue
        if symbol != "E":
            raise ValueError(f"no layer {symbol!r} in this reference")
        h = f["norm"](x, w["ln2"]["scale"])
        scores = f["score"](h, w["mlp"]["w_router"])
        weights = f["weigh"](scores, w["mlp"]["e_bias"],
                             None if routes is None
                             else routes[len(all_scores)])
        x = x + f["feed"](h, weights, stack,
                          tuple(jnp.int32(j) for j in where), first)
        if hp["n_shared_experts"]:
            x = x + f["shared"](h, w["mlp"])
        all_scores.append(scores)
    return f["norm"](x, params["final_norm"]["scale"]), jnp.stack(all_scores)


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
