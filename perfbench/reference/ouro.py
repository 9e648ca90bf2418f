"""Plain reference of the Ouro forward pass (ByteDance/Ouro-2.6B,
``model_type: ouro``; "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741): a stack of Llama-shaped layers that every token goes
through ``total_ut_steps`` times with the SAME weights.

What the source's ``config.json`` has no key for, taken from the family's
published description and modelling code as the writer knew them (there is
no network here), stated first because the program follows the same reading
and the configuration's file lists each under ``assumed``:

  * a layer norms its sublayers' OUTPUTS too: ``x <- x + RMSNorm_2(Attn(
    RMSNorm_1(x)))``, ``x <- x + RMSNorm_4(SwiGLU(RMSNorm_3(x)))`` — four
    scales of [d] a layer (``ln1``, ``ln1_out``, ``ln2``, ``ln2_out``);
  * q, k and v have no bias; q and k are rotated over the whole head in the
    half-rotation layout (pairs (i, i + D/2)), at the token's position, the
    SAME position in every pass;
  * the keys and values a query of pass t attends are those pass t wrote:
    here every pass is a full causal forward of the stack over that pass's
    own input, which is the same thing without a cache;
  * the final RMSNorm closes EVERY pass and its output is the next pass's
    input: ``h_t = RMSNorm_f(Stack(h_{t-1}))``, ``h_0`` the unscaled
    embedding;
  * a gate of d + 1 parameters reads each pass's normed state, ``g_t =
    sigmoid(h_t . w_g + b_g)``; the share of a token that leaves at pass t
    is ``p_t = g_t prod_{j<t} (1 - g_j)`` for t < T and ``p_T`` the rest;
    the token's exit pass is the first t with ``p_1 + ... + p_t >=
    early_exit_threshold``, else T, and the head projects THAT pass's
    state. All T passes are computed for every token whatever its exit
    (later tokens attend every pass's keys). With the published threshold 1
    the exit is the last pass unless a gate saturates.

Departures from the published code, each because this is a reference and
not a server: no cache and no batching of passes (a Python loop of four
forwards of the stack), float32 throughout under ``highest`` (the source
computes in bf16), a layer's weights sliced off the stack and cast one at a
time (``common.run_layers``: the float32 stack would be 10.7 GB), and
``exit_pass=`` by which another implementation's exits are taken where a
gate sits on the threshold.

``hp`` is the configuration file's object (the source's own keys); ``params``
is the system's weight tree, read for its layout alone: ``embed.table
[V,d]``, ``blocks.{attn.wq, attn.wk, attn.wv [L,d,H,D], attn.wo [L,H,D,d],
ln1.scale, ln1_out.scale, ln2.scale, ln2_out.scale [L,d], mlp.w_gate,
mlp.w_up [L,d,f], mlp.w_down [L,f,d]}``, ``final_norm.scale [d]``,
``exit_gate.{w [d], b []}``, ``lm_head.kernel [d,V]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference import common
from perfbench.reference.mistral import rms_norm, rotate

F32 = common.F32


def layer(x, w, eps, theta):
    h = rms_norm(x, w["ln1"]["scale"], eps)
    q = jnp.einsum("bsd,dhk->bshk", h, w["attn"]["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, w["attn"]["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, w["attn"]["wv"])
    o = common.causal_attention(rotate(q, theta), rotate(k, theta), v)
    a = jnp.einsum("bshk,hkd->bsd", o, w["attn"]["wo"])
    x = x + rms_norm(a, w["ln1_out"]["scale"], eps)
    h = rms_norm(x, w["ln2"]["scale"], eps)
    m = (jax.nn.silu(h @ w["mlp"]["w_gate"]) * (h @ w["mlp"]["w_up"])
         ) @ w["mlp"]["w_down"]
    return x + rms_norm(m, w["ln2_out"]["scale"], eps)


def exit_shares(states, gate):
    """states: the T passes' normed states [B,S,d] -> [p_1 .. p_T], [B,S]
    each."""
    w, b = gate["w"].astype(F32), gate["b"].astype(F32)
    shares, stayed = [], jnp.ones(states[0].shape[:-1], F32)
    for h in states[:-1]:
        g = jax.nn.sigmoid(h @ w + b)
        shares.append(g * stayed)
        stayed = stayed * (1.0 - g)
    return shares + [stayed]


def first_reaching(shares, threshold):
    """The first pass (from 1) whose running sum of shares reaches
    ``threshold``, else the last: int32 [B,S]."""
    last = len(shares)
    exits = jnp.full(shares[0].shape, last, jnp.int32)
    total = jnp.zeros_like(shares[0])
    for t, p in enumerate(shares[:-1], start=1):
        total = total + p  # (the earliest such pass wins)
        exits = jnp.where((total >= threshold) & (exits == last), t, exits)
    return exits


def _run(params, tokens, hp, exit_pass):
    eps, theta = hp["rms_norm_eps"], hp["rope_theta"]
    final = params["final_norm"]["scale"].astype(F32)
    x = params["embed"]["table"][tokens].astype(F32)
    states = []
    for _ in range(hp["total_ut_steps"]):
        x = common.run_layers(lambda x, w: layer(x, w, eps, theta), x,
                              params["blocks"], hp["num_hidden_layers"])
        x = rms_norm(x, final, eps)
        states.append(x)
    shares = exit_shares(states, params["exit_gate"])
    exits = (first_reaching(shares, hp["early_exit_threshold"])
             if exit_pass is None else jnp.asarray(exit_pass, jnp.int32))
    left = sum(jnp.where((exits == t)[..., None], h, 0.0)
               for t, h in enumerate(states, start=1))
    logits = left @ params["lm_head"]["kernel"].astype(F32)
    return logits, exits, jnp.stack(shares)


@common.highest
def forward(params, tokens, hp, exit_pass=None):
    """tokens [B,S] int32 -> logits [B,S,V] float32. ``exit_pass``: None
    (each position leaves where this reference's own gate says) or int
    [B,S], the pass (from 1) each position is given."""
    return _run(params, tokens, hp, exit_pass)[0]


@common.highest
def forward_and_exits(params, tokens, hp, exit_pass=None):
    """``forward``, each position's exit pass int32 [B,S] (from 1) and the
    T exit shares [T,B,S]."""
    return _run(params, tokens, hp, exit_pass)


def loss(params, tokens, hp):
    """Mean next-token cross-entropy of the exit pass's logits."""
    return common.next_token_loss(forward(params, tokens, hp), tokens)
