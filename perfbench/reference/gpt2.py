"""Plain reference of the GPT-2 forward pass (Radford et al. 2019, "Language
Models are Unsupervised Multitask Learners"): learned positions, pre-norm
blocks of LayerNorm, multi-head attention and a GELU (tanh approximation,
``gelu_new``) feed-forward, final LayerNorm, output head tied to the token
embedding.

Departure from the published model, noted because the system makes it: the
system's attention projections carry no biases, so there are none here.

``params`` layout read: ``embed.table [V,d]``, ``pos_embed.table [P,d]``,
``blocks.{attn.wq/wk/wv [L,d,H,D], attn.wo [L,H,D,d], ln1/ln2.{scale,bias}
[L,d], mlp.w_in [L,d,f], mlp.b_in [L,f], mlp.w_out [L,f,d], mlp.b_out
[L,d]}``, ``final_norm.{scale,bias}``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference import common

F32 = common.F32


def layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def layer(x, w, eps):
    h = layer_norm(x, w["ln1"], eps)
    q = jnp.einsum("bsd,dhk->bshk", h, w["attn"]["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, w["attn"]["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, w["attn"]["wv"])
    o = common.causal_attention(q, k, v)
    x = x + jnp.einsum("bshk,hkd->bsd", o, w["attn"]["wo"])
    h = layer_norm(x, w["ln2"], eps)
    h = jax.nn.gelu(h @ w["mlp"]["w_in"] + w["mlp"]["b_in"],
                    approximate=True)
    return x + h @ w["mlp"]["w_out"] + w["mlp"]["b_out"]


@common.highest
def forward(params, tokens, hp):
    """tokens [B,S] int32 -> logits [B,S,V] float32."""
    eps = hp["layer_norm_epsilon"]
    table = params["embed"]["table"].astype(F32)
    x = table[tokens] + params["pos_embed"]["table"].astype(F32)[
        jnp.arange(tokens.shape[1])]
    x = common.run_layers(lambda x, w: layer(x, w, eps), x,
                          params["blocks"], hp["n_layer"])
    x = layer_norm(x, common.to_f32(params["final_norm"]), eps)
    return x @ table.T
