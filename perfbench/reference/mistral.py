"""Plain reference of the Mistral-7B forward pass (Jiang et al. 2023,
arXiv:2310.06825; the released v0.3 has no sliding window): pre-norm blocks
of RMSNorm, rotary grouped-query attention and a SwiGLU feed-forward, final
RMSNorm, untied output head. Rotary embedding in the half-rotation layout of
the released checkpoints (first half of a head's dimensions paired with the
second half).

``hp`` is the configuration file's object (the source's own keys); ``params``
is the system's weight tree — weights are data, and this file reads only
their layout: ``embed.table [V,d]``, ``blocks.{attn.wq [L,d,H,D], attn.wk,
attn.wv [L,d,Hkv,D], attn.wo [L,H,D,d], ln1.scale, ln2.scale [L,d],
mlp.w_gate, mlp.w_up [L,d,f], mlp.w_down [L,f,d]}``, ``final_norm.scale``,
``lm_head.kernel [d,V]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.reference import common

F32 = common.F32


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def rotate(x, theta):
    """x [B,S,H,D]: rotate pairs (i, i + D/2) by position * theta^(-2i/D)."""
    s, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    angle = jnp.arange(s, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def layer(x, w, eps, theta):
    h = rms_norm(x, w["ln1"]["scale"], eps)
    q = jnp.einsum("bsd,dhk->bshk", h, w["attn"]["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, w["attn"]["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, w["attn"]["wv"])
    o = common.causal_attention(rotate(q, theta), rotate(k, theta), v)
    x = x + jnp.einsum("bshk,hkd->bsd", o, w["attn"]["wo"])
    h = rms_norm(x, w["ln2"]["scale"], eps)
    gate = jax.nn.silu(h @ w["mlp"]["w_gate"]) * (h @ w["mlp"]["w_up"])
    return x + gate @ w["mlp"]["w_down"]


@common.highest
def forward(params, tokens, hp):
    """tokens [B,S] int32 -> logits [B,S,V] float32."""
    eps, theta = hp["rms_norm_eps"], hp["rope_theta"]
    x = params["embed"]["table"][tokens].astype(F32)
    x = common.run_layers(lambda x, w: layer(x, w, eps, theta), x,
                          params["blocks"], hp["num_hidden_layers"])
    x = rms_norm(x, params["final_norm"]["scale"].astype(F32), eps)
    return x @ params["lm_head"]["kernel"].astype(F32)
