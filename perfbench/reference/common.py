"""Pieces the plain references share: causal softmax attention with grouped
keys and values, the mean next-token cross-entropy, and the layer-at-a-time
driver. Plain ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul
otherwise runs in lower precision): no kernels, no cache, no scan. Nothing
here imports ``ray_tpu``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def causal_attention(q, k, v):
    """q [B,S,H,D], k/v [B,S,Hkv,D] -> [B,S,H,D]; each group of H/Hkv query
    heads shares one key/value head."""
    b, s, h, d = q.shape
    group = h // k.shape[2]
    k = jnp.repeat(k, group, axis=2)
    v = jnp.repeat(v, group, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(d))
    mask = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(mask[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def next_token_loss(logits, tokens):
    """Mean cross-entropy of position t's logits against token t+1."""
    logp = jax.nn.log_softmax(logits[:, :-1].astype(F32), axis=-1)
    picked = jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
    return -jnp.mean(picked)


def to_f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def run_layers(layer_fn, x, blocks, num_layers: int, *extra):
    """Apply ``layer_fn(x, layer_weights, *extra)`` layer by layer. The
    system stacks its layers on a leading axis; one layer is sliced off and
    cast to float32 at a time, so the reference fits beside bf16 weights it
    could not hold whole in float32."""
    step = jax.jit(lambda x, w, *e: layer_fn(x, to_f32(w), *e))
    for i in range(num_layers):
        x = step(x, jax.tree.map(lambda a, i=i: a[i], blocks), *extra)
    return x


def highest(fn):
    """Run ``fn`` with float32 matmuls at full precision."""
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return wrapped
