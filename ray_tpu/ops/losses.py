"""Loss ops. Cross entropy in float32 with optional z-loss, mask-aware.

`fused_softmax_cross_entropy` folds the vocab projection into the loss,
computing logits chunk-by-chunk from the final hidden states so the full
[tokens, vocab] logit tensor never hits HBM (for GPT-2s at B16xS1024 that
tensor is ~3.3 GB in f32 — the single largest HBM cost of the train step).
The backward recomputes each chunk's logits (jax.checkpoint inside the scan),
trading a second chunk matmul for the saved residuals."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel.sharding import free_axes


def softmax_cross_entropy(logits, labels, mask=None, z_loss: float = 0.0):
    """Token-level CE. logits: [..., vocab] (any dtype), labels: [...] int,
    mask: [...] {0,1}. Returns (mean_loss, n_tokens). The max-subtraction and
    logsumexp run in f32 so bf16 logits are safe on the MXU."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    label_logits = jnp.take_along_axis(
        logits, labels[..., None], axis=-1
    ).squeeze(-1)
    loss = lse - label_logits
    if z_loss > 0.0:
        loss = loss + z_loss * jnp.square(lse)
    if mask is None:
        n = jnp.array(loss.size, jnp.float32)
        return jnp.mean(loss), n
    mask = mask.astype(jnp.float32)
    n = jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.sum(loss * mask) / n, n


def fused_softmax_cross_entropy(hidden, table, labels, mask=None, *,
                                z_loss: float = 0.0, chunk: int = 2048,
                                transpose_table: bool = False,
                                compute_dtype=jnp.bfloat16):
    """Projection-fused token CE: logits are `hidden @ table^T`, computed one
    token-chunk at a time under a scan and never materialized whole.

    hidden: [..., D] final hidden states (post final-norm, pre vocab
    projection); table: [V, D] (tied embedding table) or [D, V] when
    `transpose_table` (untied lm_head kernel); labels: [...] int; mask: [...]
    {0,1}. Returns (mean_loss, n_tokens) — same contract as
    `softmax_cross_entropy`.

    The vocab axis is zero-padded to a multiple of 128 (v5e lane width) with a
    -inf logit bias on the pad columns so the MXU tiles cleanly and the
    logsumexp is unchanged.
    """
    if transpose_table:
        table = table.T  # [V, D] view; XLA folds the transpose into the dot
    V, D = table.shape
    x = hidden.reshape(-1, D)
    n_tok = x.shape[0]
    labels = labels.reshape(-1)
    m = (jnp.ones((n_tok,), jnp.float32) if mask is None
         else mask.reshape(-1).astype(jnp.float32))

    chunk = min(chunk, n_tok)
    pad_n = (-n_tok) % chunk
    if pad_n:
        x = jnp.pad(x, ((0, pad_n), (0, 0)))
        labels = jnp.pad(labels, (0, pad_n))
        m = jnp.pad(m, (0, pad_n))

    pad_v = (-V) % 128
    w = table.astype(compute_dtype)
    if pad_v:
        w = jnp.pad(w, ((0, pad_v), (0, 0)))
    # -inf bias on pad columns keeps them out of the logsumexp
    col_bias = jnp.where(jnp.arange(V + pad_v) < V, 0.0, -1e30).astype(
        jnp.float32)

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.nothing_saveable)
    def chunk_loss(xi, li, mi):
        logits = jnp.dot(xi.astype(compute_dtype), w.T,
                         preferred_element_type=jnp.float32) + col_bias
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        label_logit = jnp.take_along_axis(
            logits, li[:, None], axis=-1)[:, 0]
        per_tok = lse - label_logit
        if z_loss > 0.0:
            per_tok = per_tok + z_loss * jnp.square(lse)
        return jnp.sum(per_tok * mi)

    xc = x.reshape(-1, chunk, D)
    lc = labels.reshape(-1, chunk)
    mc = m.reshape(-1, chunk)
    rows = free_axes("batch", chunk)
    if rows is not None:
        # under a mesh (``make_train_step``) a chunk is data-parallel like
        # the batch it was cut from: ITS ROWS over the batch's axes, the
        # hidden dimension whole, the table whole but for the vocabulary's
        # split. Left to the partitioner, the scanned axis takes the batch's
        # split with it and each chunk's logits, [chunk, vocab] in float32,
        # are all-reduced as partial sums over a split hidden dimension —
        # twice a chunk, the recompute's too
        constrain = jax.lax.with_sharding_constraint
        xc = constrain(xc, P(None, rows, None))
        lc, mc = constrain(lc, P(None, rows)), constrain(mc, P(None, rows))
        w = constrain(w, P(free_axes("vocab", V + pad_v), None))

    def body(acc, args):
        return acc + chunk_loss(*args), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xc, lc, mc))
    n = (jnp.array(float(n_tok), jnp.float32) if mask is None
         else jnp.maximum(jnp.sum(m), 1.0))
    return total / n, n
