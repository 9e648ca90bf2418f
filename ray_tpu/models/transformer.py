"""Decoder-only transformer family (GPT-2 and LLaMA variants), pure JAX.

Replaces the reference's stance of "models live in torch inside the worker
loop" (e.g. `train/torch/train_loop_utils.py` wraps arbitrary nn.Modules):
here the flagship models are JAX pytrees whose leaves carry logical axis
names, so one `device_put` with `ShardingRules` yields DP/FSDP/TP/SP layouts
and XLA/GSPMD inserts all collectives.

Config switches:
  * norm: 'rmsnorm' (LLaMA) | 'layernorm' (GPT-2)
  * pos:  'rope' (LLaMA) | 'learned' (GPT-2)
  * mlp:  'swiglu' (LLaMA) | 'gelu' (GPT-2) | 'moe' (SwiGLU experts,
          dropless top-k routing over grouped matmuls, ops/moe.py)
  * GQA via num_kv_heads; tied embeddings via tie_embeddings.
  * qk_norm: RMSNorm over the whole projected q and k (OLMoE);
    moe_renormalize: top-k router weights divided by their sum (Mixtral)
    or taken as they are (OLMoE).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import attention
from ray_tpu.ops.losses import softmax_cross_entropy
from ray_tpu.ops.norms import layer_norm, rms_norm
from ray_tpu.ops.ring_attention import ring_attention_local
from ray_tpu.ops.rotary import apply_rotary, rope_frequencies


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 12
    embed_dim: int = 768
    num_heads: int = 12
    num_kv_heads: Optional[int] = None        # None => MHA
    mlp_dim: Optional[int] = None             # None => 4x (gelu) / 8/3x (swiglu)
    # MoE (mlp='moe'): SwiGLU experts, dropless top-k routing
    moe_num_experts: int = 0
    moe_top_k: int = 2
    moe_renormalize: bool = True              # False: OLMoE (norm_topk_prob)
    moe_aux_weight: float = 0.01
    # RMSNorm over ALL H*D (resp. Hkv*D) projected values of q and k, before
    # the split into heads and before RoPE (OLMoE's q_norm / k_norm)
    qk_norm: bool = False
    max_seq_len: int = 2048
    norm: str = "rmsnorm"                     # 'rmsnorm' | 'layernorm'
    pos: str = "rope"                         # 'rope' | 'learned'
    mlp: str = "swiglu"                       # 'swiglu' | 'gelu' | 'moe'
    rope_theta: float = 10000.0
    tie_embeddings: bool = True
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16                 # activation/compute dtype
    param_dtype: Any = jnp.float32
    remat: bool = True                        # checkpoint each block
    # 'full': recompute everything in backward (min memory, ~+2N flops/tok);
    # 'dots': save matmul outputs, recompute elementwise only (near-full
    # memory, tiny recompute) — the right trade when HBM allows
    remat_policy: str = "full"
    scan_layers: bool = True                  # stack layers, lax.scan over them
    attn_impl: str = "auto"                   # 'auto'|'flash'|'reference'|'ring'
    # fold the vocab projection into the CE loss (chunked, logits never
    # materialized — see ops/losses.fused_softmax_cross_entropy)
    fused_ce: bool = True
    ce_chunk: int = 2048

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def head_dim(self) -> int:
        return self.embed_dim // self.num_heads

    @property
    def hidden_dim(self) -> int:
        if self.mlp_dim:
            return self.mlp_dim
        if self.mlp in ("swiglu", "moe"):
            # LLaMA convention: 2/3 * 4d rounded to a multiple of 256
            h = int(8 * self.embed_dim / 3)
            return 256 * ((h + 255) // 256)
        return 4 * self.embed_dim


# ---------------------------------------------------------------------------
# params


def _block_params(cfg: TransformerConfig, key) -> Dict[str, Any]:
    d, h, kvh, hd, f = (cfg.embed_dim, cfg.num_heads, cfg.kv_heads,
                        cfg.head_dim, cfg.hidden_dim)
    ks = jax.random.split(key, 8)
    init = jax.nn.initializers.normal(0.02, cfg.param_dtype)
    out_init = jax.nn.initializers.normal(
        0.02 / math.sqrt(2 * cfg.num_layers), cfg.param_dtype)
    p: Dict[str, Any] = {
        "attn": {
            "wq": init(ks[0], (d, h, hd)),
            "wk": init(ks[1], (d, kvh, hd)),
            "wv": init(ks[2], (d, kvh, hd)),
            "wo": out_init(ks[3], (h, hd, d)),
        },
        "ln1": _norm_params(cfg, d),
        "ln2": _norm_params(cfg, d),
    }
    if cfg.qk_norm:
        p["attn"]["q_norm"] = jnp.ones((h * hd,), cfg.param_dtype)
        p["attn"]["k_norm"] = jnp.ones((kvh * hd,), cfg.param_dtype)
    if cfg.mlp == "moe":
        from ray_tpu.ops.moe import init_moe_params

        if cfg.moe_num_experts < 2:
            raise ValueError("mlp='moe' needs moe_num_experts >= 2")
        p["mlp"] = init_moe_params(ks[4], d, f, cfg.moe_num_experts,
                                   cfg.param_dtype)
    elif cfg.mlp == "swiglu":
        p["mlp"] = {
            "w_gate": init(ks[4], (d, f)),
            "w_up": init(ks[5], (d, f)),
            "w_down": out_init(ks[6], (f, d)),
        }
    else:
        p["mlp"] = {
            "w_in": init(ks[4], (d, f)),
            "b_in": jnp.zeros((f,), cfg.param_dtype),
            "w_out": out_init(ks[5], (f, d)),
            "b_out": jnp.zeros((d,), cfg.param_dtype),
        }
    return p


def _norm_params(cfg: TransformerConfig, dim: int):
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.ones((dim,), cfg.param_dtype)}
    return {"scale": jnp.ones((dim,), cfg.param_dtype),
            "bias": jnp.zeros((dim,), cfg.param_dtype)}


def init_params(cfg: TransformerConfig, key) -> Dict[str, Any]:
    keys = jax.random.split(key, cfg.num_layers + 3)
    init = jax.nn.initializers.normal(0.02, cfg.param_dtype)
    params: Dict[str, Any] = {
        "embed": {"table": init(keys[0], (cfg.vocab_size, cfg.embed_dim))},
        "final_norm": _norm_params(cfg, cfg.embed_dim),
    }
    if cfg.pos == "learned":
        params["pos_embed"] = {
            "table": init(keys[1], (cfg.max_seq_len, cfg.embed_dim))}
    if not cfg.tie_embeddings:
        params["lm_head"] = {
            "kernel": init(keys[2], (cfg.embed_dim, cfg.vocab_size))}
    blocks = [_block_params(cfg, keys[3 + i]) for i in range(cfg.num_layers)]
    if cfg.scan_layers:
        params["blocks"] = jax.tree.map(
            lambda *xs: jnp.stack(xs, axis=0), *blocks)
    else:
        params["blocks"] = {str(i): b for i, b in enumerate(blocks)}
    return params


def logical_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    """Pytree (same structure as params) of logical-axis tuples."""
    L = ("layers",) if cfg.scan_layers else ()

    def norm_axes():
        if cfg.norm == "rmsnorm":
            return {"scale": L + ("embed_notp",)}
        return {"scale": L + ("embed_notp",), "bias": L + ("embed_notp",)}

    block = {
        "attn": {
            "wq": L + ("embed", "heads", "head_dim"),
            "wk": L + ("embed", "kv", "head_dim"),
            "wv": L + ("embed", "kv", "head_dim"),
            "wo": L + ("heads", "head_dim", "embed"),
        },
        "ln1": norm_axes(),
        "ln2": norm_axes(),
    }
    if cfg.qk_norm:
        block["attn"]["q_norm"] = L + (None,)
        block["attn"]["k_norm"] = L + (None,)
    if cfg.mlp == "moe":
        from ray_tpu.ops.moe import moe_logical_axes

        block["mlp"] = {k: L + v for k, v in moe_logical_axes().items()}
    elif cfg.mlp == "swiglu":
        block["mlp"] = {"w_gate": L + ("embed", "mlp"),
                        "w_up": L + ("embed", "mlp"),
                        "w_down": L + ("mlp", "embed")}
    else:
        block["mlp"] = {"w_in": L + ("embed", "mlp"),
                        "b_in": L + ("mlp",),
                        "w_out": L + ("mlp", "embed"),
                        "b_out": L + ("embed_notp",)}
    axes: Dict[str, Any] = {
        "embed": {"table": ("vocab", "embed")},
        "final_norm": {"scale": ("embed_notp",)} if cfg.norm == "rmsnorm"
        else {"scale": ("embed_notp",), "bias": ("embed_notp",)},
        "blocks": block if cfg.scan_layers
        else {str(i): jax.tree.map(lambda a: a, block)
              for i in range(cfg.num_layers)},
    }
    if cfg.pos == "learned":
        axes["pos_embed"] = {"table": (None, "embed")}
    if not cfg.tie_embeddings:
        axes["lm_head"] = {"kernel": ("embed", "vocab")}
    return axes


def count_params(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# forward


def _norm(cfg, p, x):
    if cfg.norm == "rmsnorm":
        return rms_norm(x, p["scale"], cfg.norm_eps)
    return layer_norm(x, p["scale"], p["bias"], cfg.norm_eps)


def _qkv(cfg, p, x, rope, positions):
    """The q/k/v projection of every forward (training, tensor-parallel,
    cached and paged decode): x [B, S, d] -> q [B, S, H, D], k and v
    [B, S, Hkv, D], q and k normalized (``cfg.qk_norm``) and rotated."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cfg.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(cfg.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(cfg.dtype))
    if cfg.qk_norm:
        # one scale vector over all heads' values: the norm sees the
        # projection whole, before it is split into heads
        q = rms_norm(q.reshape(*q.shape[:2], -1), p["q_norm"],
                     cfg.norm_eps).reshape(q.shape)
        k = rms_norm(k.reshape(*k.shape[:2], -1), p["k_norm"],
                     cfg.norm_eps).reshape(k.shape)
    if rope is not None:
        cos, sin = rope
        q = apply_rotary(q, cos, sin, positions)
        k = apply_rotary(k, cos, sin, positions)
    return q, k, v


def _attn(cfg, p, x, rope, positions, sp_axis, kv_cache=None):
    q, k, v = _qkv(cfg, p, x, rope, positions)
    if kv_cache is not None:
        # decode: append to cache, attend over the full prefix
        bias = kv_cache.mask_bias(x.shape[1])
        new_cache, k_all, v_all = kv_cache.update(k, v)
        o = attention(q, k_all, v_all, causal=False, impl="reference",
                      bias=bias)
    elif cfg.attn_impl == "ring" and sp_axis is not None:
        o = ring_attention_local(q, k, v, sp_axis, causal=True)
        new_cache = None
    else:
        o = attention(q, k, v, causal=True, impl=cfg.attn_impl
                      if cfg.attn_impl != "ring" else "auto")
        new_cache = None
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(cfg.dtype))
    return out, new_cache


def _mlp(cfg, p, x, valid=None, layer=None):
    """Returns (y, aux_loss, moe): aux is 0 and moe None except for MoE
    routing, where moe is ``{"counts": [E], "routes": [B, S, k]}`` (rows
    each expert received; the experts each row chose). ``valid``: bool
    [B, S], rows that are not live (the expert layer routes them nowhere; a
    dense mlp takes no notice). ``layer`` (experts only): ``p`` is every
    layer's weights stacked and this is the one to apply (``stacked_mlp``)."""
    if cfg.mlp == "moe":
        from ray_tpu.ops.moe import moe_layer

        y, aux, counts, routes = moe_layer(
            p, x, num_experts=cfg.moe_num_experts, top_k=cfg.moe_top_k,
            renormalize=cfg.moe_renormalize, dtype=cfg.dtype, valid=valid,
            layer=layer)
        return y, aux, {"counts": counts, "routes": routes}
    if cfg.mlp == "swiglu":
        gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(cfg.dtype))
        up = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(cfg.dtype))
        return jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up,
                          p["w_down"].astype(cfg.dtype)), 0.0, None
    h = jnp.einsum("bsd,df->bsf", x, p["w_in"].astype(cfg.dtype))
    h = jax.nn.gelu(h + p["b_in"].astype(cfg.dtype), approximate=True)
    return (jnp.einsum("bsf,fd->bsd", h, p["w_out"].astype(cfg.dtype))
            + p["b_out"].astype(cfg.dtype)), 0.0, None


def stacked_mlp(cfg, params, layer_params, i):
    """``(mlp weights, layer)`` for ``_mlp``: a layer's own weights and
    None, but for experts stacked over layers (``scan_layers``) outside a
    scan the whole stack and ``i`` — the grouped matmuls cannot fuse the
    slice as a dense matmul does, and would copy the layer's experts."""
    if cfg.mlp == "moe" and cfg.scan_layers:
        return params["blocks"]["mlp"], i
    return layer_params["mlp"], None


def _block(cfg, p, x, rope, positions, sp_axis, kv_cache=None, mlp=None):
    """``mlp``: ``stacked_mlp``'s pair where the caller walks stacked
    layers one by one; None for ``(p["mlp"], None)``."""
    a, new_cache = _attn(cfg, p["attn"], _norm(cfg, p["ln1"], x), rope,
                         positions, sp_axis, kv_cache)
    x = x + a
    mlp_p, layer = mlp or (p["mlp"], None)
    m, aux, moe = _mlp(cfg, mlp_p, _norm(cfg, p["ln2"], x), layer=layer)
    x = x + m
    return x, new_cache, aux, moe


def forward(cfg: TransformerConfig, params, tokens, *, positions=None,
            sp_axis: Optional[str] = None, kv_caches=None,
            return_aux: bool = False, return_hidden: bool = False,
            return_routes: bool = False):
    """tokens [B, S] int32 -> logits [B, S, vocab].

    return_hidden: skip the vocab projection and return the post-final-norm
    hidden states [B, S, D] (with aux) — used by the fused-CE loss path.
    return_routes (debug, mlp='moe' without kv_caches): also return the
    experts every token chose in every layer, int32 [L, B, S, k] — top-k is
    discontinuous, so a comparison with another implementation has to be
    made on the same choices.

    sp_axis: when running inside shard_map with sequence sharded over that
    axis, attention goes through the ring kernel and `positions` must be the
    global positions of this shard.
    kv_caches: optional list/stack of per-layer decode caches (see
    ray_tpu.models.decode); when set, runs in incremental-decode mode.
    """
    x = params["embed"]["table"].astype(cfg.dtype)[tokens]
    if cfg.pos == "learned":
        pos = positions if positions is not None else jnp.arange(tokens.shape[1])
        x = x + params["pos_embed"]["table"].astype(cfg.dtype)[pos]
        rope = None
    else:
        rope = rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)

    block_fn = _block
    if cfg.remat and kv_caches is None:
        policies = {
            "full": jax.checkpoint_policies.nothing_saveable,
            "dots": jax.checkpoint_policies.checkpoint_dots,
        }
        if cfg.remat_policy not in policies:
            raise ValueError(
                f"unknown remat_policy {cfg.remat_policy!r}; "
                f"expected one of {sorted(policies)}")
        policy = policies[cfg.remat_policy]
        block_fn = jax.checkpoint(
            _block, static_argnums=(0, 5), policy=policy)

    if return_routes and (cfg.mlp != "moe" or kv_caches is not None):
        raise ValueError("return_routes needs mlp='moe' and no kv_caches")
    new_caches = None
    aux_total = 0.0
    routes = None
    if cfg.scan_layers and kv_caches is None:
        def body(carry, layer_params):
            h, aux_acc = carry
            h, _, aux, moe = block_fn(cfg, layer_params, h, rope, positions,
                                      sp_axis)
            return (h, aux_acc + aux), (moe["routes"] if return_routes
                                        else None)
        (x, aux_total), routes = jax.lax.scan(body, (x, 0.0),
                                              params["blocks"])
    elif cfg.scan_layers:
        new_caches = []
        for i in range(cfg.num_layers):
            layer_p = jax.tree.map(lambda a, i=i: a[i], params["blocks"])
            x, c, aux, _ = _block(cfg, layer_p, x, rope, positions, sp_axis,
                                  kv_caches[i],
                                  stacked_mlp(cfg, params, layer_p, i))
            aux_total = aux_total + aux
            new_caches.append(c)
    else:
        new_caches = [] if kv_caches is not None else None
        per_layer = []
        for i in range(cfg.num_layers):
            cache = kv_caches[i] if kv_caches is not None else None
            x, c, aux, moe = block_fn(cfg, params["blocks"][str(i)], x, rope,
                                      positions, sp_axis, cache)
            aux_total = aux_total + aux
            if new_caches is not None:
                new_caches.append(c)
            if return_routes:
                per_layer.append(moe["routes"])
        if return_routes:
            routes = jnp.stack(per_layer)

    x = _norm(cfg, params["final_norm"], x)
    if return_hidden:
        return x, aux_total
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x,
                            params["embed"]["table"].astype(cfg.dtype))
    else:
        logits = jnp.einsum("bsd,dv->bsv", x,
                            params["lm_head"]["kernel"].astype(cfg.dtype))
    if kv_caches is not None:
        return logits, new_caches
    if return_routes:
        return logits, routes
    if return_aux:
        return logits, aux_total
    return logits


# ---------------------------------------------------------------------------
# tensor parallelism (Megatron column/row sharding, arXiv:1909.09756)
#
# Each block is cut over tp ranks: QKV / ffn-up are COLUMN-parallel (output
# features sharded — heads for attention, ffn columns for the mlp) and the
# attention proj / ffn-down are ROW-parallel (input features sharded), so a
# rank's block forward needs exactly one partial-sum allreduce per sublayer:
# the conjugate (g, f) operator pair from ray_tpu.util.collective.tp. Norms,
# post-reduce biases, embeddings and the lm_head stay replicated and receive
# exact replicated gradients via f's backward reduce — no flush-time tp sync.


def tp_block_shard_spec(cfg: TransformerConfig) -> Dict[str, Dict[str, int]]:
    """path -> shard axis for ONE UNSTACKED block's sharded leaves.

    Column-parallel leaves shard their output-feature axis, row-parallel
    leaves their input-feature axis. Leaves absent from the spec (norms,
    gelu's post-reduce b_out) are replicated. For scan-stacked blocks add 1
    to every axis (the leading layers axis).
    """
    if cfg.qk_norm:
        raise ValueError(
            "tensor parallelism does not support cfg.qk_norm=True — the "
            "norm spans all heads' values, which tp splits over ranks")
    spec: Dict[str, Dict[str, int]] = {
        "attn": {"wq": 1, "wk": 1, "wv": 1,   # (d, heads, hd) — heads
                 "wo": 0},                     # (heads, hd, d) — heads
    }
    if cfg.mlp == "swiglu":
        spec["mlp"] = {"w_gate": 1, "w_up": 1,  # (d, f) — ffn columns
                       "w_down": 0}             # (f, d) — ffn columns
    elif cfg.mlp == "gelu":
        spec["mlp"] = {"w_in": 1, "b_in": 0,    # column-parallel (+ its bias)
                       "w_out": 0}              # row-parallel; b_out replicated
    else:
        raise ValueError(
            "tensor parallelism does not support cfg.mlp='moe' — experts "
            "are already expert-parallel; shard with moe_num_experts "
            "instead, or set cfg.mlp to 'swiglu'/'gelu'")
    return spec


def _tp_map_block(cfg, block, fn, stacked: bool):
    """Apply fn(leaf, shard_axis_or_None) over one block's leaves."""
    spec = tp_block_shard_spec(cfg)
    off = 1 if stacked else 0
    out: Dict[str, Any] = {}
    for group, leaves in block.items():
        gspec = spec.get(group, {})
        out[group] = {
            name: fn(leaf, gspec[name] + off if name in gspec else None)
            for name, leaf in leaves.items()}
    return out


def shard_block_params(cfg: TransformerConfig, block, tp: int, tp_rank: int,
                       *, stacked: bool = False):
    """Rank ``tp_rank``'s shard of one block's params (replicated leaves
    pass through unsliced). ``stacked``: block carries a leading layers
    axis (scan_layers stacking)."""
    def cut(leaf, axis):
        if axis is None:
            return leaf
        n = leaf.shape[axis]
        k = n // tp
        idx = (slice(None),) * axis + (slice(tp_rank * k, (tp_rank + 1) * k),)
        return leaf[idx]

    return _tp_map_block(cfg, block, cut, stacked)


def merge_tp_block_params(cfg: TransformerConfig, shards, *,
                          stacked: bool = False):
    """Bit-exact inverse of shard_block_params: concatenate the rank
    shards back into the fused block (replicated leaves taken from
    rank 0)."""
    def glue(path_leaves, axis):
        if axis is None:
            return path_leaves[0]
        return jnp.concatenate(path_leaves, axis=axis)

    spec = tp_block_shard_spec(cfg)
    off = 1 if stacked else 0
    out: Dict[str, Any] = {}
    for group in shards[0]:
        gspec = spec.get(group, {})
        out[group] = {
            name: glue([s[group][name] for s in shards],
                       gspec[name] + off if name in gspec else None)
            for name in shards[0][group]}
    return out


def _tp_attn_partial(cfg, p, x, rope, positions=None):
    """Attention over this rank's local heads; returns the PARTIAL output
    projection (sum over local heads only — g completes it)."""
    q, k, v = _qkv(cfg, p, x, rope, positions)
    o = attention(q, k, v, causal=True,
                  impl=cfg.attn_impl if cfg.attn_impl != "ring" else "auto")
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(cfg.dtype))


def _tp_mlp_partial(cfg, p, x):
    """MLP over this rank's local ffn columns; returns the PARTIAL down
    projection (gelu's replicated b_out is added AFTER g — see
    _tp_mlp_finish)."""
    if cfg.mlp == "swiglu":
        gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(cfg.dtype))
        up = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(cfg.dtype))
        return jnp.einsum("bsf,fd->bsd", jax.nn.silu(gate) * up,
                          p["w_down"].astype(cfg.dtype))
    h = jnp.einsum("bsd,df->bsf", x, p["w_in"].astype(cfg.dtype))
    h = jax.nn.gelu(h + p["b_in"].astype(cfg.dtype), approximate=True)
    return jnp.einsum("bsf,fd->bsd", h, p["w_out"].astype(cfg.dtype))


def _tp_mlp_finish(cfg, p, reduced):
    """Post-reduce epilogue: replicated bias (gelu) rides on the FULL sum
    so each rank adds it exactly once."""
    if cfg.mlp == "gelu":
        return reduced + p["b_out"].astype(cfg.dtype)
    return reduced


def _tp_block(cfg, p, x, rope, g, f):
    """Sharded block forward, exact parity with _block on the fused model.

    f on the norm outputs (column-parallel inputs) makes replicated-param
    and residual cotangents exact; g on the row-parallel partial sums
    completes each sublayer's activation."""
    a = g(_tp_attn_partial(cfg, p["attn"], f(_norm(cfg, p["ln1"], x)), rope))
    x = x + a
    m = _tp_mlp_finish(
        cfg, p["mlp"],
        g(_tp_mlp_partial(cfg, p["mlp"], f(_norm(cfg, p["ln2"], x)))))
    return x + m


def _tp_block_tail(cfg, p, x, rope, g, f):
    """Last block of a forward chunk, tail-split: returns (u, mlp_partial)
    where the full output is u + allreduce(mlp_partial). The trainer issues
    that final reduce asynchronously on the host and overlaps it with the
    next microbatch's compute. Only valid when the mlp has no post-reduce
    epilogue (swiglu — see tp_tail_supported)."""
    a = g(_tp_attn_partial(cfg, p["attn"], f(_norm(cfg, p["ln1"], x)), rope))
    u = x + a
    mp = _tp_mlp_partial(cfg, p["mlp"], f(_norm(cfg, p["ln2"], u)))
    return u, mp


def tp_tail_supported(cfg: TransformerConfig) -> bool:
    """Whether forward chunks may tail-split their last block (the partial
    sum must BE the block's residual delta — no post-reduce bias)."""
    return cfg.mlp == "swiglu"


def loss_fn(cfg: TransformerConfig, params, batch, *, sp_axis=None,
            positions=None):
    """Causal-LM loss. batch: {'tokens': [B,S], optional 'mask': [B,S]}.
    Targets are tokens shifted left; the last position is dropped."""
    tokens = batch["tokens"]
    targets = tokens[:, 1:]
    mask = batch.get("mask")
    if mask is not None:
        mask = mask[:, 1:]
    if cfg.fused_ce:
        from ray_tpu.ops.losses import fused_softmax_cross_entropy

        hidden, aux = forward(cfg, params, tokens, sp_axis=sp_axis,
                              positions=positions, return_hidden=True)
        if cfg.tie_embeddings:
            table, transpose = params["embed"]["table"], False
        else:
            table, transpose = params["lm_head"]["kernel"], True
        loss, n = fused_softmax_cross_entropy(
            hidden[:, :-1], table, targets, mask, chunk=cfg.ce_chunk,
            compute_dtype=cfg.dtype, transpose_table=transpose)
    else:
        logits, aux = forward(cfg, params, tokens, sp_axis=sp_axis,
                              positions=positions, return_aux=True)
        loss, n = softmax_cross_entropy(logits[:, :-1], targets, mask)
    metrics = {"loss": loss, "tokens": n}
    if cfg.mlp == "moe":
        loss = loss + cfg.moe_aux_weight * aux
        metrics["moe_aux"] = aux
    return loss, metrics
