"""Decoder-only transformer family (GPT-2 and LLaMA variants), pure JAX.

Replaces the reference's stance of "models live in torch inside the worker
loop" (e.g. `train/torch/train_loop_utils.py` wraps arbitrary nn.Modules):
here the flagship models are JAX pytrees whose leaves carry logical axis
names, so one `device_put` with `ShardingRules` yields DP/FSDP/TP/SP layouts
and XLA/GSPMD inserts all collectives.

Config switches:
  * norm: 'rmsnorm' (LLaMA) | 'layernorm' (GPT-2)
  * pos:  'rope' (LLaMA) | 'learned' (GPT-2) | 'none' (no positional
          signal in attention at all: the state-space layers of a hybrid
          carry the order)
  * mlp:  'swiglu' (LLaMA) | 'gelu' (GPT-2) | 'moe' (SwiGLU experts,
          dropless top-k routing over grouped matmuls, ops/moe.py). The
          feed-forward is a pattern over the depth too: the first
          ``moe_dense_layers`` layers of an expert model are plain SwiGLU of
          ``dense_mlp_dim`` (a source's ``first_k_dense_replace``), the
          expert layers follow; ``cfg.mlp_of(i)`` is the ONE place that
          says which layer has which, and the leading layers are kept apart
          from the scanned ones (``params["blocks"]["lead"]`` and
          ``["body"]``). ``moe_scoring`` ('softmax' | 'sigmoid', the latter
          chosen by score + bias), ``moe_routed_scale`` and
          ``moe_shared_experts`` are the router's rule and the dense expert
          every token takes beside its routed ones (``moe_shared_dim``: its
          own width where that is not the experts'); ``moe_activation`` is
          the expert's form ('swiglu', three matrices, or 'relu2', two:
          ``W_down relu(x W_up)^2``); ``moe_held_first`` / ``moe_held_count``
          say which experts THIS chip holds of a layer that is shared over
          several: the router keeps its width, the layer computes its own
          experts' part.
  * layer_pattern: a source's string of SUBLAYERS ('M' a Mamba-2 mixer, '*'
          an attention mixer, 'E' an expert feed-forward;
          ``LAYER_SYMBOLS``): every layer is then ONE pre-norm sublayer, a
          mixer or a feed-forward alone, and holds the parameters of what
          it has and no other. ``cfg.kinds[i]`` and ``cfg.mlp_of(i)`` are
          the one definition of what layer ``i`` has (``NONE``: it has no
          such part), read by the parameters, their axes, the scan's period
          and every forward.
  * GQA via num_kv_heads; tied embeddings via tie_embeddings; head_dim a
    field where it is not embed_dim // num_heads.
  * rope_parameters: a RoPE rule a kind of layer, keyed as the source keys
    them (``ops.rotary.rule_frequencies``: 'default' or 'yarn'); the angles
    are then computed from the positions, no table.
  * qk_norm: RMSNorm over the whole projected q and k (OLMoE);
    head_qk_norm: RMSNorm over each head's values, one scale of head_dim
    shared by the heads (MiniCPM);
    moe_renormalize: top-k router weights divided by their sum (Mixtral)
    or taken as they are (OLMoE).
  * layer_kinds: the mixer of each layer, a pattern over the depth —
    'attention' (softmax attention over the whole context, the only kind
    when the pattern is None), 'minicpm4' (block-selected sparse attention
    with an output gate and no RoPE, ops/sparse_attention.py) and
    'lightning-attn' (decayed linear attention on a [D, D] state a head,
    with RoPE, an output norm and gate, ops/linear_attention.py) and
    'power-retention' (degree 2, gated and normalised, on the symmetric
    half of the outer product as the state of a K/V head, shared by its
    query heads; q/k norm and RoPE as the block has them, no output gate;
    ops/power_retention.py) and 'sliding_attention' (softmax attention
    over the last ``sliding_window`` positions, the query's own among them:
    the attention block as it stands, K/V heads and all, with a window in
    the mask; in the serving pool its pages are a pool of their own, of
    which a slot holds those the window still covers) and
    'indexed_attention' (the attention block with a learned INDEXER beside
    it: ``sa_config``'s index heads score every token of the context from
    one cached index key a token, and the query attends the ``topk`` best
    TOKENS, every one while the context is no longer than that;
    ops/indexed_attention.py; its pages hold the index key beside K and V,
    in the full layers' pool) and 'latent_attention' (multi-head LATENT
    attention: q through a low-rank bottleneck with a norm of its own, keys
    and values rebuilt from ONE latent of ``latent_kv_rank`` values a token
    plus ONE rotated key of ``latent_rope_dim`` shared by the heads; what a
    token leaves in the cache is the latent after its norm and that key
    after RoPE, and a cached forward attends the latents themselves, the
    key's and the value's up-projection absorbed into the query and the
    output; ops/latent_attention.py; its pages, in the full layers' pool,
    hold the two joined in one row a token) and 'indexed_latent_attention'
    (latent attention WITH an indexer: the index queries come from the
    QUERY'S BOTTLENECK after its norm, a token leaves its latent row and
    its index key's row in a page, the first ``latent_rope_dim`` values of
    an index head are rotated and the rest not, and a query attends the
    ``topk`` LATENTS its indexer picks, every one while the context is no
    longer than that; ops/picked_latent_attention.py) and 'mamba2' (Mamba-2's
    state-space mixer: one in-projection to a gate, the joined x, B, C and
    a step a head; a causal convolution of ``ssm_conv_kernel`` taps over
    the joined three, a selective scan on a float32 state [N, P] a head
    with B and C shared by a group's heads, the gate, an RMSNorm a group
    and ``w_out``; two states a sequence, the convolution's last inputs
    and the scan's; ops/ssm.py). 'full_attention' is
    taken for 'attention', so a source's ``layer_types`` map straight onto
    ``layer_kinds``. ``rope_scaling``'s ``mrope_section`` turns runs of
    frequency pairs by a position stream each (temporal, height, width:
    ``positions`` [3, B, S]; text positions [B, S] are all three). Each
    state kind is written once, state in and state out,
    as three pieces — project the rows, mix one group of them, finish the
    rows (``STATE_MIXERS``, ``sparse_mix``) — that every forward calls: this
    one with or without caches (``state_mixer``, ``sparse_mixer``), and the
    paged serving forward of models/decode.py, which mixes a group of rows
    at a time. What a kind that keeps a state a sequence keeps is said in
    ONE place, ``state_shapes``.
  * scale_emb, scale_depth (over scale_depth_layers), dim_model_base: the
    MiniCPM scales of the embedding, of every residual branch and of the
    hidden state before the output head.
  * loop_passes (a source's ``total_ut_steps``): the whole stack applied so
    many times a token, the SAME weights every pass. The final norm closes
    every pass and its output enters the next; a learned gate reads each
    pass's normed state and ``exit_threshold`` picks whose state the head
    projects (``exit_shares``, ``exit_pass``) — every pass is computed for
    every token whatever the gate says. The keys and values a query of pass
    t attends are those pass t wrote: a cache a (pass, layer). 1: the stack
    once, no gate, today's model. output_norms: a norm on the mixer's and on
    the feed-forward's OUTPUT before the residual add, beside the two on
    their inputs (``ln1_out``, ``ln2_out``).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.attention import attention
from ray_tpu.ops.indexed_attention import (IndexerSizes, index_row,
                                           index_width, indexed_attention)
from ray_tpu.ops.latent_attention import (join as latent_row,
                                          latent_attention, pool_width)
from ray_tpu.ops.picked_latent_attention import picked_latent_attention
from ray_tpu.ops.flash_attention import FLASH_LSE, FLASH_OUT
from ray_tpu.ops.linear_attention import (linear_attention_chunk,
                                          linear_attention_step, slopes)
from ray_tpu.ops.losses import softmax_cross_entropy
from ray_tpu.ops.norms import layer_norm, rms_norm
from ray_tpu.ops import power_retention
from ray_tpu.ops.power_retention import (power_retention_chunk,
                                         power_retention_step)
from ray_tpu.ops.ring_attention import ring_attention_local
from ray_tpu.ops.rotary import (apply_rotary, apply_rotary_at,
                                rope_frequencies, yarn_mscale)
from ray_tpu.ops.sparse_attention import (SparseSizes, sparse_attention,
                                          update_page_means)
from ray_tpu.ops import ssm
from ray_tpu.ops.ssm import SsmSizes
from ray_tpu.parallel.sharding import free_axes, free_parts

ATTENTION, SPARSE, LINEAR = "attention", "minicpm4", "lightning-attn"
RETENTION = "power-retention"
SLIDING = "sliding_attention"
INDEXED = "indexed_attention"
LATENT = "latent_attention"
INDEXED_LATENT = "indexed_latent_attention"
MAMBA = "mamba2"
LAYER_KINDS = (ATTENTION, SPARSE, LINEAR, RETENTION, SLIDING, INDEXED, LATENT,
               INDEXED_LATENT, MAMBA)
# the kinds whose sizes are the ``latent_*`` fields
LATENT_KINDS = (LATENT, INDEXED_LATENT)
# what a layer that is ONE sublayer lacks: its kind where it has no mixer,
# its ``mlp_of`` where it has no feed-forward
NONE = "none"
# a source's ``hybrid_override_pattern``, a symbol a layer -> (the layer's
# mixer, its feed-forward)
LAYER_SYMBOLS = {"M": (MAMBA, NONE), "*": (ATTENTION, NONE),
                 "E": (NONE, "moe")}
# what a source calls the kind this file calls 'attention': its name in
# ``layer_kinds`` as given and in ``rope_parameters``
FULL_ATTENTION = "full_attention"
# the kinds that keep a fixed state a sequence and no keys or values: no
# page of the serving pool is theirs
STATE_KINDS = (LINEAR, RETENTION, MAMBA)


def holds_page(kind: str) -> bool:
    """Whether a layer of ``kind`` keeps something a token in the pages of
    the serving pool (not a state a sequence, and not nothing at all)."""
    return kind != NONE and kind not in STATE_KINDS


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 12
    embed_dim: int = 768
    num_heads: int = 12
    num_kv_heads: Optional[int] = None        # None => MHA
    head_dim: Optional[int] = None            # None => embed_dim // num_heads
    mlp_dim: Optional[int] = None             # None => 4x (gelu) / 8/3x (swiglu)
    # MoE (mlp='moe'): SwiGLU experts, dropless top-k routing
    moe_num_experts: int = 0
    moe_top_k: int = 2
    moe_renormalize: bool = True              # False: OLMoE (norm_topk_prob)
    moe_aux_weight: float = 0.01
    # the router's rule (ops.moe.route): 'softmax', or 'sigmoid' scores
    # chosen by score + a bias an expert and weighed without it, the weights
    # then times moe_routed_scale
    moe_scoring: str = "softmax"
    moe_routed_scale: float = 1.0
    # group-limited choice (a source's n_group / topk_group): the experts in
    # so many groups of consecutive indices, of which the best so many stay
    # (1: every expert stands for choice)
    moe_groups: int = 1
    moe_top_groups: int = 1
    # dense experts every token takes beside its routed ones, mlp_dim wide
    # each (one SwiGLU of their joined width)
    moe_shared_experts: int = 0
    # the first so many layers of an expert model are plain SwiGLU of
    # dense_mlp_dim (a source's first_k_dense_replace / intermediate_size)
    moe_dense_layers: int = 0
    dense_mlp_dim: Optional[int] = None
    # an expert's form (ops.moe.ACTIVATIONS): 'swiglu' or the two-matrix
    # 'relu2'; the shared expert has the same form
    moe_activation: str = "swiglu"
    # the shared expert's own width (0: moe_shared_experts x mlp_dim)
    moe_shared_dim: int = 0
    # the experts this chip holds of every expert layer, ``count`` from
    # ``first`` on (0: all moe_num_experts, which stays the router's width)
    moe_held_first: int = 0
    moe_held_count: int = 0
    # every layer ONE sublayer, a symbol of LAYER_SYMBOLS each (a source's
    # hybrid_override_pattern); None: every layer a mixer and a feed-forward
    layer_pattern: Optional[str] = None
    # 'mamba2': heads, a head's values, the groups that share B and C, the
    # state's size, the convolution's taps, a block of the chunked scan
    ssm_num_heads: int = 0
    ssm_head_dim: int = 0
    ssm_groups: int = 1
    ssm_state_dim: int = 0
    ssm_conv_kernel: int = 4
    ssm_chunk: int = 128
    # RMSNorm over ALL H*D (resp. Hkv*D) projected values of q and k, before
    # the split into heads and before RoPE (OLMoE's q_norm / k_norm)
    qk_norm: bool = False
    # RMSNorm over each head's head_dim values of q and k, before RoPE
    head_qk_norm: bool = False
    # the mixer of every layer (LAYER_KINDS); None: 'attention' throughout
    layer_kinds: Optional[Tuple[str, ...]] = None
    # 'minicpm4': the sizes of the selection (ops.sparse_attention
    # .SparseSizes' fields; a dict is taken and frozen)
    sparse_config: Any = None
    # 'sliding_attention': the positions a query attends, its own among them
    sliding_window: int = 0
    # a RoPE rule a kind of layer, {'full_attention': {...},
    # 'sliding_attention': {...}} as the source's config has it (a dict is
    # taken and frozen); None: ``rope_theta`` for every layer that rotates
    rope_parameters: Any = None
    # 'indexed_attention': the indexer's sizes (ops.indexed_attention
    # .IndexerSizes' fields, a source's ``sa_config``; a dict is taken and
    # frozen)
    sa_config: Any = None
    # the same three sizes where a source lists them flat (index_n_heads,
    # index_head_dim, index_topk): read where ``sa_config`` is None
    index_num_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # 'latent_attention', 'indexed_latent_attention': the ranks of the
    # query's and the keys-and-values'
    # bottlenecks, a head's unrotated and rotated q/k values and its v
    # values (head_dim is the two q/k parts together)
    latent_q_rank: int = 0
    latent_kv_rank: int = 0
    latent_nope_dim: int = 0
    latent_rope_dim: int = 0
    latent_v_dim: int = 0
    # a source's ``rope_scaling`` (a dict is taken and frozen), read for
    # ``mrope_section`` (runs of frequency pairs, a position stream each)
    # and, by the latent kinds, for a ``type`` of 'yarn': the rule their
    # rotated parts turn by and the softmax scale's factor (``latent_rope``)
    rope_scaling: Any = None
    # 'lightning-attn': head h decays by exp(-2^(-e (h + 1) / H)) a token
    linear_slope_exponent: float = 8.0
    # MiniCPM's scales: the embedding times scale_emb; every residual
    # branch times scale_depth / sqrt(scale_depth_layers or num_layers)
    # (0: added as it is); the hidden state before the head divided by
    # embed_dim / dim_model_base (0: as it is)
    scale_emb: float = 1.0
    scale_depth: float = 0.0
    scale_depth_layers: int = 0
    dim_model_base: int = 0
    # the stack applied this many times a token (a source's total_ut_steps),
    # the final norm behind every pass; 1: once, and no exit gate
    loop_passes: int = 1
    # a token leaves at the first pass whose exit shares add up to this (a
    # source's early_exit_threshold), else at the last: ``exit_pass``
    exit_threshold: float = 1.0
    # a norm on each sublayer's OUTPUT before the residual add
    output_norms: bool = False
    max_seq_len: int = 2048
    norm: str = "rmsnorm"                     # 'rmsnorm' | 'layernorm'
    pos: str = "rope"                         # 'rope' | 'learned' | 'none'
    mlp: str = "swiglu"                       # 'swiglu' | 'gelu' | 'moe'
    rope_theta: float = 10000.0
    tie_embeddings: bool = True
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16                 # activation/compute dtype
    param_dtype: Any = jnp.float32
    remat: bool = True                        # checkpoint each block
    # What a block keeps for its backward (``remat_policy`` below, the one
    # table). 'full': its input, the attention kernel's output and
    # log-sum-exp and, under ``tp``, the reduced ``wo`` result; all else is
    # recomputed (min memory, ~+2N flops/tok). 'dots': those and every
    # matmul's output, recomputing elementwise only (near-full memory, tiny
    # recompute) — the right trade when HBM allows. The kernel's two stay
    # under both because its recompute is the one part of a block that
    # grows with S squared, and what keeping them costs is as small as the
    # block's input ([B, S, H*D], the float32 log-sum-exp 2/D of it).
    remat_policy: str = "full"
    scan_layers: bool = True                  # stack layers, lax.scan over them
    attn_impl: str = "auto"                   # 'auto'|'flash'|'reference'|'ring'
    # fold the vocab projection into the CE loss (chunked, logits never
    # materialized — see ops/losses.fused_softmax_cross_entropy)
    fused_ce: bool = True
    ce_chunk: int = 2048

    def __post_init__(self):
        if self.head_dim is None:
            object.__setattr__(self, "head_dim",
                               self.embed_dim // self.num_heads)
        pattern = self.layer_pattern
        if pattern is not None:
            kinds = tuple(LAYER_SYMBOLS.get(symbol, (None,))[0]
                          for symbol in pattern)
            # (``dataclasses.replace`` hands the derived kinds back in)
            if (len(pattern) != self.num_layers or None in kinds
                    or self.layer_kinds not in (None, kinds)
                    or self.moe_dense_layers):
                raise ValueError(
                    f"layer_pattern must give one of {sorted(LAYER_SYMBOLS)} "
                    f"for each of the {self.num_layers} layers, with "
                    "layer_kinds and moe_dense_layers left alone, got "
                    f"{pattern!r}")
            object.__setattr__(self, "layer_kinds", kinds)
        if self.layer_kinds is not None:
            kinds = tuple(ATTENTION if kind == FULL_ATTENTION else kind
                          for kind in self.layer_kinds)
            if len(kinds) != self.num_layers or set(kinds) - set(
                    LAYER_KINDS + ((NONE,) if pattern else ())):
                raise ValueError(
                    f"layer_kinds must name one of {LAYER_KINDS} for each of "
                    f"the {self.num_layers} layers, got {kinds}")
            object.__setattr__(self, "layer_kinds", kinds)
        if isinstance(self.sparse_config, dict):
            object.__setattr__(self, "sparse_config",
                               tuple(sorted(self.sparse_config.items())))
        if isinstance(self.rope_parameters, dict):
            object.__setattr__(self, "rope_parameters", tuple(sorted(
                (kind, tuple(sorted(rule.items())))
                for kind, rule in self.rope_parameters.items())))
        if isinstance(self.sa_config, dict):
            object.__setattr__(self, "sa_config",
                               tuple(sorted(self.sa_config.items())))
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, "rope_scaling", tuple(sorted(
                (key, tuple(value) if isinstance(value, list) else value)
                for key, value in self.rope_scaling.items())))
        if INDEXED in self.kinds and self.sa_config is None:
            raise ValueError("an 'indexed_attention' layer needs sa_config "
                             "(the indexer's sizes)")
        if (INDEXED_LATENT in self.kinds and self.sa_config is None
                and min(self.index_num_heads, self.index_head_dim,
                        self.index_topk) < 1):
            raise ValueError(
                "an 'indexed_latent_attention' layer needs sa_config or "
                "index_num_heads, index_head_dim and index_topk")
        if SLIDING in self.kinds and self.sliding_window < 1:
            raise ValueError("a 'sliding_attention' layer needs "
                             f"sliding_window >= 1, got {self.sliding_window}")
        if set(LATENT_KINDS) & set(self.kinds):
            sizes = (self.latent_q_rank, self.latent_kv_rank,
                     self.latent_nope_dim, self.latent_rope_dim,
                     self.latent_v_dim)
            if min(sizes) < 1 or self.latent_rope_dim % 2:
                raise ValueError(
                    "a latent attention layer needs latent_q_rank, "
                    "latent_kv_rank, latent_nope_dim, latent_rope_dim (even) "
                    f"and latent_v_dim, got {sizes}")
            object.__setattr__(self, "head_dim", self.latent_nope_dim
                               + self.latent_rope_dim)
            if (INDEXED_LATENT in self.kinds
                    and self.indexer.indexer_head_dim < self.latent_rope_dim):
                raise ValueError("an index head holds the rotated part: "
                                 f"{self.indexer.indexer_head_dim} < "
                                 f"{self.latent_rope_dim}")
        if MAMBA in self.kinds and (
                min(self.ssm_num_heads, self.ssm_head_dim, self.ssm_state_dim,
                    self.ssm_groups) < 1
                or self.ssm_num_heads % self.ssm_groups):
            raise ValueError(
                "a 'mamba2' layer needs ssm_num_heads (a multiple of "
                "ssm_groups), ssm_head_dim and ssm_state_dim")
        held = (self.moe_held_first, self.moe_held_count)
        if held != (0, 0) and not (
                0 <= held[0] and 0 < held[1]
                and sum(held) <= self.moe_num_experts):
            raise ValueError(
                "moe_held_first / moe_held_count name a run of the "
                f"{self.moe_num_experts} experts, got {held}")
        if self.loop_passes < 1 or self.loop_passes > 1 and (
                set(self.kinds) != {ATTENTION} or self.mlp == "moe"
                or pattern is not None or not self.scan_layers):
            raise ValueError(
                "loop_passes counts the times the stack is applied: >= 1, "
                "and more than once only over stacked layers (scan_layers) "
                "that are all 'attention' over a dense feed-forward, got "
                f"{self.loop_passes} over {sorted(set(self.kinds))}, "
                f"mlp={self.mlp!r}")
        if self.moe_dense_layers and not (
                self.mlp == "moe"
                and 0 < self.moe_dense_layers < self.num_layers
                and len(set(self.kinds[:self.moe_dense_layers])) == 1):
            raise ValueError(
                "moe_dense_layers counts the leading dense layers of an "
                "expert model (mlp='moe'), of one mixer kind and fewer than "
                f"num_layers, got {self.moe_dense_layers}")

    @property
    def kv_heads(self) -> int:
        return self.num_kv_heads or self.num_heads

    @property
    def kinds(self) -> Tuple[str, ...]:
        return self.layer_kinds or (ATTENTION,) * self.num_layers

    @property
    def lead_layers(self) -> int:
        """The leading layers whose feed-forward is not the model's
        ``mlp``: stacked apart from the rest and applied before the scan."""
        return self.moe_dense_layers

    def mlp_of(self, i: int) -> str:
        """Which feed-forward layer ``i`` has: THE definition, read by the
        parameters, their axes and every forward."""
        if self.layer_pattern is not None:
            return LAYER_SYMBOLS[self.layer_pattern[i]][1]
        return "swiglu" if i < self.moe_dense_layers else self.mlp

    def mlp_width(self, ff: str) -> int:
        """The hidden width of a feed-forward of kind ``ff`` of this model:
        an expert's, or a leading dense layer's."""
        if ff != self.mlp and self.dense_mlp_dim:
            return self.dense_mlp_dim
        return self.hidden_dim

    @property
    def expert_layers(self) -> int:
        return sum(self.mlp_of(i) == "moe" for i in range(self.num_layers))

    @property
    def period(self) -> int:
        """The pattern's period: the layers one scan step applies when
        layers are stacked (1 for a model of one kind), over the layers
        behind the leading ones."""
        kinds = [(kind, self.mlp_of(i)) for i, kind in enumerate(self.kinds)
                 ][self.lead_layers:]
        return next(p for p in range(1, len(kinds) + 1)
                    if len(kinds) % p == 0
                    and all(k == kinds[i % p] for i, k in enumerate(kinds)))

    @property
    def looped(self) -> bool:
        """The stack is applied more than once a token."""
        return self.loop_passes > 1

    @property
    def recurrent(self) -> bool:
        """Some layer carries a state that is no K/V cache: what a token
        leaves behind cannot be cut at a page boundary or rewound."""
        return any(kind in STATE_KINDS for kind in self.kinds)

    @property
    def holds_pages(self) -> bool:
        """Some layer keeps keys and values: the serving pool has pages."""
        return any(holds_page(kind) for kind in self.kinds)

    @property
    def ssm(self) -> SsmSizes:
        return SsmSizes(self.ssm_num_heads, self.ssm_head_dim,
                        self.ssm_groups, self.ssm_state_dim,
                        self.ssm_conv_kernel, self.ssm_chunk)

    @property
    def held(self) -> Optional[Tuple[int, int]]:
        """(first, count) of the experts this chip holds; None: all."""
        if not self.moe_held_count:
            return None
        return self.moe_held_first, self.moe_held_count

    @property
    def experts_held(self) -> int:
        return self.moe_held_count or self.moe_num_experts

    @property
    def sparse(self) -> SparseSizes:
        return SparseSizes(**dict(self.sparse_config or ()))

    @property
    def indexer(self) -> IndexerSizes:
        if self.sa_config is None and self.index_topk:
            return IndexerSizes(indexer_num_heads=self.index_num_heads,
                                indexer_head_dim=self.index_head_dim,
                                topk=self.index_topk)
        return IndexerSizes(**dict(self.sa_config or ()))

    @property
    def latent_rope(self):
        """(the rule the latent kinds' rotated parts turn by, what their
        softmax scale ``head_dim ** -0.5`` is multiplied by): (None, 1.0)
        — ``rope_theta`` alone — unless ``rope_scaling`` is of ``type``
        'yarn' (DeepSeek-V3's: cos and sin scaled by ``mscale`` over
        ``mscale_all_dim``'s factor, 1 where the two are equal, and the
        scores by the square of the latter's)."""
        scaling = dict(self.rope_scaling or ())
        if scaling.get("type", scaling.get("rope_type")) != "yarn":
            return None, 1.0
        factor = float(scaling["factor"])
        every = yarn_mscale(factor, float(scaling.get("mscale_all_dim", 0)))
        rule = {key: scaling[key] for key in (
            "factor", "original_max_position_embeddings", "beta_fast",
            "beta_slow") if key in scaling}
        rule.update(rope_type="yarn", rope_theta=self.rope_theta,
                    attention_factor=yarn_mscale(
                        factor, float(scaling.get("mscale", 1))) / every)
        return rule, every * every

    @property
    def mrope_section(self) -> Optional[Tuple[int, ...]]:
        return dict(self.rope_scaling or ()).get("mrope_section")

    @property
    def residual_scale(self) -> float:
        if not self.scale_depth:
            return 1.0
        return self.scale_depth / math.sqrt(self.scale_depth_layers
                                            or self.num_layers)

    def rope_rule(self, kind: str):
        """The rule (``ops.rotary.rule_frequencies``) the layers of ``kind``
        rotate by, None where ``rope_theta`` says it all."""
        if self.rope_parameters is None:
            return None
        rules = dict(self.rope_parameters)
        return dict(rules[FULL_ATTENTION if kind == ATTENTION else kind])

    def window(self, kind: str) -> Optional[int]:
        """The positions a query of a layer of ``kind`` attends, its own
        among them; None for the whole context."""
        return self.sliding_window if kind == SLIDING else None

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


def _block_params(cfg: TransformerConfig, key, kind: str = ATTENTION,
                  ff: Optional[str] = None) -> Dict[str, Any]:
    """``ff``: the layer's feed-forward (``cfg.mlp_of``); None: ``cfg.mlp``."""
    ff = ff or cfg.mlp
    d, f = cfg.embed_dim, cfg.mlp_width(ff)
    ks = jax.random.split(key, 8)
    init = jax.nn.initializers.normal(0.02, cfg.param_dtype)
    out_init = jax.nn.initializers.normal(
        0.02 / math.sqrt(2 * cfg.num_layers), cfg.param_dtype)
    # a layer holds what it has: a mixer under its norm, a feed-forward
    # under its own, or (every layer but a ``layer_pattern``'s) both
    p: Dict[str, Any] = {}
    if kind != NONE:
        p["attn"] = _mixer_params(cfg, kind, ks, init, out_init)
        p["ln1"] = _norm_params(cfg, d)
    if ff != NONE:
        p["ln2"] = _norm_params(cfg, d)
    for name in _output_norms(cfg, kind, ff):
        # the norm SETS its branch's size, so its seeded scale says how far
        # a pass moves the stream. At 1 the stream is 2 L unit vectors over
        # an input of 1; at (2 L)^-1/2 they add up to the residual's own
        # size: a seeded stack gone through several times then amplifies
        # rounding for some seeds' weights (bf16 against float32 at the
        # published sizes: 1.1% on most sequences, 7-27% on one in six,
        # where weights rounded to float8 read 13-157%: no limit lies
        # between). At 0.3 of it a pass moves the stream by a third and the
        # passes contract: bf16 1.5-2.7% on every sequence read, float8
        # 16-69% (PERF.md 6, PR 65)
        p[name] = jax.tree.map(
            lambda a: a * 0.3 * (2 * cfg.num_layers) ** -0.5,
            _norm_params(cfg, d))
    if ff == "moe":
        from ray_tpu.ops.moe import SIGMOID, init_moe_params

        if cfg.moe_num_experts < 2:
            raise ValueError("mlp='moe' needs moe_num_experts >= 2")
        p["mlp"] = init_moe_params(
            ks[4], d, f, cfg.moe_num_experts, cfg.param_dtype,
            choice_bias=cfg.moe_scoring == SIGMOID,
            shared_dim=cfg.moe_shared_dim or cfg.moe_shared_experts * f,
            activation=cfg.moe_activation, held=cfg.experts_held)
    elif ff == "swiglu":
        p["mlp"] = {
            "w_gate": init(ks[4], (d, f)),
            "w_up": init(ks[5], (d, f)),
            "w_down": out_init(ks[6], (f, d)),
        }
    elif ff != NONE:
        p["mlp"] = {
            "w_in": init(ks[4], (d, f)),
            "b_in": jnp.zeros((f,), cfg.param_dtype),
            "w_out": out_init(ks[5], (f, d)),
            "b_out": jnp.zeros((d,), cfg.param_dtype),
        }
    return p


def _output_norms(cfg: TransformerConfig, kind: str, ff: str):
    """The names of a layer's norms behind its sublayers (``output_norms``):
    one for what it has of a mixer and a feed-forward."""
    if not cfg.output_norms:
        return ()
    return (("ln1_out",) if kind != NONE else ()) + (
        ("ln2_out",) if ff != NONE else ())


def _mixer_params(cfg: TransformerConfig, kind: str, ks, init, out_init):
    """A layer's mixer of ``kind`` from the layer's keys ``ks``."""
    d, h, kvh, hd = (cfg.embed_dim, cfg.num_heads, cfg.kv_heads,
                     cfg.head_dim)
    if kind in LATENT_KINDS:
        return _latent_params(cfg, ks, init, out_init,
                              indexer=kind == INDEXED_LATENT)
    if kind == MAMBA:
        return _mamba_params(cfg, ks, init, out_init)
    if kind == LINEAR:
        kvh = h  # a key and a value head for every query head
    attn = {
        "wq": init(ks[0], (d, h, hd)),
        "wk": init(ks[1], (d, kvh, hd)),
        "wv": init(ks[2], (d, kvh, hd)),
        "wo": out_init(ks[3], (h, hd, d)),
    }
    if cfg.qk_norm:
        attn["q_norm"] = jnp.ones((h * hd,), cfg.param_dtype)
        attn["k_norm"] = jnp.ones((kvh * hd,), cfg.param_dtype)
    if cfg.head_qk_norm:
        attn["q_norm"] = jnp.ones((hd,), cfg.param_dtype)
        attn["k_norm"] = jnp.ones((hd,), cfg.param_dtype)
    if kind in (SPARSE, LINEAR):
        attn["wg"] = init(ks[7], (d, h, hd))   # the output gate
    if kind == LINEAR:
        attn["o_norm"] = jnp.ones((h * hd,), cfg.param_dtype)
    if kind == RETENTION:
        attn["wc"] = init(ks[7], (d, kvh))     # a log-gate a K/V head
    if kind == INDEXED:
        hi, di = cfg.indexer.indexer_num_heads, cfg.indexer.indexer_head_dim
        ki = jax.random.split(ks[7], 3)
        attn.update(
            wi_q=init(ki[0], (d, hi, di)), wi_k=init(ki[1], (d, di)),
            wi_w=init(ki[2], (d, hi)),
            ik_scale=jnp.ones((di,), cfg.param_dtype),
            ik_bias=jnp.zeros((di,), cfg.param_dtype))
    return attn


# what a seeded 'mamba2' layer's step bias and decay are drawn between:
# Mamba-2's own initialisation (softplus(dt_bias) log-uniform over a source's
# time_step_min .. time_step_max, A uniform over 1 .. 16)
_SSM_DT = (0.001, 0.1)
_SSM_A = (1.0, 16.0)


def _mamba_params(cfg: TransformerConfig, ks, init, out_init):
    """A 'mamba2' layer's mixer: the in-projection to [gate | x B C | dt],
    the convolution's taps and bias over the joined x, B, C (drawn as
    PyTorch draws a Conv1d's: uniform within 1 / sqrt(taps); weights of the
    projections' spread would leave the convolution's output, and with it
    the whole scan, a hundredth of the gate's size), the step's bias, the
    decay's log, the skip, the gated norm's scale and ``w_out``."""
    d, sizes = cfg.embed_dim, cfg.ssm
    inner, width, H = sizes.inner, sizes.conv_width, sizes.heads
    km = jax.random.split(ks[7], 4)
    bound = sizes.conv ** -0.5
    uniform = lambda key, shape, lo, hi: jax.random.uniform(
        key, shape, jnp.float32, lo, hi)
    dt = jnp.exp(uniform(km[2], (H,), *map(math.log, _SSM_DT)))
    return {
        "w_in": init(ks[0], (d, inner + width + H)),
        "conv_w": uniform(km[0], (sizes.conv, width), -bound, bound
                          ).astype(cfg.param_dtype),
        "conv_b": uniform(km[1], (width,), -bound, bound
                          ).astype(cfg.param_dtype),
        # the inverse of softplus, float32 as the source keeps them
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "a_log": jnp.log(uniform(km[3], (H,), *_SSM_A)),
        "d_skip": jnp.ones((H,), jnp.float32),
        "norm": jnp.ones((inner,), cfg.param_dtype),
        "w_out": out_init(ks[3], (inner, d)),
    }


def _latent_params(cfg: TransformerConfig, ks, init, out_init,
                   indexer: bool = False):
    """A 'latent_attention' layer's mixer: the query's bottleneck and its
    norm, the projection to the latent and (behind it) the one rotated key
    every head shares, the latent's norm, the up-projection to a head's
    unrotated key values and (behind them) its values, and ``wo``. With
    ``indexer`` ('indexed_latent_attention') the indexer's beside them, from
    keys of their own: the index queries' projection out of the QUERY'S
    BOTTLENECK, the index key's and the heads' weights' out of the layer's
    input, the key's LayerNorm."""
    d, h = cfg.embed_dim, cfg.num_heads
    rq, rkv, nope, rot, dv = (
        cfg.latent_q_rank, cfg.latent_kv_rank, cfg.latent_nope_dim,
        cfg.latent_rope_dim, cfg.latent_v_dim)
    kl = jax.random.split(ks[7], 4)
    attn = {
        "wq_a": init(kl[0], (d, rq)),
        "q_a_norm": jnp.ones((rq,), cfg.param_dtype),
        "wq_b": init(kl[1], (rq, h, nope + rot)),
        "wkv_a": init(kl[2], (d, rkv + rot)),
        "kv_norm": jnp.ones((rkv,), cfg.param_dtype),
        "wkv_b": init(kl[3], (rkv, h, nope + dv)),
        "wo": out_init(ks[3], (h, dv, d)),
    }
    if indexer:
        hi, di = cfg.indexer.indexer_num_heads, cfg.indexer.indexer_head_dim
        ki = jax.random.split(jax.random.fold_in(ks[7], 4), 3)
        attn.update(
            wi_q=init(ki[0], (rq, hi, di)), wi_k=init(ki[1], (d, di)),
            wi_w=init(ki[2], (d, hi)),
            ik_scale=jnp.ones((di,), cfg.param_dtype),
            ik_bias=jnp.zeros((di,), cfg.param_dtype))
    return attn


def _norm_params(cfg: TransformerConfig, dim: int):
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.ones((dim,), cfg.param_dtype)}
    return {"scale": jnp.ones((dim,), cfg.param_dtype),
            "bias": jnp.zeros((dim,), cfg.param_dtype)}


_EXIT_GATE_KEY = 0x6174  # folded into the model's key for the exit gate


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
    if cfg.looped:
        # d + 1 values from a key of their own: no other weight moves
        params["exit_gate"] = {
            "w": init(jax.random.fold_in(key, _EXIT_GATE_KEY),
                      (cfg.embed_dim,)),
            "b": jnp.zeros((), cfg.param_dtype)}
    if cfg.looped:
        # one kind over one feed-forward (``__post_init__``): ONE layer's
        # draws under ``vmap`` over the layers' keys. Drawn a layer at a
        # time the published 48 layers are 48 copies of the draws in the
        # program, two minutes of compile for the chip — more than a serving
        # replica is given to start (``serve/_private/controller.py``:
        # INIT_TIMEOUT_S), so a replica with a cold compile cache is
        # replaced for ever (PERF.md 6, PR 65)
        params["blocks"] = jax.vmap(lambda k: _block_params(
            cfg, k, cfg.kinds[0], cfg.mlp_of(0)))(keys[3:])
        return params
    blocks = [_block_params(cfg, keys[3 + i], kind, cfg.mlp_of(i))
              for i, kind in enumerate(cfg.kinds)]
    stack = lambda layers: jax.tree.map(
        lambda *xs: jnp.stack(xs, axis=0), *layers)
    lead = cfg.lead_layers
    if not cfg.scan_layers:
        params["blocks"] = {str(i): b for i, b in enumerate(blocks)}
        return params
    if cfg.period == 1:
        body = stack(blocks[lead:])
    else:
        # layers of unequal kinds have unequal shapes: stacked by their
        # place in the pattern's period, which is the scan's unit
        body = {f"p{j}": stack(blocks[lead + j::cfg.period])
                for j in range(cfg.period)}
    # leading layers of another feed-forward have shapes of their own too:
    # a stack before the scanned ones
    params["blocks"] = ({"lead": stack(blocks[:lead]), "body": body}
                        if lead else body)
    return params


def body_params(cfg: TransformerConfig, params):
    """The stacked layers behind the leading ones (``scan_layers``): all of
    ``params["blocks"]`` for a model without leading layers."""
    return params["blocks"]["body"] if cfg.lead_layers else params["blocks"]


def layer_params(cfg: TransformerConfig, params, i: int):
    """Layer ``i``'s weights, whichever way ``init_params`` laid them out."""
    if not cfg.scan_layers:
        return params["blocks"][str(i)]
    if i < cfg.lead_layers:
        return jax.tree.map(lambda a: a[i], params["blocks"]["lead"])
    i -= cfg.lead_layers
    if cfg.period == 1:
        return jax.tree.map(lambda a: a[i], body_params(cfg, params))
    return jax.tree.map(lambda a: a[i // cfg.period],
                        body_params(cfg, params)[f"p{i % cfg.period}"])


def logical_axes(cfg: TransformerConfig) -> Dict[str, Any]:
    """Pytree (same structure as params) of logical-axis tuples."""
    L = ("layers",) if cfg.scan_layers else ()

    def norm_axes():
        if cfg.norm == "rmsnorm":
            return {"scale": L + ("embed_notp",)}
        return {"scale": L + ("embed_notp",), "bias": L + ("embed_notp",)}

    def mixer_axes(kind):
        if kind in LATENT_KINDS:
            attn = {
                "wq_a": L + ("embed", None), "q_a_norm": L + (None,),
                "wq_b": L + (None, "heads", "head_dim"),
                "wkv_a": L + ("embed", None), "kv_norm": L + (None,),
                "wkv_b": L + (None, "heads", "head_dim"),
                "wo": L + ("heads", "head_dim", "embed")}
            if kind == INDEXED_LATENT:
                attn.update(
                    wi_q=L + (None, None, None), wi_k=L + ("embed", None),
                    wi_w=L + ("embed", None), ik_scale=L + (None,),
                    ik_bias=L + (None,))
            return attn
        if kind == MAMBA:
            return {
                "w_in": L + ("embed", None), "conv_w": L + (None, None),
                "conv_b": L + (None,), "dt_bias": L + (None,),
                "a_log": L + (None,), "d_skip": L + (None,),
                "norm": L + (None,), "w_out": L + (None, "embed")}
        kv = "heads" if kind == LINEAR else "kv"
        attn = {
            "wq": L + ("embed", "heads", "head_dim"),
            "wk": L + ("embed", kv, "head_dim"),
            "wv": L + ("embed", kv, "head_dim"),
            "wo": L + ("heads", "head_dim", "embed"),
        }
        if cfg.qk_norm or cfg.head_qk_norm:
            attn["q_norm"] = L + (None,)
            attn["k_norm"] = L + (None,)
        if kind in (SPARSE, LINEAR):
            attn["wg"] = L + ("embed", "heads", "head_dim")
        if kind == LINEAR:
            attn["o_norm"] = L + (None,)
        if kind == RETENTION:
            attn["wc"] = L + ("embed", "kv")
        if kind == INDEXED:
            attn.update(
                wi_q=L + ("embed", None, None), wi_k=L + ("embed", None),
                wi_w=L + ("embed", None), ik_scale=L + (None,),
                ik_bias=L + (None,))
        return attn

    def block_axes(kind, ff=cfg.mlp):
        block = {}
        if kind != NONE:
            block.update(attn=mixer_axes(kind), ln1=norm_axes())
        if ff != NONE:
            block["ln2"] = norm_axes()
        for name in _output_norms(cfg, kind, ff):
            block[name] = norm_axes()
        if ff == "moe":
            from ray_tpu.ops.moe import SIGMOID, moe_logical_axes

            block["mlp"] = {k: L + v for k, v in moe_logical_axes(
                cfg.moe_scoring == SIGMOID,
                bool(cfg.moe_shared_experts),
                cfg.moe_activation).items()}
        elif ff == "swiglu":
            block["mlp"] = {"w_gate": L + ("embed", "mlp"),
                            "w_up": L + ("embed", "mlp"),
                            "w_down": L + ("mlp", "embed")}
        elif ff != NONE:
            block["mlp"] = {"w_in": L + ("embed", "mlp"),
                            "b_in": L + ("mlp",),
                            "w_out": L + ("mlp", "embed"),
                            "b_out": L + ("embed_notp",)}
        return block

    kinds, lead = cfg.kinds, cfg.lead_layers
    if not cfg.scan_layers:
        blocks = {str(i): block_axes(kind, cfg.mlp_of(i))
                  for i, kind in enumerate(kinds)}
    elif cfg.period == 1:
        blocks = block_axes(kinds[lead], cfg.mlp_of(lead))
    else:
        blocks = {f"p{j}": block_axes(kinds[lead + j], cfg.mlp_of(lead + j))
                  for j in range(cfg.period)}
    if cfg.scan_layers and lead:
        blocks = {"lead": block_axes(kinds[0], cfg.mlp_of(0)),
                  "body": blocks}
    axes: Dict[str, Any] = {
        "embed": {"table": ("vocab", "embed")},
        "final_norm": {"scale": ("embed_notp",)} if cfg.norm == "rmsnorm"
        else {"scale": ("embed_notp",), "bias": ("embed_notp",)},
        "blocks": blocks,
    }
    if cfg.pos == "learned":
        axes["pos_embed"] = {"table": (None, "embed")}
    if not cfg.tie_embeddings:
        axes["lm_head"] = {"kernel": ("embed", "vocab")}
    if cfg.looped:
        axes["exit_gate"] = {"w": ("embed_notp",), "b": ()}
    return axes


def count_params(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# forward


def _norm(cfg, p, x):
    if cfg.norm == "rmsnorm":
        return rms_norm(x, p["scale"], cfg.norm_eps)
    return layer_norm(x, p["scale"], p["bias"], cfg.norm_eps)


COMPUTED = "computed"  # ``rope``: angles from the positions, no table

# Under a mesh whose tensor-parallel axis is the compiler's to place
# (``make_train_step(mesh)``; never a decode program, which sees no mesh) a
# block's recompute pays no reduce again: ``_attn`` gives ``wo``'s result,
# the partial sums already reduced over that axis, this name, and
# ``forward``'s policy keeps it. ``w_down``'s is not needed backward and is
# not recomputed. What is left is Megatron's four: the two row-parallel
# dots' sums forward, the gradient of each norm's output backward (the
# chip's compiler sums the partial gradients of ``wq``, ``wk`` and ``wv``,
# and of ``w_gate`` and ``w_up``, before it reduces; the CPU's reduces each
# where it stands).
ATTN_OUT = "attn_out"


def remat_policy(name: str):
    """``TransformerConfig.remat_policy`` as a ``jax.checkpoint`` policy:
    the one table, for ``forward`` and the pipeline stages' block slices
    (``presets._apply_blocks``, ``_tp_apply_blocks``). Both policies keep
    the three names (``ATTN_OUT`` is named only where keeping it saves a
    reduce); ``checkpoint_dots`` alone does not see a Pallas call and would
    run the forward kernel a second time."""
    named = jax.checkpoint_policies.save_only_these_names(
        ATTN_OUT, FLASH_OUT, FLASH_LSE)
    policies = {
        "full": named,
        "dots": jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.checkpoint_dots, named),
    }
    if name not in policies:
        raise ValueError(f"unknown remat_policy {name!r}; "
                         f"expected one of {sorted(policies)}")
    return policies[name]


def _qkv(cfg, p, x, rope, positions, kind=ATTENTION):
    """The q/k/v projection of every forward (training, tensor-parallel,
    cached and paged decode): x [B, S, d] -> q [B, S, H, D], k and v
    [B, S, Hkv, D], q and k normalized (``cfg.qk_norm``,
    ``cfg.head_qk_norm``) and rotated (``rope``: the (cos, sin) tables,
    ``COMPUTED`` — by ``kind``'s own rule where ``cfg.rope_parameters``
    gives one — or None)."""
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
    if cfg.head_qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if rope is COMPUTED:
        if positions is None:
            positions = jnp.arange(x.shape[1], dtype=jnp.int32)[None]
        rule = cfg.rope_rule(kind)
        q = apply_rotary_at(q, positions, cfg.rope_theta, rule,
                            cfg.mrope_section)
        k = apply_rotary_at(k, positions, cfg.rope_theta, rule,
                            cfg.mrope_section)
    elif rope is not None:
        cos, sin = rope
        q = apply_rotary(q, cos, sin, positions)
        k = apply_rotary(k, cos, sin, positions)
    return q, k, v


# more query rows than this attend a block of them at a time (a cached
# prefill, and a window layer without a cache): the reference's [heads, rows,
# keys] scores of a long prompt are gigabytes otherwise (a window layer's
# check prompt passes its window)
_CACHED_QUERY_BLOCK = 512


def _by_query_blocks(attend_block, q, block):
    """``attend_block(rows [B, block, H, D], the block's index)`` over q
    [B, S, H, D] a block of rows at a time; the last block's padding rows
    are cut off again."""
    S = q.shape[1]
    n = -(-S // block)
    blocks = jnp.pad(q, ((0, 0), (0, n * block - S), (0, 0), (0, 0)))
    blocks = blocks.reshape(q.shape[0], n, block, *q.shape[2:])
    out = jax.lax.map(lambda args: attend_block(*args),
                      (jnp.moveaxis(blocks, 1, 0), jnp.arange(n)))
    return jnp.moveaxis(out, 0, 1).reshape(
        q.shape[0], n * block, *q.shape[2:])[:, :S]


def _cached_attention(q, k_all, v_all, kv_cache, window):
    """q [B, S, H, D] at the cache's positions ``length`` .. against the
    whole fixed-size cache, masked by ``kv_cache.mask_bias``."""
    S = q.shape[1]
    attend = lambda q, bias: attention(q, k_all, v_all, causal=False,
                                       impl="reference", bias=bias)
    block = _CACHED_QUERY_BLOCK
    if S <= block:
        return attend(q, kv_cache.mask_bias(S, window=window))
    return _by_query_blocks(
        lambda rows, b: attend(rows, kv_cache.mask_bias(
            block, window=window, first=b * block)), q, block)


def _window_bias(rows: int, keys: int, window: int, first=0, key0=0):
    """Additive bias [1, 1, 1, rows, keys]: query ``first + r`` attends key
    ``key0 + c`` iff ``0 <= j`` and ``i - window < j <= i``."""
    i = first + jnp.arange(rows)[:, None]
    j = key0 + jnp.arange(keys)[None, :]
    allowed = (j >= 0) & (j <= i) & (j > i - window)
    return jnp.where(allowed, 0.0, -1e30).astype(
        jnp.float32)[None, None, None]


def _window_attention(q, k, v, window):
    """Without a cache: q, k, v [B, S, ., D] at positions 0 .. S-1, query i
    over the keys ``i - window < j <= i``. The masked reference (the flash
    kernels take no window: ROADMAP R4); a long sequence a block of query
    rows at a time against the keys that block's windows reach and no
    others, so the scores are [heads, block, window + block] whatever S."""
    S = q.shape[1]
    block = _CACHED_QUERY_BLOCK
    attend = functools.partial(attention, causal=False, impl="reference")
    if S <= block:
        return attend(q, k, v, bias=_window_bias(S, S, window))
    back = min(window - 1, S)  # keys before a block's first row in reach
    n = -(-S // block)
    pad = ((0, 0), (back, n * block - S), (0, 0), (0, 0))
    k, v = jnp.pad(k, pad), jnp.pad(v, pad)

    def attend_block(rows, b):
        # the padded arrays' row t is key t - back: this block's keys start
        # at its first row less ``back``
        keys = [jax.lax.dynamic_slice_in_dim(a, b * block, back + block, 1)
                for a in (k, v)]
        return attend(rows, *keys, bias=_window_bias(
            block, back + block, window, first=b * block,
            key0=b * block - back))

    return _by_query_blocks(attend_block, q, block)


def _attn(cfg, p, x, rope, positions, sp_axis, kv_cache=None,
          kind=ATTENTION):
    q, k, v = _qkv(cfg, p, x, rope, positions, kind)
    window = cfg.window(kind)
    if kv_cache is not None:
        # decode: append to cache, attend over the full prefix
        new_cache, k_all, v_all = kv_cache.update(k, v)
        o = _cached_attention(q, k_all, v_all, kv_cache, window)
    elif window is not None:
        o = _window_attention(q, k, v, window)
        new_cache = None
    elif cfg.attn_impl == "ring" and sp_axis is not None:
        o = ring_attention_local(q, k, v, sp_axis, causal=True)
        new_cache = None
    else:
        o = _plain_attention(cfg, q, k, v)
        new_cache = None
    return _attn_out(cfg, p, o, named=kv_cache is None), new_cache


def _plain_attention(cfg, q, k, v):
    return attention(q, k, v, causal=True, impl=cfg.attn_impl
                     if cfg.attn_impl != "ring" else "auto")


def _attn_out(cfg, p, o, named=True):
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(cfg.dtype))
    if named and free_axes("heads", p["wo"].shape[0]) is not None:
        out = checkpoint_name(out, ATTN_OUT)
    return out


# The mixers that are no plain attention, each written once and in three
# pieces: PROJECT the rows (q, k, v and a gate: the weights, whatever the
# rows are), MIX one group of rows — rows that meet the state or the pool at
# ONE shape, a step's one token a row or a chunk's run — and FINISH the rows
# (output norm, gate, ``wo``: weights again). A forward with one group
# (this file's, with or without caches; the serving step) calls the three in
# a row (``state_mixer``, ``sparse_mixer``); the serving program that takes a
# chunk's rows and a step's through a layer together (models/decode.py)
# projects and finishes them as ONE batch and mixes a group at a time. The
# state comes in and goes out, and who holds it (nobody, a contiguous cache,
# the serving pool) is the caller's business.


def state_shapes(cfg, kind: str, rows: int) -> Dict[str, tuple]:
    """What a layer of a kind in ``STATE_KINDS`` keeps for ``rows``
    sequences: the float32 arrays by name, a row a sequence. Every holder (a
    forward without caches, ``decode.init_caches``, the serving pool, the
    scheduler's count of bytes) reads the shapes here."""
    if kind == LINEAR:
        return {"s": (rows, cfg.num_heads, cfg.head_dim, cfg.head_dim)}
    if kind == RETENTION:
        return power_retention.state_shapes(rows, cfg.kv_heads,
                                            cfg.head_dim)
    if kind == MAMBA:
        return ssm.state_shapes(rows, cfg.ssm)
    raise ValueError(f"a {kind!r} layer keeps no state")


def _gated_out(cfg, p, x, o):
    """``(o * sigmoid(x Wg)) Wo``: the output gate both mixers end in."""
    gate = jnp.einsum("bsd,dhk->bshk", x, p["wg"].astype(cfg.dtype))
    return jnp.einsum("bshk,hkd->bsd", o * jax.nn.sigmoid(gate),
                      p["wo"].astype(cfg.dtype))


def _linear_project(cfg, p, x, positions):
    return _qkv(cfg, p, x, COMPUTED, positions)


def _linear_mix(cfg, p, rows, state, *, real_len=None, active=None):
    """'lightning-attn', one group: q, k, v [B, S, H, D] on the state
    ``{"s": [B, H, D, D]}`` float32 -> (o [B, S, H, D] float32, state)."""
    q, k, v = rows
    B, S = q.shape[:2]
    slope = slopes(cfg.num_heads, cfg.linear_slope_exponent)
    if S == 1:
        o, s = linear_attention_step(
            q[:, 0], k[:, 0], v[:, 0], state["s"], slope,
            jnp.ones((B,), jnp.int32) if active is None else active)
        o = o[:, None]
    else:
        o, s = linear_attention_chunk(
            q, k, v, state["s"], slope, S if real_len is None else real_len)
    return o.astype(jnp.float32), {"s": s}


def _linear_finish(cfg, p, x, o):
    B, S = x.shape[:2]
    o = rms_norm(o.reshape(B, S, -1), p["o_norm"], cfg.norm_eps)
    return _gated_out(cfg, p, x, o.reshape(
        B, S, cfg.num_heads, cfg.head_dim).astype(cfg.dtype))


def _retention_project(cfg, p, x, positions):
    """q, k, v and the log-gate ``log sigmoid(x Wc)`` [B, S, Hkv], one a
    K/V head, float32."""
    q, k, v = _qkv(cfg, p, x, COMPUTED, positions)
    gate = jax.nn.log_sigmoid(jnp.einsum(
        "bsd,dg->bsg", x, p["wc"].astype(cfg.dtype),
        preferred_element_type=jnp.float32))
    return q, k, v, gate


def _retention_mix(cfg, p, rows, state, *, real_len=None, active=None):
    """'power-retention', one group: q [B, S, H, D], k, v [B, S, Hkv, D]
    and the log-gate on the state the dict ``state_shapes`` describes (``s``
    and the normaliser ``z``, a K/V head each, float32) -> (o [B, S, H, D],
    state)."""
    q, k, v, gate = rows
    B, S = q.shape[:2]
    if S == 1:
        o, s, z = power_retention_step(
            q[:, 0], k[:, 0], v[:, 0], gate[:, 0], state["s"], state["z"],
            jnp.ones((B,), jnp.int32) if active is None else active)
        o = o[:, None]
    else:
        o, s, z = power_retention_chunk(
            q, k, v, gate, state["s"], state["z"],
            S if real_len is None else real_len)
    return o.astype(cfg.dtype), {"s": s, "z": z}


def _retention_finish(cfg, p, x, o):
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(cfg.dtype))


def _mamba_project(cfg, p, x, positions):
    """The in-projection, split: the gate z [B, S, inner], the joined x, B,
    C before their convolution [B, S, inner + 2 G N] and the steps before
    their bias and softplus [B, S, H]. (The convolution needs the inputs a
    sequence carries, so it is the mix's.)"""
    sizes = cfg.ssm
    zxd = jnp.einsum("bsd,dk->bsk", x, p["w_in"].astype(cfg.dtype))
    inner, width = sizes.inner, sizes.conv_width
    return zxd[..., :inner], zxd[..., inner:inner + width], zxd[
        ..., inner + width:]


def _mamba_mix(cfg, p, rows, state, *, real_len=None, active=None):
    """'mamba2', one group: the gate, the joined x, B, C and the steps on
    the two states ``state_shapes`` describes ({"conv", "ssm"}, float32):
    the convolution over the inputs the sequence carries and its silu, the
    split, ``dt = softplus(dt + dt_bias)``, the scan (a chunk's or a
    step's, ``ops.ssm``) and the gate, ``y silu(z)`` -> (o [B, S, inner]
    float32, state). The norm behind the gate is the finish's."""
    z, xbc, dt = rows
    sizes, f32 = cfg.ssm, jnp.float32
    B, S = z.shape[:2]
    H, P, G, N = sizes.heads, sizes.head_dim, sizes.groups, sizes.state
    xbc, conv = ssm.causal_conv(xbc, state["conv"], p["conv_w"], p["conv_b"],
                                real_len=real_len, active=active)
    xbc = xbc.astype(cfg.dtype)
    x = xbc[..., :sizes.inner].reshape(B, S, H, P)
    bm, cm = (xbc[..., sizes.inner + j * G * N:sizes.inner + (j + 1) * G * N]
              .reshape(B, S, G, N) for j in range(2))
    dt = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))
    a, d = -jnp.exp(p["a_log"].astype(f32)), p["d_skip"].astype(f32)
    impl = "reference" if cfg.attn_impl == "reference" else None
    if S == 1:
        y, s = ssm.ssd_step(
            x[:, 0], dt[:, 0], a, bm[:, 0], cm[:, 0], d, state["ssm"],
            jnp.ones((B,), jnp.int32) if active is None else active, sizes,
            impl)
        y = y[:, None]
    else:
        y, s = ssm.ssd_chunk(x, dt, a, bm, cm, d, state["ssm"], sizes,
                             real_len, impl)
    return (y.reshape(B, S, -1) * jax.nn.silu(z.astype(f32)),
            {"conv": conv, "ssm": s})


def _mamba_finish(cfg, p, x, o):
    """The gated values through an RMSNorm a GROUP (the gate came first),
    then ``w_out``."""
    B, S = o.shape[:2]
    G = cfg.ssm.groups
    o = rms_norm(o.reshape(B, S, G, -1), p["norm"].reshape(G, -1),
                 cfg.norm_eps).reshape(B, S, -1)
    return jnp.einsum("bsk,kd->bsd", o.astype(cfg.dtype),
                      p["w_out"].astype(cfg.dtype))


# kind -> (project(cfg, p, x, positions) -> the rows' arrays [B, S, ...],
#          mix(cfg, p, rows, state, *, real_len, active) -> (o, state),
#          finish(cfg, p, x, o) -> y [B, S, d])
STATE_MIXERS = {
    LINEAR: (_linear_project, _linear_mix, _linear_finish),
    RETENTION: (_retention_project, _retention_mix, _retention_finish),
    MAMBA: (_mamba_project, _mamba_mix, _mamba_finish),
}


def state_mixer(cfg, kind, p, x, positions, state):
    """A layer of a kind in ``STATE_KINDS`` over ONE group of rows, all of
    them live and real: x [B, S, d] at ``positions`` [B, S], state the dict
    of float32 arrays ``state_shapes`` describes -> (y [B, S, d], state).
    One token a row is a step, more are a chunk. (The serving forward calls
    the three pieces itself: its steps have rows that are not ``active``,
    which keep their state bitwise, and its chunks trailing padding past
    ``real_len``.)"""
    project, mix, finish = STATE_MIXERS[kind]
    o, state = mix(cfg, p, project(cfg, p, x, positions), state)
    return finish(cfg, p, x, o), state


def written_pages(write_tables, positions, page_tokens: int):
    """The pages [B, S] of a paged pool that the rows at ``positions`` [B,
    S] land on through ``write_tables`` [B, P]; a row's offset in its page
    is ``positions % page_tokens``."""
    return jnp.take_along_axis(
        write_tables,
        jnp.clip(positions // page_tokens, 0, write_tables.shape[1] - 1),
        axis=1)


def write_pages(pool, rows, pages, offs, which=None):
    """rows [B, S, Hkv, D] into pool [N, T, Hkv * D] at (pages, offs) [B,
    S]; with ``which`` (a scalar) into pool ``which`` of a page's stack,
    [N, pools, T, Hkv * D]."""
    rows = rows.reshape(*rows.shape[:2], -1).astype(pool.dtype)
    if which is None:
        return pool.at[pages, offs].set(rows)
    return pool.at[pages, which, offs].set(rows)


def sparse_mix(cfg, q, pools, read_tables, positions, lengths, *, impl: str):
    """'minicpm4', one group, its keys and values and the pooled rows of
    their pages already in the pool: q [B, S, H, D] at ``positions`` [B, S]
    attends the blocks it chooses. ``pools`` = (k [N, T, Hkv * D], v, the
    pages' pooled key rows [N, Hkv * D] float32), a row's pages through
    ``read_tables`` [B, P], ``lengths`` [B] as ``ops.paged_attention`` takes
    them. Returns (o [B, S, H, D], the choice, bool [B, S, Hkv, NB])."""
    return sparse_attention(q, *pools, read_tables, positions, lengths,
                            cfg.sparse, impl=impl, return_selected=True)


def sparse_mixer(cfg, p, x, positions, lengths, pools, read_tables,
                 write_tables, *, impl: str, taps: Optional[List] = None):
    """'minicpm4' over ONE group of rows: x [B, S, d] at ``positions`` [B,
    S] over a paged pool (``sparse_mix`` says what the arguments are; a
    row's pages are written through ``write_tables`` [B, P]). The window's
    keys and values are written, the pooled rows of the pages they fell on
    recomputed, then the chosen blocks attended. Returns (y, pools);
    ``taps`` (a list) is given the choice."""
    k_pool, v_pool, means = pools
    T = k_pool.shape[1]
    q, k, v = _qkv(cfg, p, x, None, positions)
    cells = written_pages(write_tables, positions, T), positions % T
    k_pool = write_pages(k_pool, k, *cells)
    v_pool = write_pages(v_pool, v, *cells)
    pools = (k_pool, v_pool,
             update_page_means(means, k_pool, (write_tables, positions)))
    o, selected = sparse_mix(cfg, q, pools, read_tables, positions, lengths,
                             impl=impl)
    if taps is not None:
        taps.append(selected)
    return _gated_out(cfg, p, x, o), pools


def sparse_pool_pages(cfg, tokens: int) -> int:
    """Pages (whole blocks of them) a run of ``tokens`` tokens takes."""
    return -(-tokens // cfg.sparse.block_size) * cfg.sparse.pages_per_block


def index_project(cfg, p, x, positions):
    """The indexer's three projections of the normalized x [B, S, d]: the
    index queries qI [B, S, Hi, Di] and the token's one index key kI [B, S,
    Di] (LayerNorm first), both rotated over the whole index head by the
    layer's rule at ``positions``, and the heads' weights w [B, S, Hi]
    float32, already times ``Hi^-1/2 Di^-1/2``."""
    sizes = cfg.indexer
    qi = jnp.einsum("bsd,dhk->bshk", x, p["wi_q"].astype(cfg.dtype))
    ki = layer_norm(jnp.einsum("bsd,dk->bsk", x, p["wi_k"].astype(cfg.dtype)),
                    p["ik_scale"], p["ik_bias"], cfg.norm_eps)
    turn = lambda a: apply_rotary_at(a, positions, cfg.rope_theta,
                                     cfg.rope_rule(INDEXED),
                                     cfg.mrope_section)
    w = jnp.einsum("bsd,dh->bsh", x, p["wi_w"].astype(cfg.dtype),
                   preferred_element_type=jnp.float32)
    scale = (sizes.indexer_num_heads * sizes.indexer_head_dim) ** -0.5
    return turn(qi), turn(ki[:, :, None])[:, :, 0], w * scale


def indexed_project(cfg, p, x, rope, positions):
    """What an 'indexed_attention' layer makes of the normalized x [B, S,
    d]: (the rows that attend — q [B, S, H, D], qI, w —, what they leave in
    the pages — k, v [B, S, Hkv, D] and the index key as the pool holds it
    [B, S, 1, W] (``ops.indexed_attention.index_row``), in the order of the
    pools)."""
    q, k, v = _qkv(cfg, p, x, rope, positions, INDEXED)
    qi, ki, w = index_project(cfg, p, x, positions)
    return (q, qi, w), (k, v, index_row(ki)[:, :, None])


def indexed_mix(cfg, rows, pools, read_tables, positions, lengths, *,
                impl: str):
    """'indexed_attention', one group, its keys, values and index keys
    already in the pool: rows = (q [B, S, H, D], qI, w) at ``positions``
    [B, S] attend the tokens their indexer picks. ``pools`` = (k [N, T, Hkv
    * D], v, the index keys [N, T, W]), a row's pages through
    ``read_tables`` [B, P], ``lengths`` [B] as ``ops.paged_attention``
    takes them. Returns (o [B, S, H, D], the choice, bool [B, S,
    context])."""
    return indexed_attention(*rows, *pools, read_tables, positions, lengths,
                             cfg.indexer, impl=impl, return_selected=True)


def indexed_mixer(cfg, p, x, rope, rope_positions, positions, lengths,
                  pools, read_tables, write_tables, *, impl: str,
                  taps: Optional[List] = None):
    """'indexed_attention' over ONE group of rows: x [B, S, d] at
    ``positions`` [B, S] of their sequences (``rope_positions``: what the
    rotation sees, those or three streams of them) over a paged pool
    (``indexed_mix`` says what the arguments are; a row's pages are written
    through ``write_tables`` [B, P]). Keys, values and index keys are
    written in one go, then the picked tokens attended. Returns (y, pools);
    ``taps`` (a list) is given the choice."""
    T = pools[0].shape[1]
    rows, new = indexed_project(cfg, p, x, rope, rope_positions)
    cells = written_pages(write_tables, positions, T), positions % T
    pools = tuple(write_pages(pool, made, *cells)
                  for pool, made in zip(pools, new))
    o, selected = indexed_mix(cfg, rows, pools, read_tables, positions,
                              lengths, impl=impl)
    if taps is not None:
        taps.append(selected)
    return jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(cfg.dtype)), pools


def _latent_turn(cfg, positions):
    """The rotation of a latent kind's rotated parts at ``positions``: by
    ``rope_theta``, or by the rule ``cfg.latent_rope`` gives."""
    return lambda a: apply_rotary_at(a, positions, cfg.rope_theta,
                                     cfg.latent_rope[0])


def _latent_rows(cfg, p, x, positions):
    """``latent_project``'s three, and before them the query's bottleneck
    after its norm, cq [B, S, q_rank] (what an indexer reads)."""
    nope, rank = cfg.latent_nope_dim, cfg.latent_kv_rank
    turn = _latent_turn(cfg, positions)
    cq = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wq_a"].astype(cfg.dtype)),
                  p["q_a_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", cq, p["wq_b"].astype(cfg.dtype))
    ckr = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(cfg.dtype))
    c = rms_norm(ckr[..., :rank], p["kv_norm"], cfg.norm_eps)
    kr = turn(ckr[..., None, rank:])
    return (cq, (q[..., :nope], turn(q[..., nope:])),
            (latent_row(c[:, :, None], kr),), (c, kr))


def latent_project(cfg, p, x, positions):
    """What a 'latent_attention' layer makes of the normalized x [B, S, d]
    at ``positions`` [B, S]: (the rows that attend — q's unrotated part [B,
    S, H, nope] and its rotated part [B, S, H, rope] —, what they leave in
    the cache — the latent after its norm and the one key after RoPE, joined
    into the pool's row [B, S, 1, width] (``ops.latent_attention.join``) —,
    and the two as they are, [B, S, rank] and [B, S, 1, rope], for a forward
    without a cache)."""
    return _latent_rows(cfg, p, x, positions)[1:]


def latent_absorb(cfg, p, q_nope):
    """The key's up-projection folded into the query: q_nope [B, S, H, nope]
    -> [B, S, H, rank], whose dot with a latent is q_nope's with the key
    that latent expands to."""
    wk = p["wkv_b"][..., :cfg.latent_nope_dim].astype(cfg.dtype)
    return jnp.einsum("bshk,rhk->bshr", q_nope, wk)


def latent_mix(cfg, p, rows, pools, read_tables, lengths, *, impl: str):
    """'latent_attention', one group, its latents and rotated keys already
    in the pool: rows = (q_nope, q_rope) [B, S, H, .] at positions
    ``lengths[b]`` on attend the LATENTS of their context, absorbed.
    ``pools`` = (the tokens' rows [N, T, width],), a row's pages through
    ``read_tables`` [B, P], ``lengths`` [B] as ``ops.paged_attention`` takes
    them. Returns o' [B, S, H, rank]: the heads' mixes of latents, which
    ``latent_finish`` expands. The scale is that of the unabsorbed head,
    1/sqrt(nope + rope)."""
    q_nope, q_rope = rows
    return latent_attention(
        latent_absorb(cfg, p, q_nope), q_rope, *pools, read_tables, lengths,
        sm_scale=latent_scale(cfg), impl=impl,
        name=("latent_step_attention" if q_nope.shape[1] == 1
              else "latent_chunk_attention"))


def latent_scale(cfg) -> float:
    """The softmax scale of a latent kind: that of the unabsorbed head, times
    the YaRN factor where ``rope_scaling`` gives one (``cfg.latent_rope``)."""
    return cfg.head_dim ** -0.5 * cfg.latent_rope[1]


def latent_finish(cfg, p, o):
    """o' [B, S, H, rank] -> y [B, S, d]: the value's up-projection, then
    ``wo``."""
    wv = p["wkv_b"][..., cfg.latent_nope_dim:].astype(cfg.dtype)
    o = jnp.einsum("bshr,rhv->bshv", o.astype(cfg.dtype), wv)
    return jnp.einsum("bshv,hvd->bsd", o, p["wo"].astype(cfg.dtype))


def latent_expanded(cfg, p, rows, made):
    """The layer WITHOUT a cache, unabsorbed: every token's keys and values
    rebuilt from its latent, the shared rotated key behind each head's own,
    and plain causal attention over heads of nope + rope (the values are as
    wide when ``latent_v_dim`` is: the flash kernel's shape; else the
    reference's) -> y [B, S, d]."""
    (q_nope, q_rope), (c, kr) = rows, made
    kv = jnp.einsum("bsr,rhk->bshk", c, p["wkv_b"].astype(cfg.dtype))
    k = jnp.concatenate([kv[..., :cfg.latent_nope_dim], jnp.broadcast_to(
        kr, kr.shape[:2] + (cfg.num_heads, kr.shape[-1]))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    v = kv[..., cfg.latent_nope_dim:]
    if v.shape[-1] == q.shape[-1]:
        o = _plain_attention(cfg, q, k, v)
    else:
        o = attention(q, k, jnp.pad(v, ((0, 0),) * 3 + (
            (0, q.shape[-1] - v.shape[-1]),)), causal=True,
            impl="reference")[..., :v.shape[-1]]
    return jnp.einsum("bshv,hvd->bsd", o, p["wo"].astype(cfg.dtype))


def latent_mixer(cfg, p, x, positions, lengths, pools, read_tables,
                 write_tables, *, impl: str):
    """'latent_attention' over ONE group of rows: x [B, S, d] at
    ``positions`` [B, S] over a paged pool (``latent_mix`` says what the
    arguments are; a row's pages are written through ``write_tables`` [B,
    P]). The latents and rotated keys are written, then attended. Returns
    (y, pools)."""
    T = pools[0].shape[1]
    rows, new, _ = latent_project(cfg, p, x, positions)
    cells = written_pages(write_tables, positions, T), positions % T
    pools = tuple(write_pages(pool, made, *cells)
                  for pool, made in zip(pools, new))
    o = latent_mix(cfg, p, rows, pools, read_tables, lengths, impl=impl)
    return latent_finish(cfg, p, o), pools


def indexed_latent_project(cfg, p, x, positions):
    """What an 'indexed_latent_attention' layer makes of the normalized x
    [B, S, d] at ``positions`` [B, S]: (the rows that attend — q's unrotated
    and rotated parts as ``latent_project`` makes them, the index queries qI
    [B, S, Hi, Di] OUT OF THE QUERY'S BOTTLENECK cq and the heads' weights w
    [B, S, Hi] float32, already times ``Hi^-1/2 Di^-1/2`` —, what they leave
    in the pages — the latent's row and the index key's row [B, S, 1, W]
    (LayerNorm first), in the order of the pools). The FIRST
    ``latent_rope_dim`` values of every index head and of the index key
    turn by the layer's rule, the rest do not."""
    sizes, rot = cfg.indexer, cfg.latent_rope_dim
    cq, rows, (row,), _ = _latent_rows(cfg, p, x, positions)
    turn = _latent_turn(cfg, positions)
    part = lambda a: jnp.concatenate([turn(a[..., :rot]), a[..., rot:]],
                                     axis=-1)
    qi = jnp.einsum("bsr,rhk->bshk", cq, p["wi_q"].astype(cfg.dtype))
    ki = layer_norm(jnp.einsum("bsd,dk->bsk", x, p["wi_k"].astype(cfg.dtype)),
                    p["ik_scale"], p["ik_bias"], cfg.norm_eps)
    w = jnp.einsum("bsd,dh->bsh", x, p["wi_w"].astype(cfg.dtype),
                   preferred_element_type=jnp.float32)
    scale = (sizes.indexer_num_heads * sizes.indexer_head_dim) ** -0.5
    return ((*rows, part(qi), w * scale),
            (row, index_row(part(ki[:, :, None]))))


def indexed_latent_mix(cfg, p, rows, pools, read_tables, positions, lengths,
                       *, impl: str):
    """'indexed_latent_attention', one group, its latents and index keys
    already in the pools: rows = (q_nope, q_rope, qI, w) at ``positions``
    [B, S] attend the LATENTS their indexer picks, absorbed. ``pools`` =
    (the latent rows [N, T, width], the index keys [N, T, W]), a row's
    pages through ``read_tables`` [B, P], ``lengths`` [B] as
    ``ops.paged_attention`` takes them. Returns (o' [B, S, H, rank], which
    ``latent_finish`` expands, the choice, bool [B, S, context])."""
    q_nope, q_rope, qi, w = rows
    return picked_latent_attention(
        latent_absorb(cfg, p, q_nope), q_rope, qi, w, *pools, read_tables,
        positions, lengths, cfg.indexer, sm_scale=latent_scale(cfg),
        impl=impl, return_selected=True)


def indexed_latent_mixer(cfg, p, x, positions, lengths, pools, read_tables,
                         write_tables, *, impl: str,
                         taps: Optional[List] = None):
    """'indexed_latent_attention' over ONE group of rows: x [B, S, d] at
    ``positions`` [B, S] over a paged pool (``indexed_latent_mix`` says what
    the arguments are; a row's pages are written through ``write_tables``
    [B, P]). Latent rows and index keys are written, then the picked latents
    attended. Returns (y, pools); ``taps`` (a list) is given the choice."""
    T = pools[0].shape[1]
    rows, new = indexed_latent_project(cfg, p, x, positions)
    cells = written_pages(write_tables, positions, T), positions % T
    pools = tuple(write_pages(pool, made, *cells)
                  for pool, made in zip(pools, new))
    o, selected = indexed_latent_mix(cfg, p, rows, pools, read_tables,
                                     positions, lengths, impl=impl)
    if taps is not None:
        taps.append(selected)
    return latent_finish(cfg, p, o), pools


# tokens of a page of the pool an 'indexed_attention' or 'latent_attention'
# layer makes for itself where nobody hands it one (no cache, a contiguous
# cache)
OWN_PAGE_TOKENS = 16


def _own_tables(pools, rows: int):
    """The identity page tables [rows, P] of a pool a layer made for itself:
    page 0 the garbage page, then each row's pages in order."""
    P = (pools[0].shape[0] - 1) // rows
    return (1 + jnp.arange(rows, dtype=jnp.int32)[:, None] * P
            + jnp.arange(P, dtype=jnp.int32)[None])


def _mixer(cfg, kind, p, x, rope, positions, sp_axis, cache, taps):
    """One layer's mixer over the normalized x -> (y, the cache after it).
    ``cache``: None, or what ``models.decode.init_caches`` makes for the
    kind (a contiguous cache is, for the sparse kind, a pool of its own
    whose page table is the identity)."""
    if kind in (ATTENTION, SLIDING):
        return _attn(cfg, p, x, rope, positions, sp_axis, cache, kind)
    from ray_tpu.ops.paged_attention import resolve_impl

    B, S = x.shape[:2]
    if kind == INDEXED:
        # the rows' places in their sequences; ``positions`` (those, or
        # three streams of them) is what the rotation sees
        if cache is None:
            n = 1 + B * -(-S // OWN_PAGE_TOKENS)
            pools = tuple(
                jnp.zeros((n, OWN_PAGE_TOKENS, width), cfg.dtype)
                for width in (cfg.kv_heads * cfg.head_dim,) * 2
                + (index_width(cfg.indexer.indexer_head_dim),))
            length = jnp.zeros((), jnp.int32)
        else:
            pools, length = (cache.k, cache.v, cache.ik), cache.length
        tables = _own_tables(pools, B)
        seq = jnp.broadcast_to(length + jnp.arange(S, dtype=jnp.int32)[None],
                               (B, S))
        y, pools = indexed_mixer(
            cfg, p, x, rope, seq if positions is None else positions, seq,
            jnp.broadcast_to(length, (B,)), pools, tables, tables,
            impl=resolve_impl(cfg), taps=taps)
        return y, cache and dataclasses.replace(
            cache, k=pools[0], v=pools[1], ik=pools[2], length=length + S)
    pos = jnp.broadcast_to(jnp.arange(S)[None] if positions is None
                           else positions, (B, S)).astype(jnp.int32)
    if kind == INDEXED_LATENT:
        if cache is None:
            n = 1 + B * -(-S // OWN_PAGE_TOKENS)
            pools = tuple(
                jnp.zeros((n, OWN_PAGE_TOKENS, width), cfg.dtype)
                for width in (
                    pool_width(cfg.latent_kv_rank, cfg.latent_rope_dim),
                    index_width(cfg.indexer.indexer_head_dim)))
            length = jnp.zeros((), jnp.int32)
        else:
            pools, length = (cache.ckr, cache.ik), cache.length
        tables = _own_tables(pools, B)
        y, pools = indexed_latent_mixer(
            cfg, p, x, pos, jnp.broadcast_to(length, (B,)), pools, tables,
            tables, impl=resolve_impl(cfg), taps=taps)
        return y, cache and dataclasses.replace(
            cache, ckr=pools[0], ik=pools[1], length=length + S)
    if kind == LATENT:
        if cache is None:
            rows, _, made = latent_project(cfg, p, x, pos)
            return latent_expanded(cfg, p, rows, made), None
        tables = _own_tables((cache.ckr,), B)
        y, (ckr,) = latent_mixer(
            cfg, p, x, pos, jnp.broadcast_to(cache.length, (B,)),
            (cache.ckr,), tables, tables, impl=resolve_impl(cfg))
        return y, dataclasses.replace(cache, ckr=ckr,
                                      length=cache.length + S)
    if kind in STATE_KINDS:
        state = ({name: jnp.zeros(shape, jnp.float32) for name, shape
                  in state_shapes(cfg, kind, B).items()} if cache is None
                 else cache.arrays())
        y, state = state_mixer(cfg, kind, p, x, pos, state)
        return y, cache and dataclasses.replace(
            cache, **state, length=cache.length + S)
    if cache is None:
        n = 1 + B * sparse_pool_pages(cfg, S)
        rows = (n, cfg.sparse.kernel_stride, cfg.kv_heads * cfg.head_dim)
        pools = (jnp.zeros(rows, cfg.dtype), jnp.zeros(rows, cfg.dtype),
                 jnp.zeros((n, rows[2]), jnp.float32))
        length = jnp.zeros((), jnp.int32)
    else:
        pools, length = (cache.k, cache.v, cache.means), cache.length
    tables = _own_tables(pools, B)
    y, pools = sparse_mixer(cfg, p, x, pos, jnp.broadcast_to(length, (B,)),
                            pools, tables, tables,
                            impl=resolve_impl(cfg), taps=taps)
    return y, cache and dataclasses.replace(
        cache, k=pools[0], v=pools[1], means=pools[2], length=length + S)


def _residual(cfg, x, y):
    return x + y * cfg.residual_scale if cfg.scale_depth else x + y


def _mlp(cfg, p, x, valid=None, layer=None, ff=None):
    """A layer's feed-forward, ``ff`` of ``cfg.mlp_of`` (None: ``cfg.mlp``,
    the model's own). Returns (y, aux_loss, moe): aux is 0 and moe None
    except for an expert layer, whose router's rule is the config's
    (``moe_scoring``, ``moe_renormalize``, ``moe_routed_scale``:
    ``ops.moe.route``) and whose moe is ``{"counts": [E], "routes": [B, S,
    k]}`` (rows each expert received; the experts each row chose).
    ``valid``: bool [B, S], rows that are not live (the expert layer routes
    them nowhere; a dense mlp takes no notice). ``layer`` (experts only):
    ``p`` is every layer's weights stacked and this is the one to apply
    (``stacked_mlp``)."""
    ff = ff or cfg.mlp
    if ff == "moe":
        from ray_tpu.ops.moe import moe_layer

        y, aux, counts, routes = moe_layer(
            p, x, num_experts=cfg.moe_num_experts, top_k=cfg.moe_top_k,
            renormalize=cfg.moe_renormalize, dtype=cfg.dtype, valid=valid,
            layer=layer, scoring=cfg.moe_scoring,
            routed_scale=cfg.moe_routed_scale, held=cfg.held,
            activation=cfg.moe_activation, expert_groups=cfg.moe_groups,
            top_groups=cfg.moe_top_groups)
        moe = {"counts": counts, "routes": routes}
        if cfg.held:
            # the live rows' choices that fell on experts held elsewhere,
            # counted on the device: with ``counts`` they are every choice
            # made (live rows x top-k exactly, or a row was dropped)
            first, held = cfg.held
            away = (routes < first) | (routes >= first + held)
            if valid is not None:
                away &= valid[..., None]
            moe["left_out"] = away.sum().astype(jnp.int32)
        return y, aux, moe
    if ff == "swiglu":
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
    scan the whole stack that holds the layer and its index there — the
    grouped matmuls cannot fuse the slice as a dense matmul does, and would
    copy the layer's experts."""
    if cfg.mlp_of(i) == NONE:
        return None  # a layer that is a mixer alone
    if cfg.mlp_of(i) != "moe" or not cfg.scan_layers:
        return layer_params["mlp"], None
    i -= cfg.lead_layers
    if cfg.period == 1:
        return body_params(cfg, params)["mlp"], i
    # a pattern of kinds: a stack a place in the period (``init_params``)
    return (body_params(cfg, params)[f"p{i % cfg.period}"]["mlp"],
            i // cfg.period)


def _block(cfg, p, x, rope, positions, sp_axis, kv_cache=None, mlp=None,
           kind=ATTENTION, taps=None, ff=None):
    """``mlp``: ``stacked_mlp``'s pair where the caller walks stacked
    layers one by one; None for ``(p["mlp"], None)``. ``kind``: the layer's
    mixer; ``ff``: its feed-forward (``cfg.mlp_of``; None: ``cfg.mlp``).
    ``taps``: a list that is given what a mixer chose (debug)."""
    if kind == NONE:  # a layer that is a feed-forward alone
        return _after_mixer(cfg, p, x, None, kv_cache, mlp, ff)
    a, new_cache = _mixer(cfg, kind, p["attn"], _norm(cfg, p["ln1"], x),
                          rope, positions, sp_axis, kv_cache, taps)
    return _after_mixer(cfg, p, x, a, new_cache, mlp, ff)


def _after_mixer(cfg, p, x, a, new_cache=None, mlp=None, ff=None):
    """The rest of a block, given what its mixer made of x (None: the
    layer has none): ``_block``'s results. A layer without a feed-forward
    (``ff`` is ``NONE``) ends behind its mixer."""
    if a is not None:
        x = _residual(cfg, x, output_norm(cfg, p, "ln1_out", a))
    if ff == NONE:
        return x, new_cache, 0.0, None
    mlp_p, layer = mlp or (p["mlp"], None)
    m, aux, moe = _mlp(cfg, mlp_p, _norm(cfg, p["ln2"], x), layer=layer,
                       ff=ff)
    x = _residual(cfg, x, output_norm(cfg, p, "ln2_out", m))
    return x, new_cache, aux, moe


def output_norm(cfg, p, name: str, y):
    """A sublayer's output ``y`` through the layer's norm ``name`` where the
    block has norms behind its sublayers (``cfg.output_norms``), else as it
    is."""
    return _norm(cfg, p[name], y) if cfg.output_norms else y


# the matrices of a block's mixer and mlp: what their products cast to
# ``cfg.dtype`` where they use it (a leaf that is not named here keeps its
# type, and costs two streams a second gather, never a digit)
_MATRICES = frozenset({"wq", "wk", "wv", "wo", "wg", "wc", "wi_q", "wi_k",
                       "wi_w", "w_gate", "w_up", "w_up_t", "w_down", "w_in",
                       "w_out"})


def _block_streams(cfg, p, hs, rope, positions, sp_axis, kind=ATTENTION,
                   ff=None):
    """``_block`` on each stream of the residual (``streams``), one
    ``_block``'s results a stream. Two streams take every matrix as the
    products do, ``cfg.dtype``, from ONE cast (a stream's own
    ``astype(cfg.dtype)`` is then nothing): the matrix is gathered once for
    both, and its gradient is the two products' sum in that type before it
    is reduced over ``fsdp`` — else two gathers and two reduces a matrix.
    And they meet in the attention kernel, which takes both streams' rows
    as the ONE call a layer it was (its batching, and what a trace counts
    by its name, are a whole chip's rows): the products on either side of
    it, whose reduces are the ones to hide, stay a stream's own."""
    if len(hs) == 1:
        return [_block(cfg, p, hs[0], rope, positions, sp_axis, kind=kind,
                       ff=ff)]
    p = {**p, **{part: {name: w.astype(cfg.dtype) if name in _MATRICES else w
                        for name, w in p[part].items()}
                 for part in ("attn", "mlp") if part in p}}
    if kind == NONE:
        return [_after_mixer(cfg, p, h, None, ff=ff) for h in hs]
    normed = [_norm(cfg, p["ln1"], h) for h in hs]
    if (kind != ATTENTION or cfg.window(kind) is not None
            or cfg.attn_impl == "ring" and sp_axis is not None):
        mixed = [_mixer(cfg, kind, p["attn"], x, rope, positions, sp_axis,
                        None, None)[0] for x in normed]
    else:
        # goes, with ``_plain_attention`` and ``_attn_out``, once a trace's
        # reader takes a kernel call's rows from the event: a call a
        # stream, the branch above, is the faster form (ROADMAP S5 (1b))
        q, k, v = (_whole(rows) for rows in zip(*(
            _qkv(cfg, p["attn"], x, rope, positions, kind) for x in normed)))
        mixed = [_attn_out(cfg, p["attn"], o)
                 for o in _halves(_plain_attention(cfg, q, k, v))]
    return [_after_mixer(cfg, p, h, a, ff=ff) for h, a in zip(hs, mixed)]


def _residual_layout(x):
    """x [B, S, d] where the residual lives under a mesh in scope: the
    batch over the axes the rule table gives it, d whole. Said at the
    embedding's output and at every layer's, or the partitioner takes the
    layout from whatever stands nearest — the embedding table's split of d
    over ``fsdp``, unless a kernel's ``shard_map`` happens to pin the batch
    — and reduces activations over ``fsdp`` that it need only have gathered
    weights for. No mesh in scope: x as it is."""
    batch = free_axes("batch", x.shape[0])
    if batch is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(batch, None, None))


def hides_reduces(cfg) -> bool:
    """Whether a block under the mesh in scope has a reduce that a second
    stream can hide: it reduces over ``tp`` (``heads`` has an axis there),
    and it has no experts — the router's auxiliary loss and the experts'
    groups are statistics of the whole batch, not of a row."""
    return cfg.mlp != "moe" and free_axes("heads", cfg.num_heads) is not None


def streams(cfg, rows: int) -> int:
    """How many streams the layer scan carries a batch of ``rows`` as under
    the mesh in scope: two where ``hides_reduces`` and every batch group's
    rows are even — the halves share nothing but the weights, so one half's
    reduce can lie under the other half's matmuls — else one."""
    even = rows % (2 * free_parts("batch", rows)) == 0
    return 2 if hides_reduces(cfg) and even else 1


def _halves(x):
    """x [B, ...] -> the two halves of every batch group's rows, [B / 2,
    ...] each. NOT ``x[:B // 2]`` and the rest: those are a group (an
    ``fsdp`` rank's rows) each, and every activation would cross ``fsdp``
    to be spread again."""
    parts = x.reshape(free_parts("batch", x.shape[0]), 2, -1, *x.shape[1:])
    return tuple(parts[:, i].reshape(-1, *x.shape[1:]) for i in range(2))


def _whole(halves):
    """``_halves`` undone: the rows back in the batch's order."""
    rows, rest = sum(h.shape[0] for h in halves), halves[0].shape[1:]
    groups = free_parts("batch", rows)
    parts = jnp.stack([h.reshape(groups, -1, *rest) for h in halves], axis=1)
    return parts.reshape(rows, *rest)


def embed(cfg, params, tokens):
    x = params["embed"]["table"].astype(cfg.dtype)[tokens]
    return x * cfg.scale_emb if cfg.scale_emb != 1.0 else x


def final_hidden(cfg, params, x):
    """The last norm (and MiniCPM's division of what the head sees)."""
    x = _norm(cfg, params["final_norm"], x)
    if cfg.dim_model_base:
        x = x / (cfg.embed_dim / cfg.dim_model_base)
    return x


def project(cfg, params, x):
    """hidden [B, S, d] (after ``final_hidden``) -> logits [B, S, vocab]."""
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", x,
                          params["embed"]["table"].astype(cfg.dtype))
    return jnp.einsum("bsd,dv->bsv", x,
                      params["lm_head"]["kernel"].astype(cfg.dtype))


def rope_table(cfg):
    """The (cos, sin) tables plain attention rotates by, None for a model
    none of whose layers does (a table is as long as the context)."""
    if (cfg.pos in ("learned", "none")
            or not {ATTENTION, SLIDING, INDEXED} & set(cfg.kinds)):
        return None
    if cfg.rope_parameters is not None or cfg.rope_scaling is not None:
        return COMPUTED  # a rule a kind: angles from the positions
    return rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)


# what a trace calls a pass's body and the gate that reads the passes
LOOP_PASS, LOOP_EXIT_GATE = "loop_pass", "loop_exit_gate"


def exit_shares(cfg, params, states):
    """states [T, ..., d], each pass's state behind the final norm -> p [T,
    ...] float32, the share of a token that leaves at each pass: the gate
    ``g_t = sigmoid(h_t . w + b)``, ``p_t = g_t prod_{j<t} (1 - g_j)`` for
    t < T and ``p_T`` the rest."""
    with jax.named_scope(LOOP_EXIT_GATE):
        gate = params["exit_gate"]
        g = jax.nn.sigmoid(jnp.einsum(
            "t...d,d->t...", states.astype(jnp.float32),
            gate["w"].astype(jnp.float32)) + gate["b"].astype(jnp.float32))
        stayed = jnp.cumprod(1.0 - g, axis=0)  # prod_{j<=t} (1 - g_j)
        before = jnp.concatenate([jnp.ones_like(stayed[:1]), stayed[:-1]])
        return jnp.concatenate([(g * before)[:-1], before[-1:]])


def exit_pass(cfg, shares):
    """shares [T, ...] -> the pass a token leaves at, int32 [...] counted
    from 1: the first t whose ``p_1 + ... + p_t`` reaches
    ``cfg.exit_threshold``, else the last (the sums only grow, so that is
    one more than the passes before the last that fall short)."""
    short = jnp.cumsum(shares[:-1], axis=0) < cfg.exit_threshold
    return 1 + jnp.sum(short, axis=0, dtype=jnp.int32)


def exit_state(cfg, params, states):
    """(the state [..., d] the head projects, the pass [...] it is of) out
    of every pass's state [T, ..., d]."""
    exits = exit_pass(cfg, exit_shares(cfg, params, states))
    picked = jnp.take_along_axis(states, (exits - 1)[None, ..., None], axis=0)
    return picked[0], exits


def _looped_cached(cfg, params, x, rope, positions, caches):
    """A looped stack over its CONTIGUOUS cache (``decode.init_caches``: ONE
    cache whose k and v hold a (pass, layer) a leading row, heads-major,
    [T * L, B, Hkv, max_len, D]): the layer scan inside a scan over passes,
    the cache the loops' CARRY as the paged loop carries its pool — a
    (pass, layer)'s row is cut out for its block and written back where it
    lies, so the program holds the cache once (as xs and ys of the scans it
    is laid out anew twice; positions-major, once more in the order
    attention reads it). Returns (every pass's state behind the final norm
    [T, B, S, d], the caches)."""
    (cache,) = caches
    L = cfg.num_layers
    swap = lambda a: jnp.swapaxes(a, 1, 2)  # [B, Hkv, len, D] <-> the block's

    def layer(carry, xs):
        x, k, v = carry
        p, row = xs
        x, new, _, _ = _block(
            cfg, p, x, rope, positions, None,
            dataclasses.replace(cache, k=swap(k[row]), v=swap(v[row])))
        k, v = k.at[row].set(swap(new.k)), v.at[row].set(swap(new.v))
        return (x, k, v), None

    def one_pass(carry, t):
        with jax.named_scope(LOOP_PASS):
            (x, k, v), _ = jax.lax.scan(
                layer, carry, (body_params(cfg, params),
                               t * L + jnp.arange(L)))
            x = final_hidden(cfg, params, x)
        return (x, k, v), x

    (_, k, v), states = jax.lax.scan(one_pass, (x, cache.k, cache.v),
                                     jnp.arange(cfg.loop_passes))
    return states, [dataclasses.replace(cache, k=k, v=v,
                                        length=cache.length + x.shape[1])]


def forward(cfg: TransformerConfig, params, tokens, *, positions=None,
            sp_axis: Optional[str] = None, kv_caches=None,
            return_aux: bool = False, return_hidden: bool = False,
            return_routes: bool = False, return_selected: bool = False,
            return_exit_pass: bool = False):
    """tokens [B, S] int32 -> logits [B, S, vocab].

    return_hidden: skip the vocab projection and return the post-final-norm
    hidden states [B, S, D] (with aux, or with the caches where there are
    any) — used by the fused-CE loss path and by ``decode.prefill``, which
    projects the last position alone.
    return_routes (debug, mlp='moe' without kv_caches): also return the
    experts every token chose in every EXPERT layer (the leading dense ones
    choose nothing), int32 [L, B, S, k] — top-k is
    discontinuous, so a comparison with another implementation has to be
    made on the same choices.
    return_selected (debug, without kv_caches): also return the blocks every
    query of every 'minicpm4' layer attended, bool [layers of the kind, B,
    S, Hkv, NB], or the tokens every query of every 'indexed_attention' or
    'indexed_latent_attention' layer attended, bool [layers of the kind, B,
    S, context], for the same reason.
    return_exit_pass (debug, ``loop_passes`` > 1 without kv_caches): also
    return the pass each position left at, int32 [B, S] counted from 1 —
    the threshold is a step, so a comparison with another implementation
    has to be made on the same exits.

    sp_axis: when running inside shard_map with sequence sharded over that
    axis, attention goes through the ring kernel and `positions` must be the
    global positions of this shard.
    kv_caches: optional list/stack of per-layer decode caches (see
    ray_tpu.models.decode); when set, runs in incremental-decode mode.
    """
    x = embed(cfg, params, tokens)
    if cfg.pos == "learned":
        pos = positions if positions is not None else jnp.arange(tokens.shape[1])
        x = x + params["pos_embed"]["table"].astype(cfg.dtype)[pos]
    x = _residual_layout(x)
    rope = rope_table(cfg)
    kinds = cfg.kinds

    remat = lambda fn: fn
    if cfg.remat and kv_caches is None and not return_selected:
        policy = remat_policy(cfg.remat_policy)
        # ``COMPUTED`` is a name, not an array: static like the config
        static = (0, 3, 5) if rope is COMPUTED else (0, 5)
        remat = lambda fn: jax.checkpoint(fn, static_argnums=static,
                                          policy=policy)
    block_fn = lambda i: remat(functools.partial(
        _block, kind=kinds[i], ff=cfg.mlp_of(i)))

    if return_routes and (cfg.mlp != "moe" or kv_caches is not None):
        raise ValueError("return_routes needs mlp='moe' and no kv_caches")
    if return_selected and (
            not {SPARSE, INDEXED, INDEXED_LATENT} & set(kinds)
            or kv_caches is not None):
        raise ValueError(
            "return_selected needs a 'minicpm4', an 'indexed_attention' or "
            "an 'indexed_latent_attention' layer and no kv_caches")
    if return_exit_pass and (not cfg.looped or kv_caches is not None):
        raise ValueError("return_exit_pass needs loop_passes > 1 and no "
                         "kv_caches")
    new_caches = None
    aux_total = 0.0
    routes = None
    states = None  # a looped stack: each pass's state behind the final norm
    taps = [] if return_selected else None
    if cfg.looped and kv_caches is not None:
        states, new_caches = _looped_cached(cfg, params, x, rope, positions,
                                            kv_caches)
    elif cfg.scan_layers and kv_caches is None and not return_selected:
        period, lead = cfg.period, cfg.lead_layers
        stream_fn = lambda i: remat(functools.partial(
            _block_streams, kind=kinds[i], ff=cfg.mlp_of(i)))
        fns = [stream_fn(lead + j) for j in range(period)]
        # the carry: the residual as one stream, or as the two halves of
        # every chip's rows (``streams``; positions a row would have to be
        # halved with it: none does)
        split = (streams(cfg, x.shape[0]) == 2 and not return_routes
                 and not cfg.looped
                 and (positions is None or positions.ndim == 1))
        carried = (tuple(_residual_layout(h) for h in _halves(x))
                   if split else (x,))
        # the leading layers, one by one (a handful: a scan of their own
        # would be a second program for a layer or two); they choose
        # nothing, so ``routes`` holds the scanned layers' alone
        for i in range(lead):
            outs = stream_fn(i)(cfg, layer_params(cfg, params, i), carried,
                                rope, positions, sp_axis)
            carried = tuple(_residual_layout(h) for h, _, _, _ in outs)

        def body(carry, layer_params):
            hs, aux_acc = carry
            chosen = []
            for j, fn in enumerate(fns):
                outs = fn(cfg, layer_params if period == 1
                          else layer_params[f"p{j}"], hs, rope, positions,
                          sp_axis)
                hs = tuple(h for h, _, _, _ in outs)
                for _, _, aux, _ in outs:
                    aux_acc = aux_acc + aux
                if return_routes and outs[0][3] is not None:
                    chosen.append(outs[0][3]["routes"])
            return (tuple(_residual_layout(h) for h in hs), aux_acc), (
                jnp.stack(chosen) if return_routes else None)
        if cfg.looped:
            # the scan of the scan: the same stacked weights every pass, so
            # their gradients add up over the passes by construction
            def one_pass(carry, _):
                with jax.named_scope(LOOP_PASS):
                    ((x,), aux), _ = jax.lax.scan(
                        body, carry, body_params(cfg, params))
                    x = final_hidden(cfg, params, x)
                return ((x,), aux), x
            (carried, aux_total), states = jax.lax.scan(
                one_pass, (carried, 0.0), None, length=cfg.loop_passes)
        else:
            (carried, aux_total), routes = jax.lax.scan(
                body, (carried, 0.0), body_params(cfg, params))
        x = _residual_layout(_whole(carried)) if split else carried[0]
        if return_routes:  # [steps, layers a step, ...] -> a layer a row
            routes = routes.reshape(-1, *routes.shape[2:])
    else:
        new_caches = [] if kv_caches is not None else None
        per_layer = []
        for i, kind in enumerate(kinds):
            layer_p = layer_params(cfg, params, i)
            if kv_caches is not None or return_selected:
                x, c, aux, moe = _block(
                    cfg, layer_p, x, rope, positions, sp_axis,
                    kv_caches[i] if kv_caches is not None else None,
                    stacked_mlp(cfg, params, layer_p, i), kind, taps,
                    cfg.mlp_of(i))
            else:
                x, c, aux, moe = block_fn(i)(cfg, layer_p, x, rope,
                                             positions, sp_axis)
            aux_total = aux_total + aux
            if new_caches is not None:
                new_caches.append(c)
            if return_routes and moe is not None:
                per_layer.append(moe["routes"])
        if return_routes:
            routes = jnp.stack(per_layer)

    if cfg.looped:  # the final norm closed every pass
        x, exits = exit_state(cfg, params, states)
    else:
        x = final_hidden(cfg, params, x)
    if return_hidden:
        return x, (new_caches if kv_caches is not None else aux_total)
    logits = project(cfg, params, x)
    if kv_caches is not None:
        return logits, new_caches
    if return_exit_pass:
        return logits, exits
    if return_routes:
        return logits, routes
    if return_selected:
        return logits, jnp.stack(taps)
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
    if set(cfg.kinds) != {ATTENTION} or cfg.rope_parameters is not None:
        raise ValueError(
            "tensor parallelism knows plain attention layers alone, not "
            f"cfg.layer_kinds={cfg.layer_kinds}")
    if cfg.looped or cfg.output_norms:
        raise ValueError(
            "tensor parallelism knows the stack applied once, a norm before "
            "each sublayer and none behind it, not cfg.loop_passes="
            f"{cfg.loop_passes}, cfg.output_norms={cfg.output_norms}")
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
