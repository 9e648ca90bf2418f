"""Named model configs. Sizes match the public architectures; dtypes default
to bf16 compute over f32 params (the TPU-native training recipe)."""

from __future__ import annotations

import jax.numpy as jnp

from ray_tpu.models.transformer import TransformerConfig


def gpt2_small(**overrides) -> TransformerConfig:
    """GPT-2 124M: learned positions, LayerNorm, gelu MLP, tied embeddings."""
    kw = dict(
        vocab_size=50257, num_layers=12, embed_dim=768, num_heads=12,
        max_seq_len=1024, norm="layernorm", pos="learned", mlp="gelu",
        tie_embeddings=True, norm_eps=1e-5,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def gpt2_medium(**overrides) -> TransformerConfig:
    kw = dict(
        vocab_size=50257, num_layers=24, embed_dim=1024, num_heads=16,
        max_seq_len=1024, norm="layernorm", pos="learned", mlp="gelu",
        tie_embeddings=True, norm_eps=1e-5,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def gpt_1b(**overrides) -> TransformerConfig:
    """~0.9B-param LLaMA-style config (RMSNorm, RoPE, SwiGLU, tied
    embeddings): the single-chip bridge toward the llama3_8b FSDP target
    (BASELINE.md) — big enough that MFU reflects MXU behavior at depth,
    small enough that params+adam+grads fit a 16GB v5e with remat."""
    kw = dict(
        vocab_size=32000, num_layers=16, embed_dim=2048, num_heads=16,
        num_kv_heads=8, mlp_dim=5632, max_seq_len=2048, norm="rmsnorm",
        pos="rope", mlp="swiglu", rope_theta=10000.0, tie_embeddings=True,
        norm_eps=1e-5,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def llama3_8b(**overrides) -> TransformerConfig:
    """Llama-3-8B: RoPE(theta=500k), RMSNorm, SwiGLU, GQA 32/8, vocab 128256."""
    kw = dict(
        vocab_size=128256, num_layers=32, embed_dim=4096, num_heads=32,
        num_kv_heads=8, mlp_dim=14336, max_seq_len=8192, norm="rmsnorm",
        pos="rope", mlp="swiglu", rope_theta=500000.0, tie_embeddings=False,
        norm_eps=1e-5,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def llama_debug(**overrides) -> TransformerConfig:
    """Tiny LLaMA-shaped config for tests and multichip dry runs."""
    kw = dict(
        vocab_size=256, num_layers=2, embed_dim=64, num_heads=4,
        num_kv_heads=2, mlp_dim=128, max_seq_len=128, norm="rmsnorm",
        pos="rope", mlp="swiglu", tie_embeddings=False,
        dtype=jnp.float32,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def moe_debug(**overrides) -> TransformerConfig:
    """Tiny OLMoE-shaped config (allenai/OLMoE-1B-7B: SwiGLU experts,
    dropless top-k routing with weights that are not renormalized, RMSNorm
    on the whole projected q and k) for tests and expert-parallel dry
    runs: top-3 of 8 experts."""
    kw = dict(
        vocab_size=256, num_layers=2, embed_dim=64, num_heads=4,
        num_kv_heads=2, mlp="moe", mlp_dim=128, moe_num_experts=8,
        moe_top_k=3, moe_renormalize=False, qk_norm=True, max_seq_len=128,
        norm="rmsnorm", pos="rope", tie_embeddings=False, dtype=jnp.float32,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def minicpm_sala_debug(**overrides) -> TransformerConfig:
    """Tiny MiniCPM-SALA-shaped config (openbmb/MiniCPM-SALA: layers of two
    kinds in the published 1:3, block-selected sparse attention and decayed
    linear attention, q/k norm a head, output gates, the MiniCPM scales) for
    tests: two periods, and a selection scaled down with the context so
    that it is ACTIVE past 48 tokens."""
    kw = dict(
        vocab_size=256, num_layers=8, embed_dim=64, num_heads=4,
        num_kv_heads=2, mlp="swiglu", mlp_dim=128, max_seq_len=1024,
        layer_kinds=("minicpm4",) + ("lightning-attn",) * 3
        + ("minicpm4",) + ("lightning-attn",) * 3,
        sparse_config=dict(kernel_size=8, kernel_stride=4, block_size=16,
                           topk=2, init_blocks=1, window_size=32,
                           dense_len=48),
        head_qk_norm=True, scale_emb=12.0, scale_depth=1.4,
        scale_depth_layers=32, dim_model_base=16, norm="rmsnorm",
        pos="rope", rope_theta=10000.0, norm_eps=1e-6, tie_embeddings=False,
        dtype=jnp.float32,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def brumby_debug(**overrides) -> TransformerConfig:
    """Tiny Brumby-shaped config (manifestai/Brumby-14B-Base: the dense
    Llama-shaped block with q/k norm a head and RoPE, whose every mixer is
    power retention of degree 2 on the state of a K/V head that a group of
    query heads shares) for tests: five query heads over each of two K/V
    heads, as the published 40 over 8. Every layer is 'power-retention',
    however many ``num_layers`` says."""
    kw = dict(
        vocab_size=256, num_layers=2, embed_dim=320, num_heads=10,
        num_kv_heads=2, mlp="swiglu", mlp_dim=256, max_seq_len=1024,
        head_qk_norm=True, norm="rmsnorm", pos="rope", rope_theta=1000000.0,
        norm_eps=1e-6, tie_embeddings=False, dtype=jnp.float32,
    )
    kw.update(overrides)
    kw.setdefault("layer_kinds", ("power-retention",) * kw["num_layers"])
    return TransformerConfig(**kw)


def mellum_debug(**overrides) -> TransformerConfig:
    """Tiny Mellum-2-shaped config (JetBrains/Mellum2-12B-A2.5B-Instruct:
    three 'sliding_attention' layers to every 'full_attention' one, each
    kind with a RoPE rule of its own — plain for the window layers, YaRN
    for the full ones — a head size that is a field and not ``embed_dim //
    num_heads``, grouped K/V heads, no q/k norm, and dropless top-k experts
    whose weights are renormalized) for tests: a window of 24 tokens, YaRN
    over an original context of 32. The kinds follow the published pattern
    for however many layers ``num_layers`` says, unless ``layer_kinds`` is
    given."""
    kw = dict(
        vocab_size=256, num_layers=8, embed_dim=64, num_heads=4,
        num_kv_heads=2, head_dim=32, mlp="moe", mlp_dim=64,
        moe_num_experts=8, moe_top_k=3, moe_renormalize=True,
        sliding_window=24, max_seq_len=256, norm="rmsnorm", pos="rope",
        norm_eps=1e-6, tie_embeddings=False, dtype=jnp.float32,
        rope_parameters={
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
                "original_max_position_embeddings": 32, "beta_fast": 4.0,
                "beta_slow": 1.0, "attention_factor": 1.1386294361119891},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 10000.0}},
    )
    kw.update(overrides)
    kw.setdefault("layer_kinds", tuple(
        "full_attention" if i % 4 == 3 else "sliding_attention"
        for i in range(kw["num_layers"])))
    return TransformerConfig(**kw)


def keye_debug(**overrides) -> TransformerConfig:
    """Tiny Keye-VL-2.0-shaped language model (Kwai-Keye/Keye-VL-2.0-30B-A3B:
    every layer 'indexed_attention' — grouped K/V heads with an RMSNorm over
    each head's q and k, RoPE in three position streams (``rope_scaling``'s
    ``mrope_section``; text feeds the three alike), and a learned indexer
    (``sa_config``) that picks the ``topk`` tokens a query attends from one
    cached index key a token — under dropless top-k experts whose weights
    are renormalized) for tests: 4 index heads of 16, a choice of 16 tokens.
    The vision tower is not part of it. Every layer is of the kind, however
    many ``num_layers`` says, unless ``layer_kinds`` is given."""
    kw = dict(
        vocab_size=256, num_layers=2, embed_dim=64, num_heads=4,
        num_kv_heads=2, head_dim=32, mlp="moe", mlp_dim=48,
        moe_num_experts=8, moe_top_k=3, moe_renormalize=True,
        head_qk_norm=True, max_seq_len=256, norm="rmsnorm", pos="rope",
        norm_eps=1e-6, rope_theta=10000.0, tie_embeddings=False,
        dtype=jnp.float32,
        sa_config={"indexer_num_heads": 4, "indexer_head_dim": 16,
                   "indexer_num_kv_heads": 1, "topk": 16,
                   "q_chunk_size": 8, "kv_chunk_size": 8},
        rope_scaling={"mrope_section": [4, 6, 6], "rope_type": "default",
                      "type": "default"},
    )
    kw.update(overrides)
    kw.setdefault("layer_kinds", ("indexed_attention",) * kw["num_layers"])
    return TransformerConfig(**kw)


def glm_moe_lite_debug(**overrides) -> TransformerConfig:
    """Tiny GLM-4.7-Flash-shaped config (zai-org/GLM-4.7-Flash,
    ``glm4_moe_lite``: every layer 'latent_attention' — the query through a
    low-rank bottleneck with a norm of its own, keys and values from one
    normed latent a token plus one rotated key the heads share, heads of
    unequal q/k and v parts — under a leading dense SwiGLU layer and then
    expert layers whose router scores by sigmoid, chooses by score + bias,
    renormalizes and scales the weights, beside one shared expert) for
    tests: 4 heads of 24 + 8 q/k values and 32 v values over a latent of 32,
    8 experts of 48 top-3 times 1.8. The multi-token-prediction block is
    not part of it. Every layer is of the kind, however many ``num_layers``
    says, unless ``layer_kinds`` is given."""
    kw = dict(
        vocab_size=256, num_layers=3, embed_dim=64, num_heads=4,
        latent_q_rank=48, latent_kv_rank=32, latent_nope_dim=24,
        latent_rope_dim=8, latent_v_dim=32, mlp="moe", mlp_dim=48,
        moe_num_experts=8, moe_top_k=3, moe_renormalize=True,
        moe_scoring="sigmoid", moe_routed_scale=1.8, moe_shared_experts=1,
        moe_dense_layers=1, dense_mlp_dim=96, max_seq_len=256,
        norm="rmsnorm", pos="rope", norm_eps=1e-5, rope_theta=10000.0,
        tie_embeddings=False, dtype=jnp.float32,
    )
    kw.update(overrides)
    kw.setdefault("layer_kinds", ("latent_attention",) * kw["num_layers"])
    return TransformerConfig(**kw)


def deepseek_v32_debug(**overrides) -> TransformerConfig:
    """Tiny DeepSeek-V3.2-Exp-shaped config (``deepseek_v32``: every layer
    'indexed_latent_attention' — latent attention whose queries attend the
    ``index_topk`` latents a learned indexer picks, the index queries out of
    the query's bottleneck, the first ``latent_rope_dim`` values of an index
    head rotated, YaRN on every rotated part and in the softmax scale —
    under a leading dense SwiGLU layer and then expert layers whose sigmoid
    router chooses among the best ``moe_top_groups`` of ``moe_groups``
    groups of experts, beside one shared expert) for tests: 4 heads of 24 +
    8 q/k values and 16 v values over a latent of 32, 4 index heads of 16
    that pick 24 tokens, 16 experts of 48 in 4 groups of which 2 stay, top 3
    times 2.5. The multi-token-prediction block is not part of it. Every
    layer is of the kind unless ``layer_kinds`` is given."""
    kw = dict(
        vocab_size=256, num_layers=3, embed_dim=64, num_heads=4,
        latent_q_rank=48, latent_kv_rank=32, latent_nope_dim=24,
        latent_rope_dim=8, latent_v_dim=16, index_num_heads=4,
        index_head_dim=16, index_topk=24, mlp="moe", mlp_dim=48,
        moe_num_experts=16, moe_top_k=3, moe_renormalize=True,
        moe_scoring="sigmoid", moe_routed_scale=2.5, moe_shared_experts=1,
        moe_groups=4, moe_top_groups=2, moe_dense_layers=1,
        dense_mlp_dim=96, max_seq_len=256, norm="rmsnorm", pos="rope",
        norm_eps=1e-6, rope_theta=10000.0, tie_embeddings=False,
        dtype=jnp.float32,
        rope_scaling={"type": "yarn", "factor": 40, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 32},
    )
    kw.update(overrides)
    kw.setdefault("layer_kinds",
                  ("indexed_latent_attention",) * kw["num_layers"])
    return TransformerConfig(**kw)


def ouro(**overrides) -> TransformerConfig:
    """Ouro-2.6B (ByteDance/Ouro-2.6B, ``model_type: ouro``; "Scaling Latent
    Reasoning via Looped Language Models", arXiv:2510.25741) at its
    published sizes: 48 Llama-shaped layers (16 heads of 128, no grouping,
    SwiGLU of 5632) that every token goes through ``loop_passes`` = 4 times
    with the same weights, a norm on each sublayer's OUTPUT beside the two
    on their inputs, the final norm behind every pass and a learned gate
    whose exit shares pick the pass the head projects (the published
    threshold 1: the last unless a gate saturates)."""
    kw = dict(
        vocab_size=49152, num_layers=48, embed_dim=2048, num_heads=16,
        num_kv_heads=16, head_dim=128, mlp_dim=5632, max_seq_len=65536,
        loop_passes=4, exit_threshold=1.0, output_norms=True,
        norm="rmsnorm", pos="rope", mlp="swiglu", rope_theta=1000000.0,
        norm_eps=1e-6, tie_embeddings=False,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def ouro_debug(**overrides) -> TransformerConfig:
    """Tiny Ouro-shaped config (``ouro``'s mechanisms, for tests): three
    layers gone through four times, norms behind the sublayers, the exit
    gate."""
    kw = dict(
        vocab_size=256, num_layers=3, embed_dim=64, num_heads=4,
        num_kv_heads=4, head_dim=16, mlp_dim=128, max_seq_len=128,
        dtype=jnp.float32,
    )
    kw.update(overrides)
    return ouro(**kw)


def nemotron_h(**overrides) -> TransformerConfig:
    """NVIDIA-Nemotron-3-Nano-30B-A3B (``model_type: nemotron_h``) at its
    published sizes: 52 layers that are ONE sublayer each, as its
    ``hybrid_override_pattern`` lists them — 23 Mamba-2 mixers ('M': 64
    heads of 64 over 8 groups, a state of 128 a head, a convolution of 4
    taps, blocks of 128), 6 attention mixers ('*': 32 query and 2 K/V heads
    of 128 over a hidden size of 2688, no positional rotation) and 23
    expert feed-forwards ('E': 128 two-matrix ``relu^2`` experts of 1856,
    the sigmoid router with a bias in the choice, top 6 renormalized times
    2.5, beside one shared expert of 3712) — 31.58B parameters. What one
    chip holds of it (fewer layers, a share of the experts and of the
    vocabulary) is the caller's ``overrides``."""
    kw = dict(
        vocab_size=131072, num_layers=52,
        layer_pattern="MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        embed_dim=2688, num_heads=32, num_kv_heads=2, head_dim=128,
        ssm_num_heads=64, ssm_head_dim=64, ssm_groups=8, ssm_state_dim=128,
        ssm_conv_kernel=4, ssm_chunk=128, mlp="moe", mlp_dim=1856,
        moe_num_experts=128, moe_top_k=6, moe_renormalize=True,
        moe_scoring="sigmoid", moe_routed_scale=2.5, moe_shared_experts=1,
        moe_shared_dim=3712, moe_activation="relu2", max_seq_len=262144,
        norm="rmsnorm", pos="none", norm_eps=1e-5, tie_embeddings=False,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


def nemotron_h_debug(**overrides) -> TransformerConfig:
    """Tiny Nemotron-H-shaped config (``nemotron_h``'s mechanisms, for
    tests): seven layers 'MEM*EME', so a mixer directly before a mixer
    ('M*'), a mixer before a feed-forward ('ME') and attention before one
    ('*E') all occur; 8 state-space heads of 8 over 2 groups with a state of
    16 and blocks of 16 tokens, 4 query and 2 K/V heads of 16 without
    rotation, 8 ``relu^2`` experts of 48 top 3 times 2.5 beside a shared one
    of 96."""
    kw = dict(
        vocab_size=256, num_layers=7, layer_pattern="MEM*EME", embed_dim=64,
        num_heads=4, num_kv_heads=2, head_dim=16, ssm_num_heads=8,
        ssm_head_dim=8, ssm_groups=2, ssm_state_dim=16, ssm_conv_kernel=4,
        ssm_chunk=16, mlp="moe", mlp_dim=48, moe_num_experts=8, moe_top_k=3,
        moe_renormalize=True, moe_scoring="sigmoid", moe_routed_scale=2.5,
        moe_shared_experts=1, moe_shared_dim=96, moe_activation="relu2",
        max_seq_len=256, norm="rmsnorm", pos="none", norm_eps=1e-5,
        tie_embeddings=False, dtype=jnp.float32,
    )
    kw.update(overrides)
    return TransformerConfig(**kw)


# ---------------------------------------------------------------------------
# pipeline stage partition (MPMD train.PipelineTrainer shards)
#
# Splits a transformer's blocks into S uniform stages: stage 0 owns the
# embedding (+ learned positions), the last stage owns the final norm +
# lm_head + loss, and the blocks spread as evenly as possible (the
# remainder lands on the EARLIEST stages, which also carry the lighter
# embed/no-head ends). With ``virtual_stages=V`` > 1 the split is into
# S*V NON-CONTIGUOUS chunks for the interleaved 1F1B schedule: stage s
# owns chunks s, s+S, s+2S, ... (arXiv:2412.14374's multi-chunk-per-
# stage trick — the trainer's bubble shrinks roughly by 1/V). Every
# callable here is a module-level function under functools.partial, so
# stage specs pickle cleanly into the stage actors.


def pipeline_splits(num_layers: int, num_stages: int):
    """[(lo, hi)) block ranges for S uniform stages."""
    if num_stages < 2:
        raise ValueError("a pipeline needs >= 2 stages")
    if num_layers < num_stages:
        raise ValueError(
            f"cannot split {num_layers} blocks into {num_stages} stages")
    base, rem = divmod(num_layers, num_stages)
    splits, lo = [], 0
    for s in range(num_stages):
        hi = lo + base + (1 if s < rem else 0)
        splits.append((lo, hi))
        lo = hi
    return splits


def _check_pipeline_cfg(cfg) -> None:
    # name the offending CONFIG FIELD and the fix: these raise from deep
    # inside trainer/stage-def builds, where "pipeline stages need X"
    # without the field left users grepping for which knob to flip
    if cfg.tie_embeddings:
        raise ValueError(
            "pipeline_stage_defs: cfg.tie_embeddings=True is unsupported "
            "— the embedding table lives on stage 0 and the lm_head on "
            "the last stage, so a tied table's gradient would need "
            "summing across both ends every flush. Build the config with "
            "tie_embeddings=False (e.g. "
            "presets.gpt2_small(tie_embeddings=False))")
    if cfg.mlp == "moe":
        raise ValueError(
            "pipeline_stage_defs: cfg.mlp='moe' is unsupported — the "
            "router's load-balancing aux loss would need summing across "
            "stages every microbatch. Use a dense mlp ('gelu'/'swiglu'), "
            "or train MoE configs with the SPMD expert-parallel path")
    if cfg.looped:
        raise ValueError(
            "pipeline_stage_defs: cfg.loop_passes > 1 is unsupported — "
            "every pass would cross every stage again, and the last "
            "stage's final norm and exit gate close each of them")
    if cfg.layer_kinds and set(cfg.layer_kinds) != {"attention"}:
        raise ValueError(
            "pipeline_stage_defs: cfg.layer_kinds other than 'attention' "
            "is unsupported — a stage's blocks are stacked as one kind")


def _resolve_virtual_stages(virtual_stages, num_stages: int,
                            num_layers: int) -> int:
    """Validate + default the interleaved-1F1B chunk multiplier.
    ``None`` takes the ``RAY_TPU_PIPELINE_VIRTUAL_STAGES`` knob (default
    1); an explicit 0 — argument or env — RAISES instead of silently
    meaning 1 (the falsy-zero lesson), and V beyond blocks-per-stage
    raises with the actionable count."""
    if virtual_stages is None:
        from ray_tpu._private.config import global_config

        virtual_stages = global_config().pipeline_virtual_stages
        source = "RAY_TPU_PIPELINE_VIRTUAL_STAGES"
    else:
        source = "virtual_stages"
    v = int(virtual_stages)
    if v < 1:
        raise ValueError(
            f"{source}={virtual_stages} is invalid: virtual_stages must "
            f"be >= 1 (1 = the plain one-chunk-per-stage 1F1B schedule; "
            f"0 does not mean 'default')")
    per_stage = num_layers // num_stages
    if per_stage < 1:
        raise ValueError(
            f"cannot split cfg.num_layers={num_layers} blocks into "
            f"num_stages={num_stages} stages: every stage needs at "
            f"least one block")
    if v > per_stage:
        raise ValueError(
            f"virtual_stages={v} exceeds blocks-per-stage: "
            f"cfg.num_layers={num_layers} over num_stages={num_stages} "
            f"gives {per_stage} block(s) per stage, and every virtual "
            f"chunk needs at least one block — use virtual_stages <= "
            f"{per_stage} (or a deeper config)")
    return v


def _check_tp_cfg(cfg, tp: int) -> None:
    """tensor_parallel feasibility, the house way: every rejection names
    the offending CONFIG FIELD and the actionable count."""
    if cfg.tie_embeddings:
        raise ValueError(
            "tensor_parallel>1: cfg.tie_embeddings=True is unsupported — "
            "the tied table would need a cross-stage AND cross-tp-rank "
            "gradient sum every flush. Build the config with "
            "tie_embeddings=False")
    if cfg.mlp == "moe":
        raise ValueError(
            "tensor_parallel>1: cfg.mlp='moe' is unsupported — experts "
            "shard over the expert axis, not tensor columns. Use a dense "
            "mlp (cfg.mlp='swiglu'/'gelu'), or shard MoE configs with "
            "expert parallelism")
    if cfg.num_heads % tp:
        raise ValueError(
            f"tensor_parallel={tp} does not divide cfg.num_heads="
            f"{cfg.num_heads}: attention shards whole query heads, so "
            f"each rank needs num_heads/tp = {cfg.num_heads}/{tp} to be "
            f"an integer — use a tp that divides {cfg.num_heads}")
    if cfg.kv_heads % tp:
        raise ValueError(
            f"tensor_parallel={tp} does not divide cfg.num_kv_heads="
            f"{cfg.kv_heads}: GQA shards whole kv heads alongside their "
            f"query groups, so each rank needs num_kv_heads/tp = "
            f"{cfg.kv_heads}/{tp} to be an integer — use a tp that "
            f"divides {cfg.kv_heads}")
    if cfg.hidden_dim % tp:
        raise ValueError(
            f"tensor_parallel={tp} does not divide the ffn width "
            f"cfg.mlp_dim={cfg.hidden_dim}: the ffn-up/ffn-down pair "
            f"shards whole columns, so each rank needs mlp_dim/tp = "
            f"{cfg.hidden_dim}/{tp} to be an integer — use a tp that "
            f"divides {cfg.hidden_dim}")


def _resolve_tensor_parallel(tensor_parallel, cfg) -> int:
    """Validate + default the tensor-parallel width. ``None`` takes the
    ``RAY_TPU_PIPELINE_TP`` knob (default 1); an explicit 0 — argument
    or env — RAISES instead of silently meaning 1 (the falsy-zero
    lesson), and an infeasible tp raises naming the config field."""
    if tensor_parallel is None:
        from ray_tpu._private.config import global_config

        tensor_parallel = global_config().pipeline_tp
        source = "RAY_TPU_PIPELINE_TP"
    else:
        source = "tensor_parallel"
    t = int(tensor_parallel)
    if t < 1:
        raise ValueError(
            f"{source}={tensor_parallel} is invalid: tensor_parallel "
            f"must be >= 1 (1 = unsharded stages; 0 does not mean "
            f"'default')")
    if t > 1:
        _check_tp_cfg(cfg, t)
    return t


def partition_pipeline_params(cfg, params, num_stages: int,
                              virtual_stages: int = 1,
                              tensor_parallel: int = 1):
    """Slice a full init_params() tree into per-CHUNK shards, in
    pipeline order — ``num_stages * virtual_stages`` of them (parity
    tests init once and compare the assembled pipeline to the
    single-process model bit-for-bit; the trainer hands chunk c to
    stage actor c % num_stages). With ``tensor_parallel=tp`` > 1 each
    entry is instead a LIST of tp per-rank shards: blocks Megatron
    column/row-cut (transformer.shard_block_params), embed / pos /
    final_norm / lm_head replicated. ``reassemble_pipeline_params`` is
    the bit-exact inverse."""
    import jax

    _check_pipeline_cfg(cfg)
    tp = int(tensor_parallel)
    if tp > 1:
        _check_tp_cfg(cfg, tp)
    chunks = num_stages * int(virtual_stages)
    splits = pipeline_splits(cfg.num_layers, chunks)
    shards = []
    for c, (lo, hi) in enumerate(splits):
        shard = {}
        if cfg.scan_layers:
            shard["blocks"] = jax.tree.map(
                lambda a: a[lo:hi], params["blocks"])
        else:
            shard["blocks"] = {
                str(i - lo): params["blocks"][str(i)]
                for i in range(lo, hi)}
        if c == 0:
            shard["embed"] = params["embed"]
            if cfg.pos == "learned":
                shard["pos_embed"] = params["pos_embed"]
        if c == chunks - 1:
            shard["final_norm"] = params["final_norm"]
            shard["lm_head"] = params["lm_head"]
        if tp > 1:
            from ray_tpu.models.transformer import shard_block_params

            ranks = []
            for t in range(tp):
                rs = dict(shard)
                if cfg.scan_layers:
                    rs["blocks"] = shard_block_params(
                        cfg, shard["blocks"], tp, t, stacked=True)
                else:
                    rs["blocks"] = {
                        k: shard_block_params(cfg, b, tp, t)
                        for k, b in shard["blocks"].items()}
                ranks.append(rs)
            shards.append(ranks)
        else:
            shards.append(shard)
    return shards


def reassemble_pipeline_params(cfg, shards, num_stages: int,
                               virtual_stages: int = 1,
                               tensor_parallel: int = 1):
    """Bit-exact inverse of ``partition_pipeline_params``: glue per-chunk
    (and, with tp > 1, per-tp-rank) shards back into a full
    ``init_params()``-shaped tree — the parity oracle for comparing an
    assembled pipeline (e.g. ``PipelineTrainer.fetch_params``) against
    the fused single-process model."""
    import jax
    import jax.numpy as jnp

    chunks = num_stages * int(virtual_stages)
    tp = int(tensor_parallel)
    merged = []
    for c in range(chunks):
        sh = shards[c]
        if tp > 1:
            from ray_tpu.models.transformer import merge_tp_block_params

            base = dict(sh[0])
            if cfg.scan_layers:
                base["blocks"] = merge_tp_block_params(
                    cfg, [s["blocks"] for s in sh], stacked=True)
            else:
                base["blocks"] = {
                    k: merge_tp_block_params(
                        cfg, [s["blocks"][k] for s in sh])
                    for k in sh[0]["blocks"]}
            sh = base
        merged.append(sh)
    params = {}
    if cfg.scan_layers:
        params["blocks"] = jax.tree.map(
            lambda *xs: jnp.concatenate(xs, axis=0),
            *[m["blocks"] for m in merged])
    else:
        splits = pipeline_splits(cfg.num_layers, chunks)
        blocks = {}
        for (lo, hi), m in zip(splits, merged):
            for i in range(lo, hi):
                blocks[str(i)] = m["blocks"][str(i - lo)]
        params["blocks"] = blocks
    params["embed"] = merged[0]["embed"]
    if cfg.pos == "learned":
        params["pos_embed"] = merged[0]["pos_embed"]
    params["final_norm"] = merged[-1]["final_norm"]
    params["lm_head"] = merged[-1]["lm_head"]
    return params


def _stage_init(cfg, seed: int, num_chunks: int, chunk: int):
    """Chunk shard init, bit-identical to slicing ``init_params(cfg,
    PRNGKey(seed))`` WITHOUT materializing the full model on every stage
    actor (that spike would defeat the memory motive of pipelining a
    model that doesn't fit one host): init_params consumes one split key
    per parameter group (embed=keys[0], pos=keys[1], lm_head=keys[2],
    block i=keys[3+i]), so building only this chunk's groups from the
    same key layout reproduces the exact tensors. ``num_chunks`` counts
    the whole pipeline's chunks (num_stages * virtual_stages)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import _block_params, _norm_params

    _check_pipeline_cfg(cfg)
    lo, hi = pipeline_splits(cfg.num_layers, num_chunks)[chunk]
    keys = jax.random.split(jax.random.PRNGKey(seed), cfg.num_layers + 3)
    init = jax.nn.initializers.normal(0.02, cfg.param_dtype)
    blocks = [_block_params(cfg, keys[3 + i]) for i in range(lo, hi)]
    shard = {}
    if cfg.scan_layers:
        shard["blocks"] = jax.tree.map(
            lambda *xs: jnp.stack(xs, axis=0), *blocks)
    else:
        shard["blocks"] = {str(i): b for i, b in enumerate(blocks)}
    if chunk == 0:
        shard["embed"] = {
            "table": init(keys[0], (cfg.vocab_size, cfg.embed_dim))}
        if cfg.pos == "learned":
            shard["pos_embed"] = {
                "table": init(keys[1], (cfg.max_seq_len, cfg.embed_dim))}
    if chunk == num_chunks - 1:
        shard["final_norm"] = _norm_params(cfg, cfg.embed_dim)
        shard["lm_head"] = {
            "kernel": init(keys[2], (cfg.embed_dim, cfg.vocab_size))}
    return shard


def _apply_blocks(cfg, blocks, h, n_local: int):
    """Run one stage's block slice — the same remat/scan structure as
    transformer.forward, so a split pipeline matches the fused model."""
    import jax
    from jax import lax

    from ray_tpu.models.transformer import _block, remat_policy
    from ray_tpu.ops.rotary import rope_frequencies

    rope = None if cfg.pos == "learned" else rope_frequencies(
        cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
    block_fn = _block
    if cfg.remat:
        block_fn = jax.checkpoint(
            _block, static_argnums=(0, 5),
            policy=remat_policy(cfg.remat_policy))
    if cfg.scan_layers:
        def body(carry, layer_params):
            hh, _, _, _ = block_fn(cfg, layer_params, carry, rope, None,
                                   None)
            return hh, None
        h, _ = lax.scan(body, h, blocks)
    else:
        for i in range(n_local):
            h, _, _, _ = block_fn(cfg, blocks[str(i)], h, rope, None, None)
    return h


def _stage_fwd(cfg, lo: int, hi: int, first: bool, params, x):
    """Non-last stage forward: tokens -> hidden (stage 0) or
    hidden -> hidden."""
    import jax.numpy as jnp

    if first:
        h = params["embed"]["table"].astype(cfg.dtype)[x]
        if cfg.pos == "learned":
            h = h + params["pos_embed"]["table"].astype(
                cfg.dtype)[jnp.arange(x.shape[1])]
    else:
        h = jnp.asarray(x).astype(cfg.dtype)
    return _apply_blocks(cfg, params["blocks"], h, hi - lo)


def _stage_loss(cfg, lo: int, hi: int, params, x, tokens):
    """Last stage: hidden -> blocks -> final norm -> causal-LM loss
    (identical math to transformer.loss_fn on the fused model)."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import _norm

    h = _apply_blocks(cfg, params["blocks"],
                      jnp.asarray(x).astype(cfg.dtype), hi - lo)
    h = _norm(cfg, params["final_norm"], h)
    targets = tokens[:, 1:]
    if cfg.fused_ce:
        from ray_tpu.ops.losses import fused_softmax_cross_entropy

        loss, _ = fused_softmax_cross_entropy(
            h[:, :-1], params["lm_head"]["kernel"], targets, None,
            chunk=cfg.ce_chunk, compute_dtype=cfg.dtype,
            transpose_table=True)
    else:
        from ray_tpu.ops.losses import softmax_cross_entropy

        logits = jnp.einsum(
            "bsd,dv->bsv", h,
            params["lm_head"]["kernel"].astype(cfg.dtype))
        loss, _ = softmax_cross_entropy(logits[:, :-1], targets, None)
    return loss


def _stage_init_tp(cfg, seed: int, num_chunks: int, chunk: int, tp: int,
                   tp_rank: int = 0):
    """tp rank's shard of one chunk: the SAME deterministic per-group key
    layout as _stage_init, each block Megatron-cut after init — bit-
    identical to slicing ``partition_pipeline_params(init_params(...),
    ..., tensor_parallel=tp)``. Replicated groups (embed, pos, final
    norm, lm_head) are built whole on every rank."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.transformer import (_block_params, _norm_params,
                                            shard_block_params)

    _check_pipeline_cfg(cfg)
    _check_tp_cfg(cfg, tp)
    lo, hi = pipeline_splits(cfg.num_layers, num_chunks)[chunk]
    keys = jax.random.split(jax.random.PRNGKey(seed), cfg.num_layers + 3)
    init = jax.nn.initializers.normal(0.02, cfg.param_dtype)
    blocks = [shard_block_params(cfg, _block_params(cfg, keys[3 + i]),
                                 tp, tp_rank)
              for i in range(lo, hi)]
    shard = {}
    if cfg.scan_layers:
        shard["blocks"] = jax.tree.map(
            lambda *xs: jnp.stack(xs, axis=0), *blocks)
    else:
        shard["blocks"] = {str(i): b for i, b in enumerate(blocks)}
    if chunk == 0:
        shard["embed"] = {
            "table": init(keys[0], (cfg.vocab_size, cfg.embed_dim))}
        if cfg.pos == "learned":
            shard["pos_embed"] = {
                "table": init(keys[1], (cfg.max_seq_len, cfg.embed_dim))}
    if chunk == num_chunks - 1:
        shard["final_norm"] = _norm_params(cfg, cfg.embed_dim)
        shard["lm_head"] = {
            "kernel": init(keys[2], (cfg.embed_dim, cfg.vocab_size))}
    return shard


def _tp_apply_blocks(cfg, blocks, h, n_local: int, tp_ops,
                     split_tail: bool):
    """Run one stage's tp-sharded block slice — same remat/scan structure
    as _apply_blocks, with the (g, f) reduce pair threaded through each
    block. ``split_tail``: the LAST block returns its (residual carry,
    mlp partial) pair instead of the reduced output, so the trainer can
    issue the final partial-sum reduce asynchronously and overlap it
    with the next microbatch's compute."""
    import jax
    from jax import lax

    from ray_tpu.models.transformer import (_tp_block, _tp_block_tail,
                                            remat_policy)
    from ray_tpu.ops.rotary import rope_frequencies

    g, f = tp_ops
    rope = None if cfg.pos == "learned" else rope_frequencies(
        cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)

    def one_block(p, x):
        return _tp_block(cfg, p, x, rope, g, f)

    def tail_block(p, x):
        return _tp_block_tail(cfg, p, x, rope, g, f)

    if cfg.remat:
        policy = remat_policy(cfg.remat_policy)
        one_block = jax.checkpoint(one_block, policy=policy)
        tail_block = jax.checkpoint(tail_block, policy=policy)

    n_chain = n_local - 1 if split_tail else n_local
    if cfg.scan_layers:
        if n_chain:
            def body(carry, layer_params):
                return one_block(layer_params, carry), None
            head = jax.tree.map(lambda a: a[:n_chain], blocks)
            h, _ = lax.scan(body, h, head)
        last = jax.tree.map(lambda a: a[n_local - 1], blocks)
    else:
        for i in range(n_chain):
            h = one_block(blocks[str(i)], h)
        last = blocks[str(n_local - 1)]
    if split_tail:
        return tail_block(last, h)
    return h


def _stage_fwd_tp(cfg, lo: int, hi: int, first: bool, tail: bool, params,
                  x, *, tp_ops):
    """tp-sharded non-last-chunk forward. With ``tail`` the return value
    is the last block's (u, mlp_partial) pair — the chunk output is
    ``u + allreduce(mlp_partial)``, completed by the trainer."""
    import jax.numpy as jnp

    if first:
        h = params["embed"]["table"].astype(cfg.dtype)[x]
        if cfg.pos == "learned":
            h = h + params["pos_embed"]["table"].astype(
                cfg.dtype)[jnp.arange(x.shape[1])]
    else:
        h = jnp.asarray(x).astype(cfg.dtype)
    return _tp_apply_blocks(cfg, params["blocks"], h, hi - lo, tp_ops,
                            split_tail=tail)


def _stage_loss_tp(cfg, lo: int, hi: int, params, x, tokens, *, tp_ops):
    """tp-sharded last chunk: every reduced quantity is the full sum, so
    the loss (and its gradient) is identical on every tp rank. The final
    norm / lm_head are replicated; never tail-split."""
    import jax.numpy as jnp

    from ray_tpu.models.transformer import _norm

    h = _tp_apply_blocks(cfg, params["blocks"],
                         jnp.asarray(x).astype(cfg.dtype), hi - lo,
                         tp_ops, split_tail=False)
    h = _norm(cfg, params["final_norm"], h)
    targets = tokens[:, 1:]
    if cfg.fused_ce:
        from ray_tpu.ops.losses import fused_softmax_cross_entropy

        loss, _ = fused_softmax_cross_entropy(
            h[:, :-1], params["lm_head"]["kernel"], targets, None,
            chunk=cfg.ce_chunk, compute_dtype=cfg.dtype,
            transpose_table=True)
    else:
        from ray_tpu.ops.losses import softmax_cross_entropy

        logits = jnp.einsum(
            "bsd,dv->bsv", h,
            params["lm_head"]["kernel"].astype(cfg.dtype))
        loss, _ = softmax_cross_entropy(logits[:, :-1], targets, None)
    return loss


def pipeline_stage_defs(cfg, num_stages: int, *, virtual_stages=None,
                        seed: int = 0, tensor_parallel=None):
    """Partition ``cfg`` into pipeline chunk specs for
    ``ray_tpu.train.PipelineTrainer``: uniform block split, embedding on
    the first chunk, final-norm + lm_head + loss on the last. With
    ``virtual_stages=V`` (None = the ``RAY_TPU_PIPELINE_VIRTUAL_STAGES``
    knob, default 1) the list holds ``num_stages * V`` chunk specs in
    pipeline order — pass the SAME V to the trainer, which hands chunk c
    to stage actor ``c % num_stages`` (the interleaved 1F1B layout).
    Each spec is a dict of picklable callables ({"init", "fwd"} /
    {"init", "loss"}); init runs ON the stage actor and re-derives the
    full model's deterministic init before slicing, so an assembled
    pipeline matches ``init_params(cfg, PRNGKey(seed))`` exactly.

    With ``tensor_parallel=tp`` (None = the ``RAY_TPU_PIPELINE_TP``
    knob, default 1) each chunk is additionally Megatron column/row-
    sharded over tp ranks: init grows a ``tp_rank`` kwarg (the trainer
    binds each rank's), fwd/loss grow a ``tp_ops`` kwarg (the (g, f)
    partial-sum reduce pair from ``ray_tpu.util.collective.tp``), and
    the spec carries ``tp``/``tp_tail`` so the trainer wires per-(stage,
    dp-rank) tp groups and the async tail reduce. Pass the SAME tp to
    ``PipelineTrainer(tensor_parallel=...)``."""
    import functools

    from ray_tpu.models.transformer import tp_tail_supported

    _check_pipeline_cfg(cfg)
    v = _resolve_virtual_stages(virtual_stages, num_stages,
                                cfg.num_layers)
    t = _resolve_tensor_parallel(tensor_parallel, cfg)
    chunks = num_stages * v
    splits = pipeline_splits(cfg.num_layers, chunks)
    defs = []
    for c, (lo, hi) in enumerate(splits):
        if t == 1:
            d = {"init": functools.partial(
                _stage_init, cfg, seed, chunks, c)}
            if c == chunks - 1:
                d["loss"] = functools.partial(_stage_loss, cfg, lo, hi)
            else:
                d["fwd"] = functools.partial(
                    _stage_fwd, cfg, lo, hi, c == 0)
        else:
            d = {"init": functools.partial(
                _stage_init_tp, cfg, seed, chunks, c, t), "tp": t}
            if c == chunks - 1:
                d["loss"] = functools.partial(_stage_loss_tp, cfg, lo, hi)
                d["tp_tail"] = False
            else:
                tail = tp_tail_supported(cfg)
                d["fwd"] = functools.partial(
                    _stage_fwd_tp, cfg, lo, hi, c == 0, tail)
                d["tp_tail"] = tail
        defs.append(d)
    return defs
