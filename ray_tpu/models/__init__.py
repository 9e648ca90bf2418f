"""Model zoo: pure-JAX decoder-only transformers with logical-axis-annotated
param pytrees (shardable onto any mesh via ray_tpu.parallel.sharding)."""

from ray_tpu.models.transformer import (  # noqa: F401
    TransformerConfig,
    init_params,
    forward,
    logical_axes,
    loss_fn,
    count_params,
)
from ray_tpu.models.presets import (  # noqa: F401
    gpt2_small,
    gpt2_medium,
    gpt_1b,
    llama3_8b,
    llama_debug,
    moe_debug,
    minicpm_sala_debug,
    brumby_debug,
    deepseek_v32_debug,
    glm_moe_lite_debug,
    keye_debug,
    mellum_debug,
    nemotron_h,
    nemotron_h_debug,
    ouro,
    ouro_debug,
)
