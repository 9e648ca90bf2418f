"""GLM-4.7-Flash's cell compiled for the chip, without the chip (ISSUE 63: out
of ``tests/test_tpu_compile.py``, names and assertions as they were): the
cell's two serving programs and the check's forward GIVEN the routes at the
published widths, for a described ``v5e``. (The latent kernel alone stays
with the kernels, in ``tests/test_tpu_compile.py``.) The fixtures and helpers
are ``tests/tpu_compile_harness.py``'s.
"""

import functools

import jax

from tests.tpu_compile_harness import (  # noqa: F401
    as_a_tpu_process, cell_programs, check_forward_given_the_routes, fits,
    kernel_calls, kernel_names, v5e)


def test_glm_serve_programs_compile_and_fit(v5e):
    """The benchmark's GLM-4.7-Flash configuration (published widths: hidden
    2048, 20 heads of 192 + 64 q/k and 256 v values over a latent of 512 and
    one shared rotated key of 64, a dense layer of 10240 then 64 experts of
    1536 top-4 beside a shared one; 1 + 5 layers, bf16) under its cell's
    deployment (8 slots of 66048 tokens, 32769 pages): the prefill chunk with
    the step's rows along and the decode step, the latent kernel once a layer
    and group of rows under the step's name or the chunk's, the experts'
    kernel once an EXPERT layer (the dense layer has none); 7.79 GB of
    weights and the 4.03 GB pool (rows of 640 lanes: 512 + 64 + padding)
    beside the programs' own memory on one 16 GB chip."""
    from ray_tpu.ops.paged_attention import resolve_impl

    cfg, held, programs = cell_programs(v5e, "glm47_flash_l6",
                                         "glm47_flash_longdocs")
    assert (cfg.embed_dim, cfg.head_dim, cfg.hidden_dim) == (2048, 256, 1536)
    assert (cfg.lead_layers, cfg.expert_layers, cfg.period) == (1, 5, 1)
    assert cfg.mlp_width("swiglu") == 10240
    assert cfg.moe_scoring == "sigmoid" and cfg.moe_routed_scale == 1.8
    lane = resolve_impl(cfg)
    assert lane == "pallas"
    assert 11.7e9 < held < 11.9e9
    calls = {"prefill": {"latent_chunk_attention": 6,
                         "latent_step_attention": 6,
                         "moe_grouped_matmul": 5},
             "decode": {"latent_step_attention": 6,
                        "moe_grouped_matmul": 5}}
    for name, (program, args) in programs.items():
        compiled = jax.jit(
            functools.partial(program, cfg, attn=lane, moe_info=True),
            donate_argnums=(6,)).lower(*args).compile()
        assert kernel_calls(compiled) == calls[name], name
        total = fits(compiled)
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert total < 12.6e9, f"{name}: {total / 1e9:.1f} GB"
        assert temp < 0.6e9, f"{name}: {temp / 1e6:.0f} MB of temporaries"


def test_glm_check_programs_fit_beside_the_pool(v5e):
    """The largest program ``reference_check`` runs in the replica beside
    the weights and the pool, on the cell's 4113-token check prompt and the
    32 tokens served behind it: the cell states limits GIVEN the routes, so
    the uncached whole-sequence ``forward`` (unabsorbed, through the flash
    kernel at heads of 256) up to whole tiles; its [4224, 154880] bf16 logits
    are 1.31 GB."""
    held, compiled = check_forward_given_the_routes(
        v5e, "glm47_flash_l6", "glm47_flash_longdocs")
    assert "flash_attention_fwd" in " ".join(kernel_names(
        compiled.as_text()))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2.2e9, f"forward: {temp / 1e9:.2f} GB of temporaries"
    assert held + temp < 14.2e9
