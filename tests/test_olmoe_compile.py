"""OLMoE-1B-7B's cell compiled for the chip, without the chip (ISSUE 63: out
of ``tests/test_tpu_compile.py``, names and assertions as they were): the
cell's two serving programs at the published widths for a described ``v5e``,
and the turn with a chunk as ONE program. The chunk's program with the step's
rows along is compiled once for both tests (``compiled``). The fixtures and
helpers are ``tests/tpu_compile_harness.py``'s.
"""

import jax
import jax.numpy as jnp
import pytest

from tests.tpu_compile_harness import (  # noqa: F401
    WITH_THE_STEPS_ROWS,
    a_turn_with_a_chunk_is_one_program_at_the_cells_shapes, as_a_tpu_process,
    cell_programs, compiled, deployment, fits, names, program_config,
    serving_program, v5e)


@pytest.mark.parametrize("config,cell,held_gb", [pytest.param(
    "olmoe_1b_7b_l8", "olmoe_reason", (13.4, 13.7),
    id="olmoe_1b_7b_l8-olmoe_reason-held_gb1")])
def test_a_turn_with_a_chunk_is_one_program_at_the_cells_shapes(
        v5e, compiled, config, cell, held_gb):
    """ISSUE 40, at ``olmoe_reason``'s shapes (``mistral7b_chat``'s case is
    ``tests/test_tpu_compile.py``'s; the body is the harness's)."""
    a_turn_with_a_chunk_is_one_program_at_the_cells_shapes(
        v5e, compiled, config, cell, held_gb)


def test_olmoe_serve_programs_compile_and_fit(v5e, compiled):
    """The benchmark's OLMoE-1B-7B configuration (published widths, 8
    layers, bf16) under its cell's deployment: the prefill chunk (with the
    step's rows along) and the decode step with the expert layer's grouped
    matmuls (the kernel ``moe_grouped_matmul``, once a layer, and nothing
    of the compiler's own ``ragged-dot``) and the paged kernel at its
    second shape (page rows of 16 kv heads x 128, group size 1), weights
    and the 6.4 GB pool beside the programs' own memory on one 16 GB
    chip."""
    from ray_tpu.ops.paged_attention import resolve_impl

    cfg, held, programs = cell_programs(v5e, "olmoe_1b_7b_l8",
                                         "olmoe_reason")
    lane = resolve_impl(cfg)
    assert lane == "pallas"
    assert 13.4e9 < held < 13.7e9  # 7.13 GB of weights + 6.4 GB of pool
    for name, (program, args) in programs.items():
        # the chunk's program is the one the turn's test reads
        made = serving_program(
            compiled, ("olmoe_1b_7b_l8", "olmoe_reason", name,
                       WITH_THE_STEPS_ROWS),
            cfg, program, args, attn=lane, moe_info=True)
        assert names(made) == {"paged_attention",
                               "moe_grouped_matmul"}, name
        assert "ragged-dot" not in made.as_text(), name
        fits(made)
        # no layer's experts (805 MB) are copied off the stacked weights
        temp = made.memory_analysis().temp_size_in_bytes
        assert temp < 600e6, f"{name}: {temp / 1e6:.0f} MB of temporaries"


@pytest.mark.parametrize("config,cell,column_tiles", [
    ("olmoe_1b_7b_l8", "olmoe_reason", 1),
    ("nemotron3_nano_30b_a3b_l9", "nemotron3_nano_reason", 1),
    ("deepseek_v32_exp_l5", "deepseek_v32_longdocs", 8)])
def test_the_experts_kernel_is_what_it_was_at_one_column_tile(
        monkeypatch, config, cell, column_tiles):
    """Who else runs ``ops.moe.expert_mlp`` (ISSUE 64), shown: at a cell's
    own shapes — a turn's (chunk + slots) x top-k pairs and a step's slots x
    top-k over the experts held, OLMoE's three matrices and Nemotron's two,
    hidden-major — the call traces to the jaxpr (the walk, the kernel's body,
    every block's index map) that the weight blocks' maps as they were
    before trace to: one column tile, and a padding visit's ``j`` is 0 as a
    real one's. At DeepSeek's widths (8 column tiles) the two differ, so the
    comparison can tell. (Jaxprs, not lowered text: the kernel's serialized
    module carries the source lines of its frames, so the text of an
    unchanged kernel moves with any line above it.)"""
    from ray_tpu.ops import moe

    manifest, cfg = program_config(config)
    dep = deployment(manifest, cell)
    G, d, f = cfg.experts_held, cfg.embed_dim, cfg.mlp_width("moe")
    gated = cfg.moe_activation == moe.SWIGLU
    stack = cfg.expert_layers * G
    of = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    def traced(pairs):
        jax.clear_caches()  # ``expert_mlp`` is jitted: trace it anew
        assert f // moe.tile_sizes(pairs, G, d, f, 2,
                                   matrices=2 + gated).cols == column_tiles
        whole = jax.make_jaxpr(moe.expert_mlp)(
            of(pairs, d), of(stack, d, f) if gated else None,
            of(stack, d, f) if gated else of(stack, f, d), of(stack, f, d),
            jax.ShapeDtypeStruct((G,), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.int32))
        jitted, = whole.eqns  # printed without its blocks' index maps
        call, = (eqn for eqn in jitted.params["jaxpr"].eqns
                 if eqn.primitive.name == "pallas_call")
        return [str(whole)] + [
            str(block.index_map_jaxpr)
            for block in call.params["grid_mapping"].block_mappings]

    shapes = [(dep["prefill_chunk"] + dep["slots"]) * cfg.moe_top_k,
              dep["slots"] * cfg.moe_top_k]
    now = [traced(pairs) for pairs in shapes]
    assert all("moe_grouped_matmul" in text[0] and len(text) == 5 + gated
               for text in now)
    monkeypatch.setattr(moe, "hidden_block",
                        lambda i, j, group, total, f_tiles: (group[i], 0, j))
    monkeypatch.setattr(moe, "down_block",
                        lambda i, j, group, total, f_tiles: (group[i], j, 0))
    before = [traced(pairs) for pairs in shapes]
    assert [a == b for a, b in zip(now, before)] == [column_tiles == 1] * 2
