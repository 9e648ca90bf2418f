"""OLMoE-1B-7B's cell compiled for the chip, without the chip (ISSUE 63: out
of ``tests/test_tpu_compile.py``, names and assertions as they were): the
cell's two serving programs at the published widths for a described ``v5e``,
and the turn with a chunk as ONE program. The chunk's program with the step's
rows along is compiled once for both tests (``compiled``). The fixtures and
helpers are ``tests/tpu_compile_harness.py``'s.
"""

import pytest

from tests.tpu_compile_harness import (  # noqa: F401
    WITH_THE_STEPS_ROWS,
    a_turn_with_a_chunk_is_one_program_at_the_cells_shapes, as_a_tpu_process,
    cell_programs, compiled, fits, names, serving_program, v5e)


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
