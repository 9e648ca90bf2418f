"""Brumby-14B's cell compiled for the chip, without the chip (ISSUE 63: out
of ``tests/test_tpu_compile.py``, names and assertions as they were): the
toy's and the cell's two serving programs — no page anywhere, a state a slot
— for a described ``v5e``, and the chunk's program with the step's rows along
against the chunk alone. The chunk's program at the cell's shapes is compiled
once for both tests that read it (``compiled``). The fixtures and helpers are
``tests/tpu_compile_harness.py``'s.
"""

import functools

import jax
import pytest

from tests.tpu_compile_harness import (  # noqa: F401
    WITH_THE_STEPS_ROWS, as_a_tpu_process, compiled, deployment, fits,
    kernel_calls, names, pageless_programs, program_config, serving_program,
    the_state_kinds_chunk_program_takes_the_rows_along_in_place, v5e)


RETENTION_KERNELS = {"prefill": {"power_retention_chunk",
                                 "power_retention_step"},
                     "decode": {"power_retention_step"}}


def test_brumby_debug_serve_programs_lower_with_the_retention_kernels(v5e):
    """The two serve programs of the toy Brumby (float32, heads of 32, five
    query heads on each of two states, no page anywhere) go through Mosaic:
    the chunk's program holds ``power_retention_chunk`` and, for the
    step's rows it takes along, ``power_retention_step``, the step's the
    latter alone, each once a layer, and no other kernel."""
    from ray_tpu.models.presets import brumby_debug

    cfg = brumby_debug()
    _, programs = pageless_programs(v5e, cfg, slots=4, chunk=64)
    for name, (program, args) in programs.items():
        compiled = jax.jit(functools.partial(program, cfg, attn="pallas"),
                           donate_argnums=(6,)).lower(*args).compile()
        assert kernel_calls(compiled) == dict.fromkeys(
            RETENTION_KERNELS[name], cfg.num_layers), name
        fits(compiled)


@pytest.mark.parametrize("cell", ["brumby_longgen"])
def test_the_state_kinds_chunk_program_takes_the_rows_along_in_place(
        v5e, compiled, cell):
    """ISSUE 44, at the cell's real shapes (MiniCPM-SALA's case is
    ``tests/test_minicpm_sala_compile.py``'s; the body is the harness's)."""
    the_state_kinds_chunk_program_takes_the_rows_along_in_place(
        v5e, compiled, cell)


def test_brumby_serve_programs_compile_and_fit(v5e, compiled):
    """The benchmark's Brumby-14B configuration (published widths, 8 layers,
    bf16) under its cell's deployment: the prefill chunk and the decode
    step with their retention kernel inside, 8.4 GB of weights and 4.4 GB of
    states (16 slots x 8 layers x 34.35 MB) beside the programs' own memory
    on one 16 GB chip."""
    manifest, cfg = program_config("brumby_14b_l8")
    dep = deployment(manifest, "brumby_longgen")
    assert "page_tokens" not in dep and "kv_pages" not in dep
    held, programs = pageless_programs(v5e, cfg, dep["slots"],
                                        dep["prefill_chunk"])
    assert 12.6e9 < held < 13.0e9
    for name, (program, args) in programs.items():
        # the chunk's program is the one the state kinds' test reads
        made = serving_program(
            compiled, ("brumby_14b_l8", "brumby_longgen", name,
                       WITH_THE_STEPS_ROWS), cfg, program, args,
            attn="pallas")
        found = names(made)
        assert found == RETENTION_KERNELS[name], (name, found)
        total = fits(made)
        # the programs' own memory leaves room for the reference check
        assert total < 14.6e9, f"{name}: {total / 1e9:.1f} GB"
