"""MiniCPM-SALA's cell compiled for the chip, without the chip (ISSUE 63: out
of ``tests/test_tpu_compile.py``, names and assertions as they were): the
cell's two serving programs at the published widths for a described ``v5e``,
the chunk's program with the step's rows along against the chunk alone, and
the toy of two layer kinds. The chunk's program at the cell's shapes is
compiled once for both tests that read it (``compiled``). The fixtures and
helpers are ``tests/tpu_compile_harness.py``'s.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tests.tpu_compile_harness import (  # noqa: F401
    WITH_THE_STEPS_ROWS, as_a_tpu_process, cell_programs, compiled, fits,
    kernel_calls, names, on, serving_program,
    the_state_kinds_chunk_program_takes_the_rows_along_in_place, v5e)


def test_minicpm_sala_serve_programs_compile_and_fit(v5e, compiled):
    """The benchmark's MiniCPM-SALA configuration (published widths, 16
    layers of two kinds, bf16) under its cell's deployment: the prefill
    chunk (with the step's rows along: both kernels of the linear mixer)
    and the decode step with the four kernels of the two mixers
    (``linear_attention_chunk`` / ``_step``, ``sparse_select``,
    ``sparse_paged_attention`` — the paged kernel over a table of chosen
    pages in a step, the masked flash kernel in a chunk), 10.1 GB of
    weights, the 2.2 GB pool of the four sparse layers and 0.4 GB of states
    beside the programs' own memory on one 16 GB chip."""
    from ray_tpu.ops.paged_attention import resolve_impl

    cfg, held, programs = cell_programs(v5e, "minicpm_sala_l16",
                                         "minicpm_sala_longdoc")
    lane = resolve_impl(cfg)
    assert lane == "pallas"
    assert 12.5e9 < held < 12.9e9  # 10.1 GB + 2.2 GB of pool + 0.4 of state
    kernels = {"prefill": {"linear_attention_chunk", "linear_attention_step",
                           "sparse_select", "sparse_paged_attention"},
               "decode": {"linear_attention_step", "sparse_select",
                          "sparse_paged_attention"}}
    for name, (program, args) in programs.items():
        # the chunk's program is the one the state kinds' test reads
        made = serving_program(
            compiled, ("minicpm_sala_l16", "minicpm_sala_longdoc", name,
                       WITH_THE_STEPS_ROWS), cfg, program, args, attn=lane)
        found = names(made)
        assert found == kernels[name], (name, found)
        total = fits(made)
        # the programs' own memory leaves room for the reference check
        assert total < 14.5e9, f"{name}: {total / 1e9:.1f} GB"


def test_minicpm_sala_debug_chunk_program_lowers_with_the_steps_rows(v5e):
    """The toy of two layer kinds (float32): the chunk's program with the
    step's rows along goes through Mosaic with each kind's kernels a group
    of rows — the linear layers' chunk and step kernels once a layer, the
    block-selected layers' selection twice and their chunk's attention once
    (a pool row of 32 lanes is too narrow for the paged kernel, which the
    step's chosen blocks go through: ``resolve_impl`` says 'reference')."""
    from ray_tpu.models.decode import (StepRows, init_paged_caches,
                                       paged_prefill_into_slot)
    from ray_tpu.models.presets import minicpm_sala_debug
    from ray_tpu.models.transformer import LINEAR, SPARSE, init_params
    from ray_tpu.ops.paged_attention import resolve_impl

    cfg = minicpm_sala_debug()
    assert resolve_impl(cfg) == "reference"
    slots, chunk, T, pages = 4, 64, 4, 64
    chip = SingleDeviceSharding(v5e.devices[0])
    place = lambda tree: jax.tree.map(
        lambda a: on(chip, a.shape, a.dtype), tree)
    params = place(jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.PRNGKey(0)))
    caches = place(jax.eval_shape(functools.partial(
        init_paged_caches, cfg, slots * pages + 1, T, pages, slots=slots)))
    ids = functools.partial(on, chip, dtype=jnp.int32)
    table = ids((slots, pages))
    step = StepRows(ids((slots,)), ids((slots,)), table, table,
                    on(chip, (slots,), jnp.float32),
                    on(chip, (slots,), jnp.uint32))
    compiled = jax.jit(
        functools.partial(paged_prefill_into_slot, cfg, attn="reference"),
        donate_argnums=(6,)).lower(
            params, ids((1, chunk)), ids(()), ids(()), ids((pages,)),
            ids((pages,)), caches, ids((slots,)), ids(()),
            on(chip, (), jnp.float32), on(chip, (), jnp.uint32), step,
            ids(())).compile()
    linear, sparse = cfg.kinds.count(LINEAR), cfg.kinds.count(SPARSE)
    assert kernel_calls(compiled) == {
        "linear_attention_chunk": linear, "linear_attention_step": linear,
        "sparse_select": 2 * sparse, "sparse_paged_attention": sparse}
    fits(compiled)


@pytest.mark.parametrize("cell", ["minicpm_sala_longdoc"])
def test_the_state_kinds_chunk_program_takes_the_rows_along_in_place(
        v5e, compiled, cell):
    """ISSUE 44, at the cell's real shapes (Brumby's case is
    ``tests/test_brumby_compile.py``'s; the body is the harness's)."""
    the_state_kinds_chunk_program_takes_the_rows_along_in_place(
        v5e, compiled, cell)
