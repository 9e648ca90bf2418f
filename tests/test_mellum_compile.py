"""Mellum2-12B-A2.5B's cell compiled for the chip, without the chip (ISSUE
63: out of ``tests/test_tpu_compile.py``, names and assertions as they
were): the cell's two serving programs and the check's forward GIVEN the
routes at the published widths, for a described ``v5e``. The fixtures and
helpers are ``tests/tpu_compile_harness.py``'s.
"""

import functools

import jax

from tests.tpu_compile_harness import (  # noqa: F401
    as_a_tpu_process, cell_programs, check_forward_given_the_routes, fits,
    kernel_calls, v5e)


def test_mellum_serve_programs_compile_and_fit(v5e):
    """The benchmark's Mellum2-12B-A2.5B configuration (published widths:
    hidden 2304 = 18 lanes, experts of 896 = 7 lanes, 32 query heads over 4
    K/V heads of 128, so a group of 8; 8 layers of two kinds, bf16) under its
    cell's deployment: the prefill chunk with the step's rows along and the
    decode step, the paged kernel under BOTH names — ``window_attention``
    for the six window layers (a first block as well as a last one in its
    walk), ``paged_attention`` for the two full ones — and the experts'
    kernel once a layer, fed from the stacks of a pattern's period and not
    from a copy of a layer's experts (793 MB); 7.59 GB of weights, the
    full layers' 4.43 GB pool and the window layers' 0.62 GB beside the
    programs' own memory on one 16 GB chip."""
    from ray_tpu.ops import moe
    from ray_tpu.ops.paged_attention import resolve_impl

    cfg, held, programs = cell_programs(v5e, "mellum2_12b_l8",
                                         "mellum2_shortlong")
    assert (cfg.embed_dim, cfg.head_dim, cfg.hidden_dim) == (2304, 128, 896)
    assert cfg.num_heads // cfg.kv_heads == 8 and cfg.period == 4
    lane = resolve_impl(cfg)
    assert lane == "pallas"
    assert 12.5e9 < held < 12.8e9
    # a whole expert in one grid cell at both programs' pair counts
    assert moe.tile_sizes(32 * 8, 64, 2304, 896, 2) == (64, 896)
    assert moe.tile_sizes((512 + 32) * 8, 64, 2304, 896, 2) == (128, 896)
    calls = {"prefill": {"window_attention": 12, "paged_attention": 4,
                         "moe_grouped_matmul": 8},
             "decode": {"window_attention": 6, "paged_attention": 2,
                        "moe_grouped_matmul": 8}}
    for name, (program, args) in programs.items():
        compiled = jax.jit(
            functools.partial(program, cfg, attn=lane, moe_info=True),
            donate_argnums=(6,)).lower(*args).compile()
        assert kernel_calls(compiled) == calls[name], name
        assert "ragged-dot" not in compiled.as_text(), name
        total = fits(compiled)
        assert total < 14.6e9, f"{name}: {total / 1e9:.1f} GB"
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < 600e6, f"{name}: {temp / 1e6:.0f} MB of temporaries"


def test_mellum_forward_given_the_routes_fits_beside_the_pools(v5e):
    """The cell states limits GIVEN the routes, so ``reference_check`` runs
    the program's uncached whole-sequence ``forward`` (``return_routes``)
    over the check prompt and the tokens served behind it, up to whole
    tiles, in the replica, beside the weights and both pools: its window
    layers attend a block of query rows at a time
    (``transformer._window_attention``), not through ``[32, S, S]`` scores
    (2.6 GB a layer in float32 at 4480 tokens)."""
    held, compiled = check_forward_given_the_routes(
        v5e, "mellum2_12b_l8", "mellum2_shortlong")
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1.2e9, f"{temp / 1e9:.2f} GB of temporaries"
    assert held + temp < 14.6e9
