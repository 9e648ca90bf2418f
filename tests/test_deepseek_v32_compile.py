"""DeepSeek-V3.2-Exp's cell compiled for the chip, without the chip (ISSUE
61): the cell's two serving programs and the picked latent attention alone
at the published widths, for a described ``v5e``. The fixtures and helpers
are ``tests/tpu_compile_harness.py``'s; the tests stand in a file of their
own, as every model's do, because ``--dist loadfile`` hands a whole file to
one worker.
"""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tests.tpu_compile_harness import (  # noqa: F401
    as_a_tpu_process, cell_programs, fits, kernel_calls, on, v5e)


PICKED_KERNELS = {"index_score": 5, "indexed_select": 5}


def _run_gathers(text: str) -> list:
    """The compiled text's gathers of a query's run out of the latent pool:
    a result of [*, 2048, 640]."""
    return re.findall(r"= \w+\[[\d,]*2048,640\][^\n]* gather\(", text)


def _vmem_asked(text: str, name: str) -> list:
    """The VMEM bytes each custom call named ``name`` asks Mosaic for."""
    return [int(size) for size in re.findall(
        rf"%{name}[.\d]* = [^\n]*scoped_memory_configs[^\n]*?\"size\":"
        r"\"(\d+)\"", text)]


def test_deepseek_serve_programs_compile_and_fit(v5e):
    """The benchmark's DeepSeek-V3.2-Exp configuration (published widths:
    hidden 7168, 128 heads of 128 + 64 q/k and 128 v values over a latent
    of 512 and one shared rotated key of 64, an indexer of 64 heads of 128
    that picks 2048, a dense layer of 18432 then 16 HELD of 256 experts of
    2048 top-8 in 8 groups beside a shared one; 1 + 4 layers, bf16) under
    its cell's deployment (8 slots of 66048 tokens, 32769 pages of a latent
    row and an index key's row): the prefill chunk with the step's rows
    along and the decode step, the indexer's two kernels and the picked
    attention's once a layer and group of rows, the last under the step's
    name or the chunk's, the experts' kernel once an EXPERT layer; 9.27 GB
    of weights and the 4.03 GB pool beside the programs' own memory on one
    16 GB chip."""
    from ray_tpu.ops.latent_attention import _VMEM_LIMIT
    from ray_tpu.ops.moe import tile_sizes
    from ray_tpu.ops.paged_attention import resolve_impl

    cfg, held, programs = cell_programs(v5e, "deepseek_v32_exp_l5",
                                         "deepseek_v32_longdocs")
    assert (cfg.embed_dim, cfg.head_dim, cfg.hidden_dim) == (7168, 192, 2048)
    assert (cfg.lead_layers, cfg.expert_layers, cfg.period) == (1, 4, 1)
    assert cfg.mlp_width("swiglu") == 18432
    assert (cfg.moe_groups, cfg.moe_top_groups, cfg.held) == (8, 4, (0, 16))
    assert cfg.indexer.topk == 2048 and cfg.latent_rope[1] > 1.87
    # an expert of 88 MB does not fit VMEM: the one cell whose experts'
    # kernel walks the hidden width in column tiles (ISSUE 64), a turn's
    # (512 + 8) x 8 pairs and a step's 64 alike
    assert [tile_sizes(pairs, 16, 7168, 2048, 2).cols
            for pairs in (4160, 64)] == [256, 256]
    lane = resolve_impl(cfg)
    assert lane == "pallas"
    assert 13.2e9 < held < 13.4e9
    step = {**PICKED_KERNELS, "picked_latent_step_attention": 5,
            "moe_grouped_matmul": 4}
    calls = {"prefill": {**{k: 2 * n for k, n in PICKED_KERNELS.items()},
                         "picked_latent_chunk_attention": 5,
                         "picked_latent_step_attention": 5,
                         "moe_grouped_matmul": 4},
             "decode": step}
    for name, (program, args) in programs.items():
        compiled = jax.jit(
            functools.partial(program, cfg, attn=lane, moe_info=True),
            donate_argnums=(6,)).lower(*args).compile()
        assert kernel_calls(compiled) == calls[name], name
        # the step's rows are gathered, a layer; the chunk's never
        text = compiled.as_text()
        assert len(_run_gathers(text)) == 5, name
        assert all(size <= _VMEM_LIMIT for size in _vmem_asked(
            text, "picked_latent_chunk_attention"))
        total = fits(compiled)
        temp = compiled.memory_analysis().temp_size_in_bytes
        print(name, total / 1e9, temp / 1e9)
        assert total < 14.6e9, f"{name}: {total / 1e9:.1f} GB"
        assert temp < 1.2e9, f"{name}: {temp / 1e6:.0f} MB of temporaries"


@pytest.mark.parametrize("S,K,P", [(8, 1, 4128), (1, 512, 4128),
                                   (1, 512, 10240)],
                         ids=["step_8slots", "chunk_k512",
                              "chunk_k512_at_the_models_limit"])
def test_picked_latent_attention_compiles_at_the_cells_shapes(v5e, S, K, P):
    """``ops.picked_latent_attention`` alone at DeepSeek-V3.2-Exp's sizes:
    128 heads over rows of 640 lanes, 64 index heads of 128 over rows of 128
    lanes, 2048 picked, pages of 16 through a table of 4128 — the 8 slots'
    step and a 512 chunk — and the chunk through a table of the model's own
    163,840 positions, which the kernel walks in two segments. The chunk
    brings its chosen rows together ITSELF (ISSUE 62): no gather makes a
    run of [*, 2048, 640], and every call of the kernel asks Mosaic for
    less VMEM than the limit."""
    from ray_tpu.ops.latent_attention import _VMEM_LIMIT
    from ray_tpu.ops.indexed_attention import IndexerSizes
    from ray_tpu.ops.picked_latent_attention import picked_latent_attention

    chip = SingleDeviceSharding(v5e.devices[0])
    name = ("picked_latent_step_attention" if K == 1
            else "picked_latent_chunk_attention")
    sizes = IndexerSizes(indexer_num_heads=64, indexer_head_dim=128,
                         topk=2048)
    compiled = jax.jit(functools.partial(
        picked_latent_attention, sizes=sizes, sm_scale=0.135,
        impl="pallas")).lower(
        on(chip, (S, K, 128, 512)), on(chip, (S, K, 128, 64)),
        on(chip, (S, K, 64, 128)), on(chip, (S, K, 64), jnp.float32),
        on(chip, (32769, 16, 640)), on(chip, (32769, 16, 128)),
        on(chip, (S, P), jnp.int32), on(chip, (S, K), jnp.int32),
        on(chip, (S,), jnp.int32)).compile()
    assert kernel_calls(compiled) == {name: 1 if P == 4128 else 2,
                                       "index_score": 1, "indexed_select": 1}
    text = compiled.as_text()
    assert (K == 1) == bool(_run_gathers(text))
    asked = _vmem_asked(text, name)
    assert len(asked) == (1 if P == 4128 else 2)
    assert all(size <= _VMEM_LIMIT for size in asked), asked
    temp = compiled.memory_analysis().temp_size_in_bytes
    print(name, temp / 1e9)
    assert temp < 0.9e9, f"{temp / 1e6:.0f} MB of temporaries"
