"""Keye-VL-2.0-30B-A3B's cell compiled for the chip, without the chip (ISSUE
63: out of ``tests/test_tpu_compile.py``, names and assertions as they
were): the cell's two serving programs at the published widths for a
described ``v5e`` — compiled once for the two tests that read them
(``compiled``) —, the selection alone, and the check's forward. The fixtures
and helpers are ``tests/tpu_compile_harness.py``'s.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from tests.tpu_compile_harness import (  # noqa: F401
    WITH_THE_STEPS_ROWS, as_a_tpu_process, cell_programs,
    check_forward_given_the_routes, compiled, copied_shapes, fits,
    kernel_calls, names, on, serving_program, v5e)


CELL = ("keye_vl2_30b_a3b_l5", "keye_longctx")


def _keye_compiled(v5e, compiled):
    """``keye_longctx``'s two serving programs compiled once for the tests
    that read them: (cfg, bytes held, {name: (compiled, arguments)})."""
    from ray_tpu.ops.paged_attention import resolve_impl

    cfg, held, programs = cell_programs(v5e, *CELL)
    assert resolve_impl(cfg) == "pallas"
    return cfg, held, {
        name: (serving_program(compiled, (*CELL, name, WITH_THE_STEPS_ROWS),
                               cfg, program, args, attn="pallas",
                               moe_info=True), args)
        for name, (program, args) in programs.items()}


def test_keye_serve_programs_compile_and_fit(v5e, compiled):
    """The benchmark's Keye-VL-2.0-30B-A3B configuration (published widths:
    hidden 2048, 32 query heads over 4 K/V heads of 128, an indexer of 16
    heads of 64, 128 experts of 768; 5 layers, bf16) under its cell's
    deployment (8 slots of 49664 tokens): the prefill chunk with the step's
    rows along and the decode step, the indexer's kernels once a layer and
    group of rows — scores, the counting selection, the chunk's masked
    attention or the step's paged kernel over its gathered run — and the
    experts' kernel once a layer; 7.50 GB of weights and the 4.58 GB pool
    (K, V and the index key a token, in a row of 128 lanes: what the chip
    held for its 64 before) beside the programs' own memory on one 16 GB
    chip."""
    cfg, held, programs = _keye_compiled(v5e, compiled)
    assert (cfg.embed_dim, cfg.head_dim, cfg.hidden_dim) == (2048, 128, 768)
    assert cfg.num_heads // cfg.kv_heads == 8 and cfg.period == 1
    assert cfg.indexer.topk == 2048 and cfg.mrope_section == (16, 24, 24)
    assert 11.9e9 < held < 12.2e9
    calls = {"prefill": {"index_score": 10, "indexed_select": 10,
                         "indexed_chunk_attention": 5,
                         "indexed_step_attention": 5,
                         "moe_grouped_matmul": 5},
             "decode": {"index_score": 5, "indexed_select": 5,
                        "indexed_step_attention": 5,
                        "moe_grouped_matmul": 5}}
    for name, (made, _) in programs.items():
        assert kernel_calls(made) == calls[name], name
        total = fits(made)
        temp = made.memory_analysis().temp_size_in_bytes
        assert total < 13.2e9, f"{name}: {total / 1e9:.1f} GB"
        assert temp < 0.3e9, f"{name}: {temp / 1e6:.0f} MB of temporaries"


def test_keye_programs_read_their_pools_in_place(v5e, compiled):
    """ISSUE 60, the change's counter — bytes of whole-pool copies a turn,
    1.53 GB before it, 0 after: at the cell's shapes neither program's
    compiled text holds a ``copy`` of a pool's shape (a 64-lane index-key
    row made the write of a layer's keys two relayouts of its whole pool,
    153 MB each), nor a ``gather`` of a slot's K or V out of its pages over
    the table's 3,104: the chunk's kernel walks the table itself. The index
    keys' gather stays, over rows of whole lane tiles (the chip's timing
    kept it: PERF.md 6, PR 60). The scatters of the new index keys, one a
    layer, take the donated pool as it came — in the chunk's program, which
    is every turn of the cell; for the PLAIN step the compiler still
    prefetches index-key pools into its fast memory in slices for that
    gather and copies them back (its own doing, and the parent's too:
    PERF.md 7) — and all fifteen pools are aliased."""
    cfg, _, programs = _keye_compiled(v5e, compiled)
    names = {"float32": "f32", "bfloat16": "bf16"}
    for name, (made, args) in programs.items():
        text = made.as_text()
        pools = jax.tree.leaves(args[6])
        held = {(names[a.dtype.name], ",".join(map(str, a.shape)))
                for a in pools}
        copied = held & copied_shapes(made)
        assert not copied, f"{name}: whole-pool copies of {copied}"
        assert held == {("bf16", "24833,16,512"), ("bf16", "24833,16,128")}
        # nor does the compiler move a pool through its fast memory in
        # slices and back (its own prefetch for the gather), but in the
        # plain step, where it still takes index-key pools that way
        moved = set(re.findall(r"(?:slice|copy)-start\(%caches_\d+__(\w+?)[.\d]*\)",
                               text))
        assert moved <= ({"ik"} if name == "decode" else set()), (name, moved)
        contexts = set(re.findall(
            r"= bf16\[(?:\d+,)?3104,16,(\d+)\]\S* gather\(", text))
        assert contexts == {"128"}, f"{name}: gathers of contexts {contexts}"
        written = re.findall(
            r"= bf16\[24833,16,128\]\S* fusion\(%([\w-]+?)[.\d]*, [^\n]*/scatter\"",
            text)
        assert len(written) == cfg.num_layers, (name, written)
        if name == "prefill":  # (the plain step's prefetched ones apart)
            assert sorted(written) == [
                f"caches_{i}__ik" for i in range(cfg.num_layers)], written
        assert made.memory_analysis().alias_size_in_bytes == sum(
            a.size * a.dtype.itemsize for a in pools)


@pytest.mark.parametrize("rows", [(1, 512), (8, 1)])
def test_keye_selection_compiles_at_the_cells_shapes(v5e, rows):
    """ISSUE 53: ``indexed_select`` alone at the cell's two shapes — a 512
    chunk's rows and the 8 slots' step, over a table of 49,664 lanes whose
    last segment is a short one, ``topk`` 2048: the scores stay in HBM (no
    temporary of the table's width), the live segments are copied in by
    hand and the passes loop over them under a ``while``."""
    from ray_tpu.ops import indexed_attention as ia

    chip = SingleDeviceSharding(v5e.devices[0])
    compiled = jax.jit(
        lambda scores, positions: ia.select(scores, positions, 2048, False,
                                            passes=True)).lower(
        on(chip, (*rows, 49664), jnp.float32),
        on(chip, rows, jnp.int32)).compile()
    assert kernel_calls(compiled) == {"indexed_select": 1}
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20


def test_keye_check_programs_fit_beside_the_pool(v5e):
    """The largest program ``reference_check`` runs in the replica beside
    the weights and the pool, on the cell's 8704-token check prompt and the
    32 tokens served behind it: the cell states limits GIVEN the routes, so
    the uncached whole-sequence ``forward`` up to whole tiles (the cached
    prefill of the prompt is the same kernels over fewer rows, with a pool
    of the layer's own of 114 MB). It does not go through
    ``[32, S, S]`` scores: the kind's chunk kernel takes any number of
    rows. The forward's 2.76 GB are its [8832, 151936] bf16 logits, which the
    harness slices behind the program: why the configuration holds 5 layers
    and not 6 (13.94 GB held would leave them 0.2 GB of slack). ``held``
    counts an index key at the 128 lanes of its row since PR 60 (0.25 GB
    more than the 64 it counted before, which the chip held in 128 too)."""
    held, compiled = check_forward_given_the_routes(v5e, *CELL)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2.9e9, f"forward: {temp / 1e9:.2f} GB of temporaries"
    assert held + temp < 15.1e9
