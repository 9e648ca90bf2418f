"""The described chip, for every file that asks its compiler (ISSUE 63):
the ``v5e:2x2`` description, the fixture that steers the code under test the
way a TPU process would go, what a compiled program's text and memory say,
a benchmark cell's two serving programs at its published widths — and ONE
memo of compiled objects a file (``compiled``), so that the tests that ask
about the same compiled program ask the same object.

``tests/test_tpu_compile.py`` holds the kernels, the training steps and the
Mistral / GPT-2 serving programs; a model's described-chip cases stand in a
file of their own beside its other tests (``tests/test_<model>_compile.py``):
``--dist loadfile`` hands a whole file to one worker. A test module imports
the two fixtures it uses by name (``v5e``, ``as_a_tpu_process``, and
``compiled`` where it shares).
"""

import functools
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")


@pytest.fixture(autouse=True)
def as_a_tpu_process(monkeypatch):
    """Compile for the described chip: kernels go through Mosaic and
    ``auto`` picks the TPU lanes. The persistent compile cache is off — a
    described-device executable can be written but never read back. On the
    way out JAX's own caches are cleared: a kernel's jitted wrapper traced
    here holds a Mosaic call, and a later test of the same process that
    calls it at the same shapes on the CPU would be handed that trace
    ("Only interpret mode is supported on CPU backend": six cases of
    ``tests/test_serve_fused_turn.py[moe_debug]`` whenever xdist paired the
    two files, PR 41)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()
    jax.clear_caches()


@pytest.fixture(scope="module")
def compiled():
    """``compiled(key, build)``: what ``build()`` compiled the first time
    ``key`` — (configuration, cell, program, variant such as "with the
    step's rows" / "alone", keywords) — was asked for. It lives as long as
    one file's tests and goes with them. It holds compiled objects
    (``.lower().compile()``) and no traced function, so ``as_a_tpu_process``
    clears JAX's caches after every test as before: a compiled object does
    not live in them."""
    memo = {}

    def once(key, build):
        if key not in memo:
            memo[key] = build()
        return memo[key]

    yield once
    memo.clear()


WITH_THE_STEPS_ROWS, ALONE = "with the step's rows", "alone"


def serving_program(compiled, key, cfg, program, args, **kw):
    """A serving program compiled as the scheduler jits it (the pools
    donated), once a (``key``, keywords) of the file's memo."""
    return compiled(
        (*key, tuple(sorted(kw.items()))),
        lambda: jax.jit(functools.partial(program, cfg, **kw),
                        donate_argnums=(6,)).lower(*args).compile())


def without_the_steps_rows(args):
    """The chunk's program's arguments with no ``StepRows``: it goes
    alone."""
    return args[:11] + (None,) + args[12:]


def on(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def kernel_names(text: str) -> set:
    """The names the compiled kernels carry: a ``pallas_call``'s ``name=``
    becomes part of the custom call's instruction name (``%jvp_<name>_.1``,
    ``%<name>.1``), which is what a profiler trace shows as the op."""
    return set(re.findall(
        r"%(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text))


def kernel_calls(compiled) -> dict:
    """How many custom calls of each kernel name the compiled text holds."""
    names = [re.sub(r"[.\d]+$", "", k)
             for k in kernel_names(compiled.as_text())]
    return {k: names.count(k) for k in set(names)}


def names(compiled) -> set:
    return {re.sub(r"[.\d]+$", "", name)
            for name in kernel_names(compiled.as_text())}


def fits(compiled) -> int:
    ma = compiled.memory_analysis()
    total = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
             + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 2**30:.1f} GiB does not fit 16 GB"
    return total


def copied_shapes(compiled) -> set:
    """(element type, dimensions) of every copy in a compiled program's
    text, as the text writes them: ``("f32", "16,8,13,128,640")``."""
    return set(re.findall(r"= (\w+)\[([\d,]+)\]\S* copy(?:-start)?\(",
                          compiled.as_text()))


def program_config(config: str):
    """The program's configuration for a benchmark configuration, at its
    published widths: (the manifest, cfg)."""
    from perfbench.lib import configs
    from perfbench.lib import manifest as manifest_lib

    manifest = manifest_lib.load()
    hp = manifest_lib.config(manifest, config)
    return manifest, configs.build_program_config(*configs.program_overrides(
        hp, manifest_lib.read_json_from_bench("families", hp["model_type"])))


def deployment(manifest, cell: str) -> dict:
    from perfbench.lib import manifest as manifest_lib

    return manifest_lib.read_json(manifest, "cells", cell)["deployment"]


def cell_programs(v5e, config: str, cell: str):
    """A benchmark cell's two serving programs as its scheduler calls them,
    at the configuration's published widths under the cell's deployment:
    (cfg, bytes held by weights and pool, {name: (program, arguments)}). The
    chunk's program takes the step's rows along."""
    from ray_tpu.models.decode import (StepRows, init_paged_caches,
                                       paged_decode_step,
                                       paged_prefill_into_slot)
    from ray_tpu.models.transformer import ATTENTION, SLIDING, init_params

    manifest, cfg = program_config(config)
    dep = deployment(manifest, cell)
    slots, chunk, T = dep["slots"], dep["prefill_chunk"], dep["page_tokens"]
    pages = dep["arena_len"] // T
    chip = SingleDeviceSharding(v5e.devices[0])

    def place(tree):
        return jax.tree.map(lambda a: on(chip, a.shape, a.dtype), tree)

    params = place(jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.PRNGKey(0)))
    # a model with window layers: their pool as the scheduler sizes it, and
    # a pair of tables a pool
    window = {}
    if SLIDING in cfg.kinds:
        window["window_pages"] = 1 + slots * min(
            pages, -(-(cfg.sliding_window + chunk) // T) + 1)
    caches = place(jax.eval_shape(functools.partial(
        init_paged_caches, cfg, dep["kv_pages"], T, pages, slots=slots,
        **window)))
    held = sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves((params, caches)))
    by_pool = lambda t: {ATTENTION: t, SLIDING: t} if window else t
    table = by_pool(on(chip, (slots, pages), jnp.int32))
    row = by_pool(on(chip, (pages,), jnp.int32))
    ids = functools.partial(on, chip, dtype=jnp.int32)
    step = (ids((slots,)), ids((slots,)), table, table,
            on(chip, (slots,), jnp.float32), on(chip, (slots,), jnp.uint32))
    return cfg, held, {
        "prefill": (paged_prefill_into_slot,
                    (params, ids((1, chunk)), ids(()), ids(()), row, row,
                     caches, ids((slots,)), ids(()),
                     on(chip, (), jnp.float32), on(chip, (), jnp.uint32),
                     StepRows(*step), ids(()))),
        "decode": (paged_decode_step,
                   (params, ids((slots,)), *step[:4], caches, *step[4:])),
    }


def pageless_programs(v5e, cfg, slots: int, chunk: int):
    """The scheduler's two programs for a model none of whose layers holds a
    page, as it calls them: no page table (None), states a slot, the
    step's rows along in the chunk's program. (cfg's bytes held by weights
    and states, {name: (program, arguments)})."""
    from ray_tpu.models.decode import (StepRows, init_paged_caches,
                                       paged_decode_step,
                                       paged_prefill_into_slot)
    from ray_tpu.models.transformer import init_params

    assert not cfg.holds_pages
    chip = SingleDeviceSharding(v5e.devices[0])
    place = lambda tree: jax.tree.map(
        lambda a: on(chip, a.shape, a.dtype), tree)
    params = place(jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.PRNGKey(0)))
    caches = place(jax.eval_shape(functools.partial(
        init_paged_caches, cfg, 1, chunk, 1, slots=slots)))
    held = sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves((params, caches)))
    ids = functools.partial(on, chip, dtype=jnp.int32)
    rows = (on(chip, (slots,), jnp.float32), on(chip, (slots,), jnp.uint32))
    return held, {
        "prefill": (paged_prefill_into_slot,
                    (params, ids((1, chunk)), ids(()), ids(()), None, None,
                     caches, ids((slots,)), ids(()),
                     on(chip, (), jnp.float32), on(chip, (), jnp.uint32),
                     StepRows(ids((slots,)), ids((slots,)), None, None,
                              *rows), ids(()))),
        "decode": (paged_decode_step,
                   (params, ids((slots,)), ids((slots,)), ids((slots,)),
                    None, None, caches, *rows)),
    }


# ------------------------------------ what several models' files ask alike


def check_forward_given_the_routes(v5e, config: str, cell: str):
    """The largest program ``reference_check`` runs in the replica beside
    the weights and the pools of a cell that states limits GIVEN the routes:
    the uncached whole-sequence ``forward`` (``return_routes``) over the
    check prompt and the tokens served behind it, up to whole tiles.
    (bytes held by weights and pools, the program compiled)."""
    from perfbench.lib import manifest as manifest_lib
    from perfbench.lib.serve_app import GIVEN_PAD
    from ray_tpu.models.transformer import forward

    cfg, held, programs = cell_programs(v5e, config, cell)
    asks = manifest_lib.read_json(manifest_lib.load(), "cells", cell)
    assert {"given_logit_err", "given_logit_rms_err"} <= set(
        asks["check_tolerance"])
    first = asks["check_prompt_tokens"] - 1
    n = first + asks["check_new_tokens"]
    params = programs["decode"][1][0]

    def run(params, tokens):
        logits, routes = forward(cfg, params, tokens, return_routes=True)
        return logits[0, first:n].astype(jnp.float32), routes

    tokens = on(params["embed"]["table"].sharding,
                (1, n + -n % GIVEN_PAD), jnp.int32)
    return held, jax.jit(run).lower(params, tokens).compile()


def a_turn_with_a_chunk_is_one_program_at_the_cells_shapes(
        v5e, compiled, config, cell, held_gb):
    """ISSUE 40: the chunk's program with the step's rows along, at the
    cells' real shapes (512 + 32 rows through every projection): TWO
    ``paged_attention`` calls a layer, at the chunk's shape and the step's,
    ONE ``moe_grouped_matmul`` a layer over (512 + 32) x 8 pairs (the tiles
    of a chunk's 4,096), the head over 33 rows and not 512, and temporaries
    no larger than the chunk's program alone holds: what chat and docs stand
    at (15.3-15.4 GB of 16) leaves it no room to add."""
    from ray_tpu.models.decode import StepRows
    from ray_tpu.ops import moe
    from ray_tpu.ops.paged_attention import resolve_impl

    cfg, held, programs = cell_programs(v5e, config, cell)
    assert held_gb[0] * 1e9 < held < held_gb[1] * 1e9
    program, args = programs["prefill"]
    assert isinstance(args[11], StepRows)
    kw = {"attn": resolve_impl(cfg)}
    if cfg.mlp == "moe":
        kw["moe_info"] = True
        assert moe.tile_sizes((512 + 32) * 8, 64, 2048, 1024, 2) == \
            moe.tile_sizes(512 * 8, 64, 2048, 1024, 2) == (128, 1024)

    fused = serving_program(compiled, (config, cell, "prefill",
                                       WITH_THE_STEPS_ROWS),
                            cfg, program, args, **kw)
    calls = kernel_calls(fused)
    assert calls.pop("paged_attention") == 2 * cfg.num_layers
    if cfg.mlp == "moe":
        assert calls.pop("moe_grouped_matmul") == cfg.num_layers
        assert "ragged-dot" not in fused.as_text()
    assert not calls
    fits(fused)
    # no logits over the chunk's 512 rows: the head sees the sampled rows
    vocab = cfg.vocab_size
    assert not re.search(rf"\[(1,)?512,{vocab}\]", fused.as_text())
    assert re.search(rf"\[(1,)?33,{vocab}\]", fused.as_text())
    alone = serving_program(compiled, (config, cell, "prefill", ALONE), cfg,
                            program, without_the_steps_rows(args), **kw)
    assert kernel_calls(alone)["paged_attention"] == cfg.num_layers
    temp, temp_alone = (c.memory_analysis().temp_size_in_bytes
                        for c in (fused, alone))
    assert temp < 1.1 * temp_alone + 16e6, (temp, temp_alone)


def the_state_kinds_chunk_program_takes_the_rows_along_in_place(
        v5e, compiled, cell):
    """ISSUE 44, at the cells' real shapes: the chunk's program with the
    step's rows along against the chunk alone. What it adds is the step's
    kernels, a group of rows a layer; the states (Brumby: 4.4 GB beside 8.4
    of weights on 16) and the pools are still updated in place — the same
    bytes aliased, no copy of the shape of a state or a pool of 30 MB or
    more, temporaries within 128 MB — and
    what a layer does with the step's rows alone (the pass over every slot's
    states, the choice of blocks) stands under a conditional, one a layer,
    that a program none of whose rows is live does not enter."""
    from ray_tpu.models.transformer import STATE_KINDS

    if cell == "brumby_longgen":
        config = "brumby_14b_l8"
        manifest, cfg = program_config(config)
        dep = deployment(manifest, cell)
        _, programs = pageless_programs(v5e, cfg, dep["slots"],
                                        dep["prefill_chunk"])
    else:
        config = "minicpm_sala_l16"
        cfg, _, programs = cell_programs(v5e, config, cell)
    program, args = programs["prefill"]
    fused = serving_program(compiled, (config, cell, "prefill",
                                       WITH_THE_STEPS_ROWS),
                            cfg, program, args, attn="pallas")
    alone = serving_program(compiled, (config, cell, "prefill", ALONE), cfg,
                            program, without_the_steps_rows(args),
                            attn="pallas")
    stateful = sum(kind in STATE_KINDS for kind in cfg.kinds)
    step_kernel = ("power_retention_step" if cell == "brumby_longgen"
                   else "linear_attention_step")
    assert kernel_calls(fused)[step_kernel] == stateful
    assert step_kernel not in kernel_calls(alone)
    conditionals = lambda c: c.as_text().count(" conditional(")
    assert conditionals(fused) - conditionals(alone) == cfg.num_layers
    own, base = fused.memory_analysis(), alone.memory_analysis()
    assert own.alias_size_in_bytes == base.alias_size_in_bytes > 2e9
    assert own.temp_size_in_bytes < base.temp_size_in_bytes + 128e6
    names = {"float32": "f32", "bfloat16": "bf16"}
    # (the 4 MB normaliser beside a retention state moves between memories)
    held = {(names[a.dtype.name], ",".join(map(str, a.shape)))
            for a in jax.tree.leaves(args[6])
            if a.size * a.dtype.itemsize > 30e6}
    assert held and not held & copied_shapes(fused)
    fits(fused)
