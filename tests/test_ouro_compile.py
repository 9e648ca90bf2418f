"""Ouro-2.6B's cell compiled for the chip, without the chip (ISSUE 65):
``ouro_reason``'s step and chunk programs at the published widths, ALL 48
layers and 4 passes, under the cell's deployment, for a described ``v5e`` —
ONE layer's body under a loop, so the program does not grow with the depth:
one ``paged_attention`` call in the step's body, the stacked pools written
and read in place. And what stays as it was: a model without passes traces
to a call a layer over a list of pools, and ``pool_index=None`` is the
kernel's program as it stood. The fixtures and helpers are
``tests/tpu_compile_harness.py``'s.
"""

import functools
import time

import jax
import jax.numpy as jnp

from tests.tpu_compile_harness import (  # noqa: F401
    WITH_THE_STEPS_ROWS, as_a_tpu_process, cell_programs, compiled,
    copied_shapes, fits, kernel_calls, serving_program, v5e)

CONFIG, CELL = "ouro_2_6b", "ouro_reason"


def test_ouro_serve_programs_are_one_layers_body_whatever_the_depth(
        v5e, compiled):
    """5.34 GB of weights and the 8.88 GB pool pair (353 pages x 192 pools
    x 16 tokens x 2048 lanes, K and V) as the two programs' arguments. The
    STEP compiled for the described chip holds ONE ``paged_attention``
    custom call for its 192 layer applications; the pool pair is aliased to
    the output whole and no copy or slice of its shape (or of a pool's of
    it) is made; temporaries under 1.5 GB; it compiles in seconds where 192
    unrolled layers would take tens of minutes (printed). The CHUNK is held
    by its jaxpr (the same body, traced not compiled: the tests' clock):
    two loops, the pool pair their carry, two kernel calls in the body (its
    own rows' and the step's it takes along)."""
    from ray_tpu.ops.paged_attention import resolve_impl

    cfg, held, programs = cell_programs(v5e, CONFIG, CELL)
    assert resolve_impl(cfg) == "pallas"
    assert (cfg.num_layers, cfg.loop_passes, cfg.output_norms) == (48, 4,
                                                                   True)
    assert 14.1e9 < held < 14.3e9
    (pool,) = programs["decode"][1][6]
    assert pool.k.shape == (353, 192, 16, 2048)
    pool_bytes = 2 * pool.k.size * 2
    shapes = {("bf16", ",".join(map(str, shape)))
              for shape in (pool.k.shape, pool.k.shape[:1] + pool.k.shape[2:])}
    program, args = programs["decode"]
    t0 = time.perf_counter()
    made = serving_program(compiled, (CONFIG, CELL, "decode",
                                      WITH_THE_STEPS_ROWS),
                           cfg, program, args, attn="pallas", loop_info=True)
    print(f"{CELL} decode: compiled for the described v5e in "
          f"{time.perf_counter() - t0:.1f} s")
    assert kernel_calls(made) == {"paged_attention": 1}
    text, ma = made.as_text(), made.memory_analysis()
    assert text.count(" while(") == 2  # the passes, the layers
    assert ma.alias_size_in_bytes >= pool_bytes
    assert ma.temp_size_in_bytes < 1.5e9, ma.temp_size_in_bytes
    assert not shapes & copied_shapes(made)
    assert "[353,192,16,2048]{3,2,1,0} dynamic-slice(" not in text
    assert "bf16[353,16,2048]" not in text  # no pool cut out
    fits(made)

    program, args = programs["prefill"]
    chunk = jax.make_jaxpr(functools.partial(
        program, cfg, attn="pallas", loop_info=True))(*args)
    (outer,) = (e for e in chunk.eqns if e.primitive.name == "scan")
    (inner,) = (e for e in outer.params["jaxpr"].eqns
                if e.primitive.name == "scan")
    for loop in (outer, inner):
        carried = loop.invars[loop.params["num_consts"]:][
            :loop.params["num_carry"]]
        assert [v.aval.shape for v in carried].count(pool.k.shape) == 2
    calls = lambda jaxpr: [e.params.get("name") for e in jaxpr.eqns].count(
        "_paged_attention_pallas")
    assert calls(inner.params["jaxpr"].jaxpr) == 2
    assert not calls(outer.params["jaxpr"].jaxpr) and not calls(chunk.jaxpr)


def test_the_contiguous_prefill_lays_its_cache_out_once(v5e):
    """``BenchLLMServer.reference_check`` runs the CONTIGUOUS cache beside
    the weights and the pool (``serve.llm``'s ``_prefill``, caches donated):
    320 tokens into a (pass, layer) cache of 324 for the described chip.
    The cache is the loops' carry, aliased to the output whole and never
    copied; what is left of the temporaries is XLA's layout of ``wq``,
    ``wk`` and ``wv`` for the loop's dots (1.21 GB; PERF.md 7). As xs and ys
    of the scans the cache was laid out anew twice: 1.93 GB, which the
    issue's 8 slots / 353 pages left no room for."""
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models.decode import init_caches, prefill
    from ray_tpu.models.transformer import init_params
    from tests.tpu_compile_harness import on, program_config

    _, cfg = program_config(CONFIG)
    chip = SingleDeviceSharding(v5e.devices[0])
    place = lambda tree: jax.tree.map(
        lambda a: on(chip, a.shape, a.dtype), tree)
    params = place(jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.PRNGKey(0)))
    caches = place(jax.eval_shape(
        functools.partial(init_caches, cfg, 1, 324)))
    (cache,) = caches
    assert cache.k.shape == (192, 1, 16, 336, 128)  # whole tiles of positions
    t0 = time.perf_counter()
    made = jax.jit(functools.partial(prefill, cfg), donate_argnums=(2,)).lower(
        params, on(chip, (1, 320), jnp.int32), caches).compile()
    print(f"{CONFIG} contiguous prefill: compiled for the described v5e in "
          f"{time.perf_counter() - t0:.1f} s")
    ma = made.memory_analysis()
    assert ma.alias_size_in_bytes >= 2 * cache.k.size * 2
    assert ma.temp_size_in_bytes < 1.3e9, ma.temp_size_in_bytes
    assert ("bf16", "192,1,16,336,128") not in copied_shapes(made)


def test_a_model_without_passes_is_the_program_it_was():
    """A jaxpr, no compile: ``mistral7b_v03_l16``'s sizes at 2 layers trace
    to a step program with a ``paged_attention`` call a layer over a LIST
    of pools and no loop; and ``paged_attention(..., pool_index=None)``
    traces to the jaxpr of the call without the argument."""
    import dataclasses
    import functools

    from ray_tpu.models.decode import init_paged_caches, paged_decode_step
    from ray_tpu.models.transformer import init_params
    from ray_tpu.ops.paged_attention import paged_attention
    from tests.tpu_compile_harness import program_config

    _, cfg = program_config("mistral7b_v03_l16")
    cfg = dataclasses.replace(cfg, num_layers=2)
    assert not cfg.looped and not cfg.output_norms
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    caches = jax.eval_shape(lambda: init_paged_caches(cfg, 33, 16, 8))
    assert len(caches) == 2 and caches[0].k.shape == (33, 16, 1024)
    of = lambda shape, dtype=jnp.int32: jax.ShapeDtypeStruct(shape, dtype)
    step = jax.make_jaxpr(functools.partial(
        paged_decode_step, cfg, attn="pallas"))(
            params, of((4,)), of((4,)), of((4,)), of((4, 8)), of((4, 8)),
            caches, of((4,), jnp.float32), of((4,), jnp.uint32))
    text = str(step)  # (the kernel's jitted wrapper is printed once)
    assert text.count("jaxpr=_paged_attention_pallas\n") == 2
    assert text.count("name=paged_attention\n") == 1
    assert not {"scan", "while"} & {e.primitive.name for e in step.eqns}
    q = of((4, 1, 32, 128), jnp.bfloat16)
    pool = of((33, 16, 1024), jnp.bfloat16)
    stacked = of((33, 3, 16, 1024), jnp.bfloat16)

    def call(pool, *index, **kw):
        """(the call's jaxpr as text, the kernel's operands)."""
        made = jax.make_jaxpr(lambda q, k, v, t, n, *w: paged_attention(
            q, k, v, t, n, impl="pallas", **kw,
            **({"pool_index": w[0]} if w else {})))(
                q, pool, pool, of((4, 8)), of((4,)), *index)
        (wrapper,) = (e for e in made.eqns if "jaxpr" in e.params)
        (kernel,) = (e for e in wrapper.params["jaxpr"].eqns
                     if e.primitive.name == "pallas_call")
        return str(made), len(kernel.invars)

    assert call(pool, pool_index=None) == call(pool)
    # four scalars, q, the two pools; told which pool, one scalar more
    assert call(pool)[1] == 7 and call(stacked, of(()))[1] == 8
