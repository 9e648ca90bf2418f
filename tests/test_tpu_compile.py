"""What the chip's compiler accepts (ISSUE 21) — asked here, without the chip.

libtpu compiles for a TPU that is described and not attached
(``jax.experimental.topologies``), so the kernels and programs of the main
path are compiled at their real widths against a ``v5e:2x2`` description:
flash attention forward and forward+backward, paged attention (decode K=1,
verify K=3, 4 and 5 and a prefill chunk K=512, at GPT-2 small's and
Mistral-7B's widths), the GPT-2 small train step on one chip and sharded over
four, and the three serve programs. Interpret mode cannot see what these see: a
slice not aligned to the tiling, a kernel GSPMD cannot partition, a program
that does not fit 16 GB. Nothing runs — a compile that passes is not a chip
run.

``jax.default_backend()`` says "cpu" here, so the fixture steers the code
under test the way a TPU process would go: kernels compiled (not
interpreted), ``auto`` lanes resolved for a TPU. The fixtures and helpers
are ``tests/tpu_compile_harness.py``'s (ISSUE 63).

THIS FILE holds the kernels (flash, paged, the experts', latent,
state-space), the training steps and the Mistral / GPT-2 / toy serving
programs. A model's cases at its cell's shapes stand beside the model, names
and assertions as they were here: ``test_olmoe_compile.py`` (with
``olmoe_reason``'s case of the turn as one program),
``test_mellum_compile.py``, ``test_minicpm_sala_compile.py`` and
``test_brumby_compile.py`` (each with its case of the state kinds' chunk
program), ``test_keye_compile.py`` (with ``indexed_select`` alone),
``test_glm_compile.py``, ``test_deepseek_v32_compile.py``. Whoever cites
"tests/test_tpu_compile.py" for one of those cases means that file.
"""

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

from tests.tpu_compile_harness import (  # noqa: F401
    HBM_BYTES, a_turn_with_a_chunk_is_one_program_at_the_cells_shapes,
    as_a_tpu_process, compiled, fits, kernel_calls, kernel_names, names, on,
    v5e)

FLASH_KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
                 "flash_attention_bwd_dkv")
GPT2S = dict(H=12, Hkv=12, D=64)
GQA128 = dict(H=32, Hkv=8, D=128)  # llama3_8b's head shape


# ------------------------------------------------------------------ kernels


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "fwd_bwd"])
@pytest.mark.parametrize("shape", [
    dict(B=16, S=1024, **GPT2S), dict(B=2, S=2048, **GQA128),
    # the benchmark's training cells as a chip sees them: gpt2s_train whole,
    # mistral7b_train_4chip's shard of fsdp=2 x tp=2
    dict(B=128, S=1024, **GPT2S), dict(B=4, S=4096, H=16, Hkv=4, D=128)],
    ids=["gpt2s", "gqa_d128", "gpt2s_train", "mistral7b_train_4chip"])
def test_flash_attention_compiles(v5e, shape, grad):
    from ray_tpu.ops.flash_attention import flash_attention

    chip = SingleDeviceSharding(v5e.devices[0])
    q = on(chip, (shape["B"], shape["S"], shape["H"], shape["D"]))
    kv = on(chip, (shape["B"], shape["S"], shape["Hkv"], shape["D"]))

    def fwd(q, k, v):
        return flash_attention(q, k, v, None, True)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    compiled = jax.jit(fn).lower(q, kv, kv).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= (3 if grad else 1)
    names = kernel_names(text)
    for kernel in FLASH_KERNELS if grad else FLASH_KERNELS[:1]:
        assert [n for n in names if kernel in n], (kernel, names)
    assert len(names) == (3 if grad else 1), names   # three kernels, no more
    fits(compiled)


@pytest.mark.parametrize("shape,S,K,pages", [
    (GPT2S, 8, 1, 64), (GPT2S, 8, 4, 64), (GQA128, 8, 1, 64),
    (GQA128, 8, 4, 64),
    # windows whose rows (K x group) are no power of two: the scheduler's
    # verify window is serve_spec_k + 1 = 5
    (GPT2S, 8, 3, 64), (GPT2S, 8, 5, 64), (GQA128, 8, 3, 64),
    (GQA128, 8, 5, 64),
    # Mistral-7B widths as the serving cells run them: the decode step over
    # 32 slots of a 16384-token context, and one prefill chunk
    (GQA128, 32, 1, 1024), (GQA128, 1, 512, 1024),
    (GPT2S, 1, 512, 64)],
    ids=["gpt2s-decode_k1", "gpt2s-verify_k4", "gqa_d128-decode_k1",
         "gqa_d128-verify_k4", "gpt2s-verify_k3", "gpt2s-verify_k5",
         "gqa_d128-verify_k3", "gqa_d128-verify_k5", "mistral-decode_32slots",
         "mistral-chunk_k512", "gpt2s-chunk_k512"])
def test_paged_attention_compiles(v5e, shape, S, K, pages):
    from ray_tpu.ops.paged_attention import (paged_attention,
                                             pallas_shape_problem)

    T = 16
    assert pallas_shape_problem(shape["Hkv"], shape["D"]) is None
    chip = SingleDeviceSharding(v5e.devices[0])
    pool = on(chip, (min(S * pages, 6144) + 1, T, shape["Hkv"] * shape["D"]))
    text = jax.jit(functools.partial(paged_attention, impl="pallas")).lower(
        on(chip, (S, K, shape["H"], shape["D"])), pool, pool,
        on(chip, (S, pages), jnp.int32), on(chip, (S,), jnp.int32),
    ).compile().as_text()
    assert "tpu_custom_call" in text
    assert [n for n in kernel_names(text) if "paged_attention" in n]


class TestPagedShapeRule:
    """A pool the compiled kernel cannot take (llama_debug: 2 kv heads of
    16 lanes) is refused where the lane is chosen, not at the first decode
    step — and only where Mosaic would really have to compile it."""

    def test_rule_names_the_problem(self):
        from ray_tpu.ops.paged_attention import pallas_shape_problem

        assert "128" in pallas_shape_problem(2, 16)      # llama_debug
        assert "128" in pallas_shape_problem(3, 64)      # no pairing of 3
        assert pallas_shape_problem(12, 64) is None      # 2 heads a window
        assert pallas_shape_problem(4, 96) is None       # 4 heads a window
        assert pallas_shape_problem(3, 128) is None      # 1 head a window

    def test_uncompilable_pool_is_refused_at_trace_time(self, v5e):
        from ray_tpu.ops.paged_attention import paged_attention

        chip = SingleDeviceSharding(v5e.devices[0])
        pool = on(chip, (33, 16, 2 * 16), jnp.float32)
        with pytest.raises(ValueError, match="cannot compile"):
            jax.jit(functools.partial(paged_attention, impl="pallas")).lower(
                on(chip, (4, 1, 4, 16), jnp.float32), pool, pool,
                on(chip, (4, 8), jnp.int32), on(chip, (4,), jnp.int32))

    def test_explicit_pallas_raises_and_auto_takes_reference(self):
        from ray_tpu.models import gpt2_small, llama_debug
        from ray_tpu.ops.paged_attention import resolve_impl

        with pytest.raises(ValueError, match="cannot compile"):
            resolve_impl(llama_debug(), "pallas")
        assert resolve_impl(llama_debug()) == "reference"
        assert resolve_impl(gpt2_small()) == "pallas"
        assert resolve_impl(gpt2_small(), "pallas") == "pallas"
        with pytest.raises(ValueError, match="unknown paged attention"):
            resolve_impl(gpt2_small(), "gather")

    def test_interpreted_kernel_takes_any_shape(self, monkeypatch):
        """Off-TPU the kernel is interpreted: the PR 20 tests drive
        llama_debug through the explicit 'pallas' lane on the CPU."""
        from ray_tpu.models import llama_debug
        from ray_tpu.ops.paged_attention import resolve_impl

        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        assert resolve_impl(llama_debug(), "pallas") == "pallas"


# ----------------------------------------------------------- train programs


def _abstract_train_state(cfg, tx, mesh):
    from ray_tpu.models.training import TrainState, _state_specs
    from ray_tpu.models.transformer import init_params

    def init(key):
        params = init_params(cfg, key)
        return TrainState(params=params, opt_state=tx.init(params),
                          step=jnp.zeros((), jnp.int32))

    abstract = jax.eval_shape(init, jax.random.PRNGKey(0))
    if isinstance(mesh, Mesh):
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 _state_specs(cfg, abstract, mesh, None))
    else:
        shardings = jax.tree.map(lambda _: mesh, abstract)
    return jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract, shardings)


# a training step runs each flash kernel once a layer (ISSUE 48): under the
# layer scan a kernel's call stands once in the text, and the block's
# recompute holds no second forward (the parent's step: two)
ONCE_A_LAYER = dict.fromkeys(FLASH_KERNELS, 1)


@pytest.mark.parametrize("batch", [16, 128], ids=["b16", "the_cells_b128"])
def test_gpt2s_train_step_fits_one_chip(v5e, batch, capsys):
    """``gpt2s_train``'s step, at a batch that compiles in seconds and at the
    cell's own (128 x 1024, 23 s): what the block keeps across its remat
    boundary is paid for at the cell's size, so a change to the block that
    costs memory there is seen here, without a chip."""
    from ray_tpu.models import gpt2_small
    from ray_tpu.models.training import (OptimizerConfig, make_optimizer,
                                         make_train_step)

    cfg, tx = gpt2_small(), make_optimizer(OptimizerConfig())
    chip = SingleDeviceSharding(v5e.devices[0])
    compiled = make_train_step(cfg, tx).lower(
        _abstract_train_state(cfg, tx, chip),
        {"tokens": on(chip, (batch, 1024), jnp.int32)}).compile()
    # every kernel keeps its name under the layer scan and the remat
    # (PERF.md: kernel.flash_roofline finds them by it)
    assert kernel_calls(compiled) == ONCE_A_LAYER
    total, ma = fits(compiled), compiled.memory_analysis()
    with capsys.disabled():
        print(f"\ngpt2s train step, batch {batch} x 1024: temporaries "
              f"{ma.temp_size_in_bytes / 1e9:.2f} GB, arguments "
              f"{ma.argument_size_in_bytes / 1e9:.2f} GB, in all "
              f"{total / 1e9:.2f} GB of {HBM_BYTES / 1e9:.2f}")


def _sharded_shapes():
    from ray_tpu.models import gpt2_small, llama3_8b

    return {
        "gpt2s": (gpt2_small, 16),
        # the Llama-shaped block (GQA 8 over 4, SwiGLU, untied head) at a
        # width that compiles in seconds; no weight has a dimension of the
        # sequence's or a cross-entropy chunk's length
        "llama_narrow": (lambda: llama3_8b(
            vocab_size=32768, num_layers=2, embed_dim=1536, num_heads=8,
            num_kv_heads=4, head_dim=128, mlp_dim=3072, max_seq_len=1024), 8),
    }


@pytest.mark.parametrize("shape", ["gpt2s", "llama_narrow"])
def test_sharded_train_step_lowers_with_the_kernel_in_it(v5e, shape):
    """fsdp=2 x tp=2 over four described chips. GSPMD cannot partition a
    Mosaic kernel; the dispatcher shard_maps it over the mesh in scope, so
    the step lowers with the kernel inside and q/k/v are never gathered
    to full batch or full heads in front of it. And what the chip's
    compiler puts on the interconnect (ISSUE 47; ``collectives``): a block
    reduces each activation once over ``tp`` — two residual-sized arrays
    forward, two backward, none in the recompute — and nothing that carries
    tokens over ``fsdp`` inside a scan, the cross-entropy's among them.
    Since ISSUE 54 each of the four is two reduces of half the bytes, a
    stream's (the halves of a chip's rows), and the compiler is asked to
    run them beside the other stream's matmuls. ISSUE 54 asked for six of
    the eight with matmuls ``between`` their start and their end: that is
    NOT met. Four have (one asynchronous collective is in flight at a time,
    and the weights' gathers over ``fsdp`` take the place from the rest;
    PERF.md 6, PR 54), and four is what is held here, so that what the chip
    measured does not fall away unseen; at no more memory than fits."""
    from ray_tpu.models.training import (OptimizerConfig, make_optimizer,
                                         make_train_step)
    from ray_tpu.parallel.mesh import (collective_census, collectives,
                                       data_sharding)

    make, batch = _sharded_shapes()[shape]
    cfg, tx = make(), make_optimizer(OptimizerConfig())
    mesh = Mesh(np.array(v5e.devices[:4]).reshape(2, 2), ("fsdp", "tp"))
    compiled = make_train_step(cfg, tx, mesh).lower(
        _abstract_train_state(cfg, tx, mesh),
        {"tokens": on(data_sharding(mesh), (batch, 1024), jnp.int32)}
    ).compile()
    text = compiled.as_text()
    # named inside the shard_map as well, the forward not run again, and
    # both streams' rows in ONE call of each kernel
    assert kernel_calls(compiled) == ONCE_A_LAYER
    gathers = re.findall(
        r"= \(?bf16\[([0-9,]+)\][^=\n]* all-gather(?:-start)?\(", text)
    # per shard q/k/v are [8, 1024, 6, 64] (or head-major), a stream's
    # half of them [4, ...]: a gather back to batch 16 or to 12 heads would
    # show one of these
    whole = {"16,1024,6,64", "8,1024,12,64", "16,1024,12,64",
             "16,6,1024,64", "8,12,1024,64", "16,12,1024,64",
             "4,1024,12,64", "4,12,1024,64"}
    assert gathers and not whole & set(gathers), whole & set(gathers)
    fits(compiled)

    half = (batch // 4, 1024, cfg.embed_dim)  # a chip's rows, a stream's
    rows = [row for row in collectives(text, mesh) if row["loop"]]
    reduced = [row for row in rows for s in row["shapes"]
               if s == half and row["op"] == "all-reduce"
               and row["axes"] == ("tp",)]
    backward = [row for row in reduced if "transpose(" in row["op_name"]]
    assert len(reduced) == 8 and len(backward) == 4, reduced
    assert not [row for row in reduced
                if "rematted_computation" in row["op_name"]], reduced
    hidden = [row for row in reduced if row.get("between", 0) > 0]
    # the issue's 6 is not reached: 4 (see above)
    assert len(hidden) >= 4, [row.get("between") for row in reduced]
    census = collective_census(text, mesh)[("loop", "all-reduce", ("tp",))]
    assert census["hidden"] >= len(hidden)
    # tokens over fsdp: a residual, or a chunk of the cross-entropy's rows
    # ([2048 or its share, ...]; the parent all-reduced [2048, vocab / tp]
    # float32 logits, twice a chunk)
    over_fsdp = [row for row in rows if "fsdp" in row["axes"] and any(
        s[:2] in (half[:2], (batch // 2, 1024))
        or s[:1] in ((cfg.ce_chunk,), (cfg.ce_chunk // 2,))
        for s in row["shapes"])]
    assert not over_fsdp, over_fsdp


def test_one_stream_is_compiled_as_the_parent_compiled_it(v5e):
    """``OVERLAP_REDUCES`` goes to the compiler with a step that carries two
    streams and with no other: three rows an ``fsdp`` group cannot be
    halved, so the layer scan carries one stream, its four ``tp`` reduces
    a layer-step stay whole and SYNCHRONOUS (no ``between``: neither a
    ``-start`` nor the fused form), and the step is the parent's."""
    from ray_tpu.models.training import (OptimizerConfig, make_optimizer,
                                         make_train_step)
    from ray_tpu.parallel.mesh import collectives, data_sharding

    make, _ = _sharded_shapes()["llama_narrow"]
    cfg, tx = make(), make_optimizer(OptimizerConfig())
    mesh = Mesh(np.array(v5e.devices).reshape(2, 2), ("fsdp", "tp"))
    text = make_train_step(cfg, tx, mesh).lower(
        _abstract_train_state(cfg, tx, mesh),
        {"tokens": on(data_sharding(mesh), (6, 1024), jnp.int32)}
    ).compile().as_text()
    reduced = [row for row in collectives(text, mesh)
               if row["loop"] and row["op"] == "all-reduce"
               and row["axes"] == ("tp",)
               and (3, 1024, cfg.embed_dim) in row["shapes"]]
    assert len(reduced) == 4, reduced
    assert not [row for row in reduced if "between" in row], reduced


# ----------------------------------------------------------- serve programs


def _serve_programs_at_the_defaults(cfg, v5e, **program_kw):
    """{name: compiled} for the prefill chunk, the decode step and the
    verify step at the scheduler's defaults (8 slots, 32-token chunks,
    16-token pages, a verify window of serve_spec_k + 1 = 5), on the lane a
    TPU replica resolves."""
    from ray_tpu._private.config import Config
    from ray_tpu.models.decode import (StepRows, init_paged_caches,
                                       paged_decode_step,
                                       paged_prefill_into_slot,
                                       paged_verify_step)
    from ray_tpu.models.transformer import init_params
    from ray_tpu.ops.paged_attention import resolve_impl

    conf = Config()
    slots, chunk, T = conf.serve_slots, conf.serve_prefill_chunk, \
        conf.serve_page_tokens
    pages = cfg.max_seq_len // T
    lane = resolve_impl(cfg)
    chip = SingleDeviceSharding(v5e.devices[0])

    def place(tree):
        return jax.tree.map(lambda a: on(chip, a.shape, a.dtype), tree)

    params = place(jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.PRNGKey(0)))
    caches = place(jax.eval_shape(functools.partial(
        init_paged_caches, cfg, slots * pages + 1, T, pages)))
    table = on(chip, (slots, pages), jnp.int32)
    row = on(chip, (pages,), jnp.int32)
    ids = functools.partial(on, chip, dtype=jnp.int32)
    step = (ids((slots,)), ids((slots,)), table, table,
            on(chip, (slots,), jnp.float32), on(chip, (slots,), jnp.uint32))
    programs = {
        # the chunk's program as the scheduler calls it: with the step's rows
        "prefill": (paged_prefill_into_slot,
                    (params, ids((1, chunk)), ids(()), ids(()), row, row,
                     caches, ids((slots,)), ids(()),
                     on(chip, (), jnp.float32), on(chip, (), jnp.uint32),
                     StepRows(*step)), 6),
        "decode": (paged_decode_step,
                   (params, ids((slots,)), *step[:4], caches, *step[4:]), 6),
        "verify": (paged_verify_step,
                   (params, ids((slots, conf.serve_spec_k + 1)), ids((slots,)),
                    ids((slots,)), table, table, caches), 6),
    }
    return lane, {
        name: jax.jit(functools.partial(program, cfg, attn=lane,
                                        **program_kw),
                      donate_argnums=(donated,)).lower(*args).compile()
        for name, (program, args, donated) in programs.items()}


def test_gpt2s_serve_programs_compile_and_fit(v5e):
    """Prefill chunk, decode and verify at the scheduler's defaults for
    GPT-2 small (8 slots, 32-token chunks, 16-token pages, a verify window
    of serve_spec_k + 1 = 5), on the lane a TPU replica resolves."""
    from ray_tpu.models import gpt2_small

    lane, compiled = _serve_programs_at_the_defaults(gpt2_small(), v5e)
    assert lane == "pallas"
    for name, program in compiled.items():
        assert "tpu_custom_call" in program.as_text(), name
        fits(program)


def test_moe_debug_serve_programs_lower_with_the_experts_kernel_inside(v5e):
    """The three paged programs of the toy expert model (float32, 8 experts
    of 64 x 128, top-3: 24 pairs a decode step, 96 a chunk) pass the stack
    and a layer, so their grouped matmuls are the kernel
    ``moe_grouped_matmul``, once a layer, and nothing of the compiler's own
    ``ragged-dot``; the same model's training step keeps ``ragged_dot``."""
    from ray_tpu.models import loss_fn, moe_debug
    from ray_tpu.models.transformer import init_params

    cfg = moe_debug()
    _, compiled = _serve_programs_at_the_defaults(cfg, v5e)
    for name, program in compiled.items():
        text = program.as_text()
        assert "moe_grouped_matmul" in names(program), (name, names(program))
        assert "ragged-dot" not in text, name
        fits(program)
    chip = SingleDeviceSharding(v5e.devices[0])
    params = jax.tree.map(
        lambda a: on(chip, a.shape, a.dtype),
        jax.eval_shape(functools.partial(init_params, cfg),
                       jax.random.PRNGKey(0)))
    batch = {"tokens": on(chip, (2, 128), jnp.int32)}
    train = jax.jit(jax.grad(functools.partial(
        loss_fn, moe_debug(attn_impl="reference")),
                             has_aux=True)).lower(
        params, batch).compile()
    assert "ragged-dot" in train.as_text()
    assert "moe_grouped_matmul" not in names(train)


@pytest.mark.parametrize("pairs", [256, 4096], ids=["decode", "chunk"])
def test_moe_grouped_matmul_compiles(v5e, pairs):
    """The experts' kernel at OLMoE's widths over the benchmark's stack of
    8 x 64 experts of 2048 x 1024, at the pairs a decode step of 32 slots
    and a 512-token chunk bring (8 experts a row): it compiles, under its
    name, within the VMEM ``tile_sizes`` reckons, and nothing of the stack
    is copied."""
    from ray_tpu.ops import moe

    chip = SingleDeviceSharding(v5e.devices[0])
    assert moe.tile_sizes(pairs, 64, 2048, 1024, 2) == (
        (64, 1024) if pairs == 256 else (128, 1024))
    args = (on(chip, (pairs, 2048)), on(chip, (8 * 64, 2048, 1024)),
            on(chip, (8 * 64, 2048, 1024)), on(chip, (8 * 64, 1024, 2048)),
            on(chip, (64,), jnp.int32), on(chip, (), jnp.int32))
    compiled = jax.jit(moe.expert_mlp).lower(*args).compile()
    assert names(compiled) == {"moe_grouped_matmul"}
    assert "ragged-dot" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 8e6


@pytest.mark.parametrize("config,cell,held_gb", [
    ("mistral7b_v03_l16", "mistral7b_chat", (13.7, 14.2))])
def test_a_turn_with_a_chunk_is_one_program_at_the_cells_shapes(
        v5e, compiled, config, cell, held_gb):
    """ISSUE 40, at ``mistral7b_chat``'s shapes (``olmoe_reason``'s case is
    ``tests/test_olmoe_compile.py``'s; the body is the harness's)."""
    a_turn_with_a_chunk_is_one_program_at_the_cells_shapes(
        v5e, compiled, config, cell, held_gb)


@pytest.mark.parametrize("S,K", [(8, 1), (1, 512), (1, 4113)],
                         ids=["step_8slots", "chunk_k512", "check_prefill"])
def test_latent_attention_compiles_at_the_cells_shapes(v5e, S, K):
    """``ops.latent_attention`` alone at GLM-4.7-Flash's sizes: 20 heads over
    rows of 640 lanes (a latent of 512, a shared key of 64, padding), pages
    of 16 through a table of 4128 — the 8 slots' step, a 512 chunk, and the
    check prompt's cached prefill in one call."""
    from ray_tpu.ops.latent_attention import latent_attention, pool_width

    chip = SingleDeviceSharding(v5e.devices[0])
    name = "latent_step_attention" if K == 1 else "latent_chunk_attention"
    compiled = jax.jit(functools.partial(
        latent_attention, sm_scale=1 / 16, impl="pallas", name=name)).lower(
        on(chip, (S, K, 20, 512)), on(chip, (S, K, 20, 64)),
        on(chip, (32769, 16, pool_width(512, 64))),
        on(chip, (S, 4128), jnp.int32), on(chip, (S,), jnp.int32)).compile()
    assert kernel_calls(compiled) == {name: 1}


@pytest.mark.parametrize("rows,tokens", [(128, 1), (1, 512), (1, 1152)],
                         ids=["step_128slots", "chunk_512", "check_prefill"])
def test_state_space_kernels_compile_at_the_published_sizes(v5e, rows,
                                                             tokens):
    """``ops.ssm`` alone at Nemotron-3-Nano's sizes (64 heads of 64 over 8
    groups, a state of 128, float32 states [rows, 8, 128, 512]): a step over
    128 slots (ISSUE 59's widest batch), a 512 chunk, and the check prompt's
    cached prefill in one call of the chunked scan."""
    from ray_tpu.ops import ssm

    sizes = ssm.SsmSizes(64, 64, 8, 128, 4, 128)
    chip = SingleDeviceSharding(v5e.devices[0])
    f32 = functools.partial(on, chip, dtype=jnp.float32)
    state = f32(ssm.state_shapes(rows, sizes)["ssm"])
    if tokens == 1:
        fn, name = functools.partial(ssm.ssd_step, sizes=sizes,
                                     impl="pallas"), ssm.STEP_KERNEL
        args = (on(chip, (rows, 64, 64)), f32((rows, 64)), f32((64,)),
                on(chip, (rows, 8, 128)), on(chip, (rows, 8, 128)),
                f32((64,)), state, on(chip, (rows,), jnp.int32))
    else:
        fn, name = (lambda *a: ssm.ssd_chunk(*a[:-1], sizes, a[-1],
                                             "pallas")), ssm.CHUNK_KERNEL
        args = (on(chip, (rows, tokens, 64, 64)), f32((rows, tokens, 64)),
                f32((64,)), on(chip, (rows, tokens, 8, 128)),
                on(chip, (rows, tokens, 8, 128)), f32((64,)), state,
                on(chip, (), jnp.int32))
    compiled = jax.jit(fn).lower(*args).compile()
    assert kernel_calls(compiled) == {name: 1}
    # sizes the kernels do not take are refused, never handed to a
    # ``jax.numpy`` form in their place; that form runs by name alone
    toy = ssm.SsmSizes(8, 8, 2, 16, 4, 16)
    with pytest.raises(ValueError, match="do not take"):
        ssm.use_kernel(toy)
    assert not ssm.use_kernel(toy, "reference") and ssm.use_kernel(sizes)
