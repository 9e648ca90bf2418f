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
interpreted), ``auto`` lanes resolved for a TPU.
"""

import functools
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding

HBM_BYTES = 16 * 1024**3
FLASH_KERNELS = ("flash_attention_fwd", "flash_attention_bwd_dq",
                 "flash_attention_bwd_dkv")
GPT2S = dict(H=12, Hkv=12, D=64)
GQA128 = dict(H=32, Hkv=8, D=128)  # llama3_8b's head shape


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


def _on(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_names(text: str) -> set:
    """The names the compiled kernels carry: a ``pallas_call``'s ``name=``
    becomes part of the custom call's instruction name (``%jvp_<name>_.1``,
    ``%<name>.1``), which is what a profiler trace shows as the op."""
    return set(re.findall(
        r"%(\S+) = [^\n]*custom_call_target=\"tpu_custom_call\"", text))


def _kernel_calls(compiled) -> dict:
    """How many custom calls of each kernel name the compiled text holds."""
    names = [re.sub(r"[.\d]+$", "", k)
             for k in _kernel_names(compiled.as_text())]
    return {k: names.count(k) for k in set(names)}


def _fits(compiled) -> int:
    ma = compiled.memory_analysis()
    total = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
             + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert total < HBM_BYTES, f"{total / 2**30:.1f} GiB does not fit 16 GB"
    return total


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
    q = _on(chip, (shape["B"], shape["S"], shape["H"], shape["D"]))
    kv = _on(chip, (shape["B"], shape["S"], shape["Hkv"], shape["D"]))

    def fwd(q, k, v):
        return flash_attention(q, k, v, None, True)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if grad else fwd
    compiled = jax.jit(fn).lower(q, kv, kv).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= (3 if grad else 1)
    names = _kernel_names(text)
    for kernel in FLASH_KERNELS if grad else FLASH_KERNELS[:1]:
        assert [n for n in names if kernel in n], (kernel, names)
    assert len(names) == (3 if grad else 1), names   # three kernels, no more
    _fits(compiled)


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
    pool = _on(chip, (min(S * pages, 6144) + 1, T, shape["Hkv"] * shape["D"]))
    text = jax.jit(functools.partial(paged_attention, impl="pallas")).lower(
        _on(chip, (S, K, shape["H"], shape["D"])), pool, pool,
        _on(chip, (S, pages), jnp.int32), _on(chip, (S,), jnp.int32),
    ).compile().as_text()
    assert "tpu_custom_call" in text
    assert [n for n in _kernel_names(text) if "paged_attention" in n]


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
        pool = _on(chip, (33, 16, 2 * 16), jnp.float32)
        with pytest.raises(ValueError, match="cannot compile"):
            jax.jit(functools.partial(paged_attention, impl="pallas")).lower(
                _on(chip, (4, 1, 4, 16), jnp.float32), pool, pool,
                _on(chip, (4, 8), jnp.int32), _on(chip, (4,), jnp.int32))

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
        {"tokens": _on(chip, (batch, 1024), jnp.int32)}).compile()
    # every kernel keeps its name under the layer scan and the remat
    # (PERF.md: kernel.flash_roofline finds them by it)
    assert _kernel_calls(compiled) == ONCE_A_LAYER
    total, ma = _fits(compiled), compiled.memory_analysis()
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
        {"tokens": _on(data_sharding(mesh), (batch, 1024), jnp.int32)}
    ).compile()
    text = compiled.as_text()
    # named inside the shard_map as well, the forward not run again, and
    # both streams' rows in ONE call of each kernel
    assert _kernel_calls(compiled) == ONCE_A_LAYER
    gathers = re.findall(
        r"= \(?bf16\[([0-9,]+)\][^=\n]* all-gather(?:-start)?\(", text)
    # per shard q/k/v are [8, 1024, 6, 64] (or head-major), a stream's
    # half of them [4, ...]: a gather back to batch 16 or to 12 heads would
    # show one of these
    whole = {"16,1024,6,64", "8,1024,12,64", "16,1024,12,64",
             "16,6,1024,64", "8,12,1024,64", "16,12,1024,64",
             "4,1024,12,64", "4,12,1024,64"}
    assert gathers and not whole & set(gathers), whole & set(gathers)
    _fits(compiled)

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
        {"tokens": _on(data_sharding(mesh), (6, 1024), jnp.int32)}
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
        return jax.tree.map(lambda a: _on(chip, a.shape, a.dtype), tree)

    params = place(jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.PRNGKey(0)))
    caches = place(jax.eval_shape(functools.partial(
        init_paged_caches, cfg, slots * pages + 1, T, pages)))
    table = _on(chip, (slots, pages), jnp.int32)
    row = _on(chip, (pages,), jnp.int32)
    ids = functools.partial(_on, chip, dtype=jnp.int32)
    step = (ids((slots,)), ids((slots,)), table, table,
            _on(chip, (slots,), jnp.float32), _on(chip, (slots,), jnp.uint32))
    programs = {
        # the chunk's program as the scheduler calls it: with the step's rows
        "prefill": (paged_prefill_into_slot,
                    (params, ids((1, chunk)), ids(()), ids(()), row, row,
                     caches, ids((slots,)), ids(()),
                     _on(chip, (), jnp.float32), _on(chip, (), jnp.uint32),
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
        _fits(program)


def _names(compiled) -> set:
    return {re.sub(r"[.\d]+$", "", name)
            for name in _kernel_names(compiled.as_text())}


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
        assert "moe_grouped_matmul" in _names(program), (name, _names(program))
        assert "ragged-dot" not in text, name
        _fits(program)
    chip = SingleDeviceSharding(v5e.devices[0])
    params = jax.tree.map(
        lambda a: _on(chip, a.shape, a.dtype),
        jax.eval_shape(functools.partial(init_params, cfg),
                       jax.random.PRNGKey(0)))
    batch = {"tokens": _on(chip, (2, 128), jnp.int32)}
    train = jax.jit(jax.grad(functools.partial(
        loss_fn, moe_debug(attn_impl="reference")),
                             has_aux=True)).lower(
        params, batch).compile()
    assert "ragged-dot" in train.as_text()
    assert "moe_grouped_matmul" not in _names(train)


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
    args = (_on(chip, (pairs, 2048)), _on(chip, (8 * 64, 2048, 1024)),
            _on(chip, (8 * 64, 2048, 1024)), _on(chip, (8 * 64, 1024, 2048)),
            _on(chip, (64,), jnp.int32), _on(chip, (), jnp.int32))
    compiled = jax.jit(moe.expert_mlp).lower(*args).compile()
    assert _names(compiled) == {"moe_grouped_matmul"}
    assert "ragged-dot" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 8e6


def _cell_programs(v5e, config: str, cell: str):
    """A benchmark cell's two serving programs as its scheduler calls them,
    at the configuration's published widths under the cell's deployment:
    (cfg, bytes held by weights and pool, {name: (program, arguments)}). The
    chunk's program takes the step's rows along."""
    from perfbench.lib import configs
    from perfbench.lib import manifest as manifest_lib
    from ray_tpu.models.decode import (StepRows, init_paged_caches,
                                       paged_decode_step,
                                       paged_prefill_into_slot)
    from ray_tpu.models.transformer import ATTENTION, SLIDING, init_params

    manifest = manifest_lib.load()
    hp = manifest_lib.config(manifest, config)
    cfg = configs.build_program_config(*configs.program_overrides(
        hp, manifest_lib.read_json_from_bench("families", hp["model_type"])))
    dep = manifest_lib.read_json(manifest, "cells", cell)["deployment"]
    slots, chunk, T = dep["slots"], dep["prefill_chunk"], dep["page_tokens"]
    pages = dep["arena_len"] // T
    chip = SingleDeviceSharding(v5e.devices[0])

    def place(tree):
        return jax.tree.map(lambda a: _on(chip, a.shape, a.dtype), tree)

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
    table = by_pool(_on(chip, (slots, pages), jnp.int32))
    row = by_pool(_on(chip, (pages,), jnp.int32))
    ids = functools.partial(_on, chip, dtype=jnp.int32)
    step = (ids((slots,)), ids((slots,)), table, table,
            _on(chip, (slots,), jnp.float32), _on(chip, (slots,), jnp.uint32))
    return cfg, held, {
        "prefill": (paged_prefill_into_slot,
                    (params, ids((1, chunk)), ids(()), ids(()), row, row,
                     caches, ids((slots,)), ids(()),
                     _on(chip, (), jnp.float32), _on(chip, (), jnp.uint32),
                     StepRows(*step), ids(()))),
        "decode": (paged_decode_step,
                   (params, ids((slots,)), *step[:4], caches, *step[4:])),
    }


@pytest.mark.parametrize("config,cell,held_gb", [
    ("mistral7b_v03_l16", "mistral7b_chat", (13.7, 14.2)),
    ("olmoe_1b_7b_l8", "olmoe_reason", (13.4, 13.7))])
def test_a_turn_with_a_chunk_is_one_program_at_the_cells_shapes(
        v5e, config, cell, held_gb):
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

    cfg, held, programs = _cell_programs(v5e, config, cell)
    assert held_gb[0] * 1e9 < held < held_gb[1] * 1e9
    program, args = programs["prefill"]
    assert isinstance(args[11], StepRows)
    kw = {"attn": resolve_impl(cfg)}
    if cfg.mlp == "moe":
        kw["moe_info"] = True
        assert moe.tile_sizes((512 + 32) * 8, 64, 2048, 1024, 2) == \
            moe.tile_sizes(512 * 8, 64, 2048, 1024, 2) == (128, 1024)

    def compiled(arguments):
        return jax.jit(functools.partial(program, cfg, **kw),
                       donate_argnums=(6,)).lower(*arguments).compile()

    fused = compiled(args)
    calls = _kernel_calls(fused)
    assert calls.pop("paged_attention") == 2 * cfg.num_layers
    if cfg.mlp == "moe":
        assert calls.pop("moe_grouped_matmul") == cfg.num_layers
        assert "ragged-dot" not in fused.as_text()
    assert not calls
    _fits(fused)
    # no logits over the chunk's 512 rows: the head sees the sampled rows
    vocab = cfg.vocab_size
    assert not re.search(rf"\[(1,)?512,{vocab}\]", fused.as_text())
    assert re.search(rf"\[(1,)?33,{vocab}\]", fused.as_text())
    alone = compiled(args[:11] + (None,) + args[12:])
    assert _kernel_calls(alone)["paged_attention"] == cfg.num_layers
    temp, temp_alone = (c.memory_analysis().temp_size_in_bytes
                        for c in (fused, alone))
    assert temp < 1.1 * temp_alone + 16e6, (temp, temp_alone)


def test_olmoe_serve_programs_compile_and_fit(v5e):
    """The benchmark's OLMoE-1B-7B configuration (published widths, 8
    layers, bf16) under its cell's deployment: the prefill chunk (with the
    step's rows along) and the decode step with the expert layer's grouped
    matmuls (the kernel ``moe_grouped_matmul``, once a layer, and nothing
    of the compiler's own ``ragged-dot``) and the paged kernel at its
    second shape (page rows of 16 kv heads x 128, group size 1), weights
    and the 6.4 GB pool beside the programs' own memory on one 16 GB
    chip."""
    from ray_tpu.ops.paged_attention import resolve_impl

    cfg, held, programs = _cell_programs(v5e, "olmoe_1b_7b_l8",
                                         "olmoe_reason")
    lane = resolve_impl(cfg)
    assert lane == "pallas"
    assert 13.4e9 < held < 13.7e9  # 7.13 GB of weights + 6.4 GB of pool
    for name, (program, args) in programs.items():
        compiled = jax.jit(
            functools.partial(program, cfg, attn=lane, moe_info=True),
            donate_argnums=(6,)).lower(*args).compile()
        assert _names(compiled) == {"paged_attention",
                                    "moe_grouped_matmul"}, name
        assert "ragged-dot" not in compiled.as_text(), name
        _fits(compiled)
        # no layer's experts (805 MB) are copied off the stacked weights
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert temp < 600e6, f"{name}: {temp / 1e6:.0f} MB of temporaries"


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

    cfg, held, programs = _cell_programs(v5e, "mellum2_12b_l8",
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
        assert _kernel_calls(compiled) == calls[name], name
        assert "ragged-dot" not in compiled.as_text(), name
        total = _fits(compiled)
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
    from perfbench.lib import manifest as manifest_lib
    from perfbench.lib.serve_app import GIVEN_PAD
    from ray_tpu.models.transformer import forward

    cfg, held, programs = _cell_programs(v5e, "mellum2_12b_l8",
                                         "mellum2_shortlong")
    cell = manifest_lib.read_json(manifest_lib.load(), "cells",
                                  "mellum2_shortlong")
    assert {"given_logit_err", "given_logit_rms_err"} <= set(
        cell["check_tolerance"])
    first = cell["check_prompt_tokens"] - 1
    n = first + cell["check_new_tokens"]
    params = programs["decode"][1][0]

    def run(params, tokens):
        logits, routes = forward(cfg, params, tokens, return_routes=True)
        return logits[0, first:n].astype(jnp.float32), routes

    tokens = _on(params["embed"]["table"].sharding,
                 (1, n + -n % GIVEN_PAD), jnp.int32)
    compiled = jax.jit(run).lower(params, tokens).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 1.2e9, f"{temp / 1e9:.2f} GB of temporaries"
    assert held + temp < 14.6e9


def test_minicpm_sala_serve_programs_compile_and_fit(v5e):
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

    cfg, held, programs = _cell_programs(v5e, "minicpm_sala_l16",
                                         "minicpm_sala_longdoc")
    lane = resolve_impl(cfg)
    assert lane == "pallas"
    assert 12.5e9 < held < 12.9e9  # 10.1 GB + 2.2 GB of pool + 0.4 of state
    kernels = {"prefill": {"linear_attention_chunk", "linear_attention_step",
                           "sparse_select", "sparse_paged_attention"},
               "decode": {"linear_attention_step", "sparse_select",
                          "sparse_paged_attention"}}
    for name, (program, args) in programs.items():
        compiled = jax.jit(functools.partial(program, cfg, attn=lane),
                           donate_argnums=(6,)).lower(*args).compile()
        found = {re.sub(r"[.\d]+$", "", k)
                 for k in _kernel_names(compiled.as_text())}
        assert found == kernels[name], (name, found)
        total = _fits(compiled)
        # the programs' own memory leaves room for the reference check
        assert total < 14.5e9, f"{name}: {total / 1e9:.1f} GB"


def _pageless_programs(v5e, cfg, slots: int, chunk: int):
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
        lambda a: _on(chip, a.shape, a.dtype), tree)
    params = place(jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.PRNGKey(0)))
    caches = place(jax.eval_shape(functools.partial(
        init_paged_caches, cfg, 1, chunk, 1, slots=slots)))
    held = sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves((params, caches)))
    ids = functools.partial(_on, chip, dtype=jnp.int32)
    rows = (_on(chip, (slots,), jnp.float32), _on(chip, (slots,), jnp.uint32))
    return held, {
        "prefill": (paged_prefill_into_slot,
                    (params, ids((1, chunk)), ids(()), ids(()), None, None,
                     caches, ids((slots,)), ids(()),
                     _on(chip, (), jnp.float32), _on(chip, (), jnp.uint32),
                     StepRows(ids((slots,)), ids((slots,)), None, None,
                              *rows), ids(()))),
        "decode": (paged_decode_step,
                   (params, ids((slots,)), ids((slots,)), ids((slots,)),
                    None, None, caches, *rows)),
    }


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
    _, programs = _pageless_programs(v5e, cfg, slots=4, chunk=64)
    for name, (program, args) in programs.items():
        compiled = jax.jit(functools.partial(program, cfg, attn="pallas"),
                           donate_argnums=(6,)).lower(*args).compile()
        assert _kernel_calls(compiled) == dict.fromkeys(
            RETENTION_KERNELS[name], cfg.num_layers), name
        _fits(compiled)


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
        lambda a: _on(chip, a.shape, a.dtype), tree)
    params = place(jax.eval_shape(
        functools.partial(init_params, cfg), jax.random.PRNGKey(0)))
    caches = place(jax.eval_shape(functools.partial(
        init_paged_caches, cfg, slots * pages + 1, T, pages, slots=slots)))
    ids = functools.partial(_on, chip, dtype=jnp.int32)
    table = ids((slots, pages))
    step = StepRows(ids((slots,)), ids((slots,)), table, table,
                    _on(chip, (slots,), jnp.float32),
                    _on(chip, (slots,), jnp.uint32))
    compiled = jax.jit(
        functools.partial(paged_prefill_into_slot, cfg, attn="reference"),
        donate_argnums=(6,)).lower(
            params, ids((1, chunk)), ids(()), ids(()), ids((pages,)),
            ids((pages,)), caches, ids((slots,)), ids(()),
            _on(chip, (), jnp.float32), _on(chip, (), jnp.uint32), step,
            ids(())).compile()
    linear, sparse = cfg.kinds.count(LINEAR), cfg.kinds.count(SPARSE)
    assert _kernel_calls(compiled) == {
        "linear_attention_chunk": linear, "linear_attention_step": linear,
        "sparse_select": 2 * sparse, "sparse_paged_attention": sparse}
    _fits(compiled)


def _copied_shapes(compiled) -> set:
    """(element type, dimensions) of every copy in a compiled program's
    text, as the text writes them: ``("f32", "16,8,13,128,640")``."""
    return set(re.findall(r"= (\w+)\[([\d,]+)\]\S* copy(?:-start)?\(",
                          compiled.as_text()))


@pytest.mark.parametrize("cell", ["minicpm_sala_longdoc", "brumby_longgen"])
def test_the_state_kinds_chunk_program_takes_the_rows_along_in_place(v5e,
                                                                     cell):
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
        from perfbench.lib import configs
        from perfbench.lib import manifest as manifest_lib

        manifest = manifest_lib.load()
        hp = manifest_lib.config(manifest, "brumby_14b_l8")
        cfg = configs.build_program_config(*configs.program_overrides(
            hp, manifest_lib.read_json_from_bench("families",
                                                  hp["model_type"])))
        dep = manifest_lib.read_json(manifest, "cells", cell)["deployment"]
        _, programs = _pageless_programs(v5e, cfg, dep["slots"],
                                         dep["prefill_chunk"])
    else:
        cfg, _, programs = _cell_programs(v5e, "minicpm_sala_l16", cell)
    program, args = programs["prefill"]

    def compiled(arguments):
        return jax.jit(functools.partial(program, cfg, attn="pallas"),
                       donate_argnums=(6,)).lower(*arguments).compile()

    fused, alone = compiled(args), compiled(args[:11] + (None,) + args[12:])
    stateful = sum(kind in STATE_KINDS for kind in cfg.kinds)
    step_kernel = ("power_retention_step" if cell == "brumby_longgen"
                   else "linear_attention_step")
    assert _kernel_calls(fused)[step_kernel] == stateful
    assert step_kernel not in _kernel_calls(alone)
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
    assert held and not held & _copied_shapes(fused)
    _fits(fused)


def test_brumby_serve_programs_compile_and_fit(v5e):
    """The benchmark's Brumby-14B configuration (published widths, 8 layers,
    bf16) under its cell's deployment: the prefill chunk and the decode
    step with their retention kernel inside, 8.4 GB of weights and 4.4 GB of
    states (16 slots x 8 layers x 34.35 MB) beside the programs' own memory
    on one 16 GB chip."""
    from perfbench.lib import configs
    from perfbench.lib import manifest as manifest_lib

    manifest = manifest_lib.load()
    hp = manifest_lib.config(manifest, "brumby_14b_l8")
    cfg = configs.build_program_config(*configs.program_overrides(
        hp, manifest_lib.read_json_from_bench("families", hp["model_type"])))
    dep = manifest_lib.read_json(manifest, "cells",
                                 "brumby_longgen")["deployment"]
    assert "page_tokens" not in dep and "kv_pages" not in dep
    held, programs = _pageless_programs(v5e, cfg, dep["slots"],
                                        dep["prefill_chunk"])
    assert 12.6e9 < held < 13.0e9
    for name, (program, args) in programs.items():
        compiled = jax.jit(functools.partial(program, cfg, attn="pallas"),
                           donate_argnums=(6,)).lower(*args).compile()
        found = {re.sub(r"[.\d]+$", "", k)
                 for k in _kernel_names(compiled.as_text())}
        assert found == RETENTION_KERNELS[name], (name, found)
        total = _fits(compiled)
        # the programs' own memory leaves room for the reference check
        assert total < 14.6e9, f"{name}: {total / 1e9:.1f} GB"


_KEYE_COMPILED = {}


def _keye_compiled(v5e):
    """``keye_longctx``'s two serving programs compiled once for the tests
    that read them: (cfg, bytes held, {name: (compiled, arguments)})."""
    from ray_tpu.ops.paged_attention import resolve_impl

    if not _KEYE_COMPILED:
        cfg, held, programs = _cell_programs(v5e, "keye_vl2_30b_a3b_l5",
                                             "keye_longctx")
        assert resolve_impl(cfg) == "pallas"
        _KEYE_COMPILED.update(cfg=cfg, held=held, programs={
            name: (jax.jit(
                functools.partial(program, cfg, attn="pallas", moe_info=True),
                donate_argnums=(6,)).lower(*args).compile(), args)
            for name, (program, args) in programs.items()})
    return (_KEYE_COMPILED["cfg"], _KEYE_COMPILED["held"],
            _KEYE_COMPILED["programs"])


def test_keye_serve_programs_compile_and_fit(v5e):
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
    cfg, held, programs = _keye_compiled(v5e)
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
    for name, (compiled, _) in programs.items():
        assert _kernel_calls(compiled) == calls[name], name
        total = _fits(compiled)
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert total < 13.2e9, f"{name}: {total / 1e9:.1f} GB"
        assert temp < 0.3e9, f"{name}: {temp / 1e6:.0f} MB of temporaries"


def test_keye_programs_read_their_pools_in_place(v5e):
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
    cfg, _, programs = _keye_compiled(v5e)
    names = {"float32": "f32", "bfloat16": "bf16"}
    for name, (compiled, args) in programs.items():
        text = compiled.as_text()
        pools = jax.tree.leaves(args[6])
        held = {(names[a.dtype.name], ",".join(map(str, a.shape)))
                for a in pools}
        copied = held & _copied_shapes(compiled)
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
        assert compiled.memory_analysis().alias_size_in_bytes == sum(
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
        _on(chip, (*rows, 49664), jnp.float32),
        _on(chip, rows, jnp.int32)).compile()
    assert _kernel_calls(compiled) == {"indexed_select": 1}
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
    from perfbench.lib import manifest as manifest_lib
    from perfbench.lib.serve_app import GIVEN_PAD
    from ray_tpu.models.transformer import forward

    cfg, held, programs = _cell_programs(v5e, "keye_vl2_30b_a3b_l5",
                                         "keye_longctx")
    cell = manifest_lib.read_json(manifest_lib.load(), "cells",
                                  "keye_longctx")
    assert {"given_logit_err", "given_logit_rms_err"} <= set(
        cell["check_tolerance"])
    prompt, new = cell["check_prompt_tokens"], cell["check_new_tokens"]
    first, n = prompt - 1, prompt - 1 + new
    params = programs["decode"][1][0]
    chip = params["embed"]["table"].sharding

    def run(params, tokens):
        logits, routes = forward(cfg, params, tokens, return_routes=True)
        return logits[0, first:n].astype(jnp.float32), routes

    compiled = jax.jit(run).lower(
        params, _on(chip, (1, n + -n % GIVEN_PAD), jnp.int32)).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2.9e9, f"forward: {temp / 1e9:.2f} GB of temporaries"
    assert held + temp < 15.1e9


def test_glm_serve_programs_compile_and_fit(v5e):
    """The benchmark's GLM-4.7-Flash configuration (published widths: hidden
    2048, 20 heads of 192 + 64 q/k and 256 v values over a latent of 512 and
    one shared rotated key of 64, a dense layer of 10240 then 64 experts of
    1536 top-4 beside a shared one; 1 + 5 layers, bf16) under its cell's
    deployment (8 slots of 66048 tokens, 32769 pages): the prefill chunk with
    the step's rows along and the decode step, the latent kernel once a layer
    and group of rows under the step's name or the chunk's, the experts'
    kernel once an EXPERT layer (the dense layer has none); 7.79 GB of
    weights and the 4.03 GB pool (rows of 640 lanes: 512 + 64 + padding)
    beside the programs' own memory on one 16 GB chip."""
    from ray_tpu.ops.paged_attention import resolve_impl

    cfg, held, programs = _cell_programs(v5e, "glm47_flash_l6",
                                         "glm47_flash_longdocs")
    assert (cfg.embed_dim, cfg.head_dim, cfg.hidden_dim) == (2048, 256, 1536)
    assert (cfg.lead_layers, cfg.expert_layers, cfg.period) == (1, 5, 1)
    assert cfg.mlp_width("swiglu") == 10240
    assert cfg.moe_scoring == "sigmoid" and cfg.moe_routed_scale == 1.8
    lane = resolve_impl(cfg)
    assert lane == "pallas"
    assert 11.7e9 < held < 11.9e9
    calls = {"prefill": {"latent_chunk_attention": 6,
                         "latent_step_attention": 6,
                         "moe_grouped_matmul": 5},
             "decode": {"latent_step_attention": 6,
                        "moe_grouped_matmul": 5}}
    for name, (program, args) in programs.items():
        compiled = jax.jit(
            functools.partial(program, cfg, attn=lane, moe_info=True),
            donate_argnums=(6,)).lower(*args).compile()
        assert _kernel_calls(compiled) == calls[name], name
        total = _fits(compiled)
        temp = compiled.memory_analysis().temp_size_in_bytes
        assert total < 12.6e9, f"{name}: {total / 1e9:.1f} GB"
        assert temp < 0.6e9, f"{name}: {temp / 1e6:.0f} MB of temporaries"


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
        _on(chip, (S, K, 20, 512)), _on(chip, (S, K, 20, 64)),
        _on(chip, (32769, 16, pool_width(512, 64))),
        _on(chip, (S, 4128), jnp.int32), _on(chip, (S,), jnp.int32)).compile()
    assert _kernel_calls(compiled) == {name: 1}


def test_glm_check_programs_fit_beside_the_pool(v5e):
    """The largest program ``reference_check`` runs in the replica beside
    the weights and the pool, on the cell's 4113-token check prompt and the
    32 tokens served behind it: the cell states limits GIVEN the routes, so
    the uncached whole-sequence ``forward`` (unabsorbed, through the flash
    kernel at heads of 256) up to whole tiles; its [4224, 154880] bf16 logits
    are 1.31 GB."""
    from perfbench.lib import manifest as manifest_lib
    from perfbench.lib.serve_app import GIVEN_PAD
    from ray_tpu.models.transformer import forward

    cfg, held, programs = _cell_programs(v5e, "glm47_flash_l6",
                                         "glm47_flash_longdocs")
    cell = manifest_lib.read_json(manifest_lib.load(), "cells",
                                  "glm47_flash_longdocs")
    assert {"given_logit_err", "given_logit_rms_err"} <= set(
        cell["check_tolerance"])
    prompt, new = cell["check_prompt_tokens"], cell["check_new_tokens"]
    first, n = prompt - 1, prompt - 1 + new
    params = programs["decode"][1][0]
    chip = params["embed"]["table"].sharding

    def run(params, tokens):
        logits, routes = forward(cfg, params, tokens, return_routes=True)
        return logits[0, first:n].astype(jnp.float32), routes

    compiled = jax.jit(run).lower(
        params, _on(chip, (1, n + -n % GIVEN_PAD), jnp.int32)).compile()
    assert "flash_attention_fwd" in " ".join(_kernel_names(
        compiled.as_text()))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2.2e9, f"forward: {temp / 1e9:.2f} GB of temporaries"
    assert held + temp < 14.2e9


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
    f32 = functools.partial(_on, chip, dtype=jnp.float32)
    state = f32(ssm.state_shapes(rows, sizes)["ssm"])
    if tokens == 1:
        fn, name = functools.partial(ssm.ssd_step, sizes=sizes,
                                     impl="pallas"), ssm.STEP_KERNEL
        args = (_on(chip, (rows, 64, 64)), f32((rows, 64)), f32((64,)),
                _on(chip, (rows, 8, 128)), _on(chip, (rows, 8, 128)),
                f32((64,)), state, _on(chip, (rows,), jnp.int32))
    else:
        fn, name = (lambda *a: ssm.ssd_chunk(*a[:-1], sizes, a[-1],
                                             "pallas")), ssm.CHUNK_KERNEL
        args = (_on(chip, (rows, tokens, 64, 64)), f32((rows, tokens, 64)),
                f32((64,)), _on(chip, (rows, tokens, 8, 128)),
                _on(chip, (rows, tokens, 8, 128)), f32((64,)), state,
                _on(chip, (), jnp.int32))
    compiled = jax.jit(fn).lower(*args).compile()
    assert _kernel_calls(compiled) == {name: 1}
    # sizes the kernels do not take are refused, never handed to a
    # ``jax.numpy`` form in their place; that form runs by name alone
    toy = ssm.SsmSizes(8, 8, 2, 16, 4, 16)
    with pytest.raises(ValueError, match="do not take"):
        ssm.use_kernel(toy)
    assert not ssm.use_kernel(toy, "reference") and ssm.use_kernel(sizes)
