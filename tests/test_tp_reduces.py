"""What a tensor-parallel training step puts on the interconnect (ISSUE 47),
counted from the compiled program's text by ``collective_census``: under
``make_train_step(mesh)`` with ``tp=2`` a block reduces each activation once —
two all-reduces of a residual-sized array over ``tp`` forward, none of them
again in the recompute, the norm outputs' gradients backward — and no
activation over ``fsdp``, whichever attention was chosen; the sharded step is the unsharded one to bf16's tolerance; and a
decode program, which sees no mesh, traces what it traced before. Since
ISSUE 54 the layer scan carries the two halves of every chip's rows as two
streams where a block reduces over ``tp``: the same bytes in twice the calls,
the halves taken INSIDE an ``fsdp`` group, one stream wherever there is no
reduce to hide; and ``collectives`` says how many matmuls the compiler
scheduled ``between`` a collective's start and its end.

Four of the CPU's host devices, small widths, the flash kernel interpreted so
that its ``shard_map`` stands in the program as it does on the chip. A count
from a CPU compile is a count, never a time.
"""

import math
import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.training import (OptimizerConfig, init_train_state,
                                     make_train_step)
from ray_tpu.models.transformer import (TransformerConfig, init_params,
                                        loss_fn)
from ray_tpu.parallel.mesh import (MeshSpec, build_mesh, collective_census,
                                   collectives, data_sharding)

B, S, D, CHUNK = 4, 256, 384, 64  # no weight has a dimension of S or CHUNK
BLOCKS = {
    "llama": dict(num_kv_heads=4, tie_embeddings=False),
    "gpt2": dict(norm="layernorm", pos="learned", mlp="gelu"),
}


def _cfg(block: str, attn_impl: str, **more) -> TransformerConfig:
    return TransformerConfig(**{**dict(
        vocab_size=512, num_layers=3, embed_dim=D, num_heads=8, head_dim=128,
        mlp_dim=1024, max_seq_len=S, ce_chunk=CHUNK, attn_impl=attn_impl),
        **BLOCKS[block], **more})


def _mesh():
    return build_mesh(MeshSpec.of(fsdp=2, tp=2), devices=jax.devices()[:4])


def _batch(cfg):
    return {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                         cfg.vocab_size)}


@pytest.fixture(scope="module", params=[
    (block, impl) for block in BLOCKS for impl in ("flash", "reference")],
    ids=lambda p: "-".join(p))
def compiled_step(request):
    """(the compiled sharded step's text, its mesh) for a block and an
    attention."""
    block, impl = request.param
    mesh = _mesh()
    return _step_text(_cfg(block, impl), mesh), mesh


def _activations(rows):
    """The rows inside a scan that move an activation: an array that
    carries a device's share of the batch and the whole sequence, [B / fsdp,
    S, ...] (the residual, q, k, v, the mlp's hidden rows), or a
    cross-entropy chunk's rows, [CHUNK or its share, ...]. What is left is
    weights and their gradients."""
    return [row for row in rows if row["loop"] and any(
        shape[:2] in ((B // 2, S), (B // 4, S))  # the whole, a stream's half
        or shape[:1] in ((CHUNK,), (CHUNK // 2,))
        for shape in row["shapes"])]


def test_a_block_reduces_each_activation_once(compiled_step, request):
    text, mesh = compiled_step
    reduces = [row for row in _activations(collectives(text, mesh))
               if row["op"] == "all-reduce" and row["bytes"] > 4 * CHUNK]
    assert all(row["axes"] == ("tp",) for row in reduces), reduces
    arrays = [(row["op_name"], shape) for row in reduces
              for shape in row["shapes"] if len(shape) > 2]
    # a stream's half of the chip's rows (ISSUE 54): each is reduced once
    assert all(shape == (B // 4, S, D) for _, shape in arrays), arrays
    backward = [name for name, _ in arrays if "transpose(" in name]
    # Megatron's g, g forward: the two row-parallel dots' sums, a stream
    # each, and neither again in the recompute, which was ``wo``'s before
    assert len(arrays) - len(backward) == 2 * 2, arrays
    assert not [name for name in backward
                if "rematted_computation" in name], backward
    # backward f, f: the gradient of each norm's output. The chip's
    # compiler sums the column-parallel dots' partial gradients before it
    # reduces, two arrays (tests/test_tpu_compile.py asks it); this one
    # reduces each where it stands, q, k, v and the mlp's one or two
    dots = 5 if "llama" in request.node.name else 4
    assert 2 * 2 <= len(backward) <= 2 * dots, backward


def test_no_activation_crosses_fsdp_in_a_layer(compiled_step):
    """The trap: without a stated layout the reference attention's program
    took the embedding table's split of the hidden dimension for the
    residual and all-reduced q, k, v, gate and up over ``fsdp``; and with
    either attention the cross-entropy's scan all-reduced every chunk's
    logits over it."""
    text, mesh = compiled_step
    over_fsdp = [row for row in _activations(collectives(text, mesh))
                 if "fsdp" in row["axes"]]
    assert not over_fsdp, over_fsdp


def test_the_census_adds_up_the_rows(compiled_step):
    text, mesh = compiled_step
    rows, census = collectives(text, mesh), collective_census(text, mesh)
    assert sum(t["calls"] for t in census.values()) == len(rows)
    assert sum(t["bytes"] for t in census.values()) == sum(
        row["bytes"] for row in rows)
    # a layer's weights are gathered over fsdp inside the scan (ZeRO-3)
    assert census[("loop", "all-gather", ("fsdp",))]["calls"] > 0
    assert ("loop", "all-reduce", ("tp",)) in census


def test_census_reads_every_form_of_replica_groups():
    """Text in, numbers out: the three ways the compilers write groups, a
    tuple result, an async pair, the chip compiler's fused reduce-scatter,
    and a loop body (and what it calls) apart from the entry."""
    text = """
%add (a: f32[], b: f32[]) -> f32[] {
  ROOT %s = f32[] add(%a, %b)
}
%all-reduce-scatter.7 (input.7: f32[8,4]) -> f32[4,4] {
  %all-reduce.9 = f32[8,4]{1,0} all-reduce(%input.7), replica_groups={{0,2},{1,3}}, use_global_device_ids=true, to_apply=%add
  ROOT %ds = f32[4,4]{1,0} dynamic-slice(%all-reduce.9, %i, %j), dynamic_slice_sizes={4,4}
}
%body (p: (s32[], bf16[4,8])) -> (s32[], bf16[4,8]) {
  %rs = f32[4,4]{1,0} fusion(%g), kind=kCustom, calls=%all-reduce-scatter.7
  %ar = (bf16[4,8]{1,0}, bf16[4,8]{1,0}) all-reduce(%x, %y), replica_groups=[2,2]<=[4], use_global_device_ids=true, to_apply=%add, metadata={op_name="jit(f)/transpose(jvp())/while/body/dot_general"}
  %ag = f32[2,8]{1,0} all-gather(%z), dimensions={0}, replica_groups=[2,2]<=[2,2]T(1,0), use_global_device_ids=true
}
ENTRY %main (p: bf16[4,8]) -> bf16[4,8] {
  %w = (s32[], bf16[4,8]) while(%t), condition=%cond, body=%body
  %ars = f32[16]{0} all-reduce-start(%v), replica_groups={{0,1,2,3}}, to_apply=%add
  %ard = f32[16]{0} all-reduce-done(%ars)
  %ags = (f32[4]{0}, f32[8]{0}) all-gather-start(%u), replica_groups={{0,2},{1,3}}, dimensions={0}
  %cp = bf16[3]{0} collective-permute(%q), source_target_pairs={{0,1},{1,0},{2,3},{3,2}}
}
"""
    mesh = _mesh()
    rows = {(r["loop"], r["op"], r["axes"]): r for r in
            collectives(text, mesh)}
    assert set(rows) == {
        (True, "all-reduce", ("tp",)), (True, "all-gather", ("fsdp",)),
        (True, "all-reduce-scatter", ("fsdp",)),
        (False, "all-reduce", ("fsdp", "tp")),
        (False, "all-gather", ("fsdp",)),
        (False, "collective-permute", ("tp",))}
    assert rows[(True, "all-reduce", ("tp",))]["bytes"] == 2 * 4 * 8 * 2
    assert "transpose(" in rows[(True, "all-reduce", ("tp",))]["op_name"]
    assert rows[(False, "all-gather", ("fsdp",))]["shapes"] == [(8,)]
    assert collective_census(text, mesh)[
        ("entry", "all-reduce", ("fsdp", "tp"))] == {
            "calls": 1, "bytes": 64, "hidden": 0}


@pytest.mark.parametrize("streams", [2, 1])
@pytest.mark.parametrize("block", list(BLOCKS))
def test_the_sharded_step_is_the_unsharded_one(block, streams):
    """Loss and every gradient leaf of one step under the mesh against the
    same weights and batch with no mesh, to bf16's tolerance: a kept
    result and a stated layout are the same sums in another order, and so
    is a weight's gradient from the two streams' products (``B`` rows: two
    an ``fsdp`` group, a row a stream; half of them: one stream)."""
    cfg, mesh = _cfg(block, "reference"), _mesh()
    state, _ = init_train_state(cfg, OptimizerConfig(),
                                jax.random.PRNGKey(0), mesh)
    batch = jax.tree.map(lambda a: a[:B * streams // 2], _batch(cfg))
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        assert _scan_carries(jax.make_jaxpr(
            lambda p, b: loss_fn(cfg, p, b)[0])(state.params, batch)
        ) == streams + 1
    grad = jax.value_and_grad(lambda p, b: loss_fn(cfg, p, b)[0])

    def on_mesh(params, b):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return grad(params, b)

    loss_m, grads_m = jax.jit(on_mesh)(
        state.params, jax.device_put(batch, data_sharding(mesh)))
    loss_1, grads_1 = jax.jit(grad)(jax.device_get(state.params), batch)
    np.testing.assert_allclose(loss_m, loss_1, rtol=2e-3)
    flat_m, flat_1 = (jax.tree.leaves_with_path(g)
                      for g in (grads_m, grads_1))
    assert len(flat_m) == len(flat_1) > 8
    for (path, gm), (_, g1) in zip(flat_m, flat_1):
        gm, g1 = np.asarray(gm, np.float32), np.asarray(g1, np.float32)
        # bf16 keeps 8 bits and three layers round in another order: a
        # leaf's worst entry is held to 3% of its largest (read: 1.1%); a
        # reduce missed or made twice is off by half or by all of it
        assert np.abs(gm - g1).max() <= 3e-2 * np.abs(g1).max() + 1e-6, (
            jax.tree_util.keystr(path))


def _scans(jaxpr):
    """Every ``scan`` equation of a jaxpr, those inside others' bodies
    too."""
    found = []
    for eqn in jaxpr.eqns:
        found += [eqn] if eqn.primitive.name == "scan" else []
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _scans(sub)
    return found


def _layer_scan(closed):
    """The layer scan of a traced forward: the one over ``num_layers``."""
    return next(eqn for eqn in _scans(closed.jaxpr)
                if eqn.params["length"] == 3)


def _scan_carries(closed) -> int:
    """What the layer scan carries: the residual's streams and the
    auxiliary loss's sum."""
    return _layer_scan(closed).params["num_carry"]


def _step_text(cfg, mesh):
    state, tx = init_train_state(cfg, OptimizerConfig(),
                                 jax.random.PRNGKey(0), mesh)
    batch = jax.device_put(_batch(cfg), data_sharding(mesh))
    return make_train_step(cfg, tx, mesh).lower(
        state, batch).compile().as_text()


def _residual_reduces(text, mesh):
    """(arrays, their bytes in bf16) the scans all-reduce over ``tp`` that
    are as wide as the residual, [rows, S, D] (this compiler may reduce two
    as one instruction's tuple)."""
    arrays = [shape for row in collectives(text, mesh)
              if row["loop"] and row["op"] == "all-reduce"
              and row["axes"] == ("tp",)
              for shape in row["shapes"] if shape[1:] == (S, D)]
    return len(arrays), sum(2 * math.prod(shape) for shape in arrays)


@pytest.mark.parametrize("block", list(BLOCKS))
def test_two_streams_reduce_the_same_bytes_in_twice_the_calls(block,
                                                              monkeypatch):
    from ray_tpu.models import transformer

    cfg, mesh = _cfg(block, "reference"), _mesh()
    calls_2, bytes_2 = _residual_reduces(_step_text(cfg, mesh), mesh)
    monkeypatch.setattr(transformer, "streams", lambda cfg, rows: 1)
    calls_1, bytes_1 = _residual_reduces(_step_text(cfg, mesh), mesh)
    assert (calls_2, bytes_2) == (2 * calls_1, bytes_1) and calls_1 >= 4


def _rows_of_a_halved_batch_over_fsdp(text, mesh):
    """The collectives over ``fsdp`` OUTSIDE the scans that move an array
    as wide as the residual, [rows, S, D]: what taking the halves, or
    putting them together, sends between two ``fsdp`` groups. (The
    cross-entropy's chunks are [.., CHUNK, D] there, the parent's rows.)"""
    return [row for row in collectives(text, mesh)
            if not row["loop"] and "fsdp" in row["axes"]
            and any(len(shape) > 2 and shape[-2:] == (S, D)
                    for shape in row["shapes"])]


@pytest.mark.parametrize("split", ["inside_a_group", "the_batchs_halves"])
def test_the_halves_are_taken_inside_an_fsdp_group(split, monkeypatch):
    """The trap (PERF.md 7, PR 47): the batch lies over ``fsdp`` as rows
    0-1 | 2-3, so ``x[:2]`` and ``x[2:]`` are a group each and every
    activation crosses ``fsdp`` at the entry to be spread again. The second
    case IS that split, and must show what the first must not."""
    from ray_tpu.models import transformer

    if split == "the_batchs_halves":
        monkeypatch.setattr(transformer, "_halves", lambda x: tuple(
            transformer._residual_layout(h)
            for h in (x[:x.shape[0] // 2], x[x.shape[0] // 2:])))
        monkeypatch.setattr(transformer, "_whole", lambda hs:
                            transformer._residual_layout(jnp.concatenate(hs)))
    cfg, mesh = _cfg("llama", "reference"), _mesh()
    text = _step_text(cfg, mesh)
    crossed = _rows_of_a_halved_batch_over_fsdp(text, mesh)
    # and inside the scans, where the streams meet in the attention
    crossed += [row for row in _activations(collectives(text, mesh))
                if "fsdp" in row["axes"]]
    assert bool(crossed) == (split == "the_batchs_halves"), crossed


def _forward_jaxpr(cfg, rows=B, mesh=None, caches=False):
    from ray_tpu.models.decode import init_caches
    from ray_tpu.models.transformer import forward

    params = jax.eval_shape(partial(init_params, cfg), jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((rows, 64), jnp.int32)
    kv = init_caches(cfg, rows, 64) if caches else None
    trace = lambda: jax.make_jaxpr(
        lambda p, t: forward(cfg, p, t, kv_caches=kv))(params, tokens)
    if mesh is None:
        return trace()
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        return trace()


def _products(jaxpr) -> int:
    """The ``dot_general`` equations of a jaxpr and of all inside it."""
    return sum((eqn.primitive.name == "dot_general") + sum(
        _products(sub) for sub in jax.core.jaxprs_in_params(eqn.params))
        for eqn in jaxpr.eqns)


@pytest.mark.parametrize("case", ["two_streams", "no_mesh", "experts",
                                  "caches", "odd_group"])
def test_one_stream_wherever_there_is_no_reduce_to_hide(case, monkeypatch):
    """Without a mesh, with experts, with caches and with an odd number of
    rows in an ``fsdp`` group the program is the parent's: the layer scan
    carries ONE array beside the auxiliary loss's sum and holds one block
    a layer — the text of the trace with ``streams`` out of reach. (The
    first case is the control: two arrays, a block's products twice.)"""
    from ray_tpu.models import transformer

    more = dict(max_seq_len=64)
    if case == "experts":
        more.update(mlp="moe", moe_num_experts=4, moe_top_k=2)
    cfg = _cfg("llama", "reference", **more)
    at = dict(rows=B // 2 if case == "odd_group" else B,
              mesh=None if case == "no_mesh" else _mesh(),
              caches=case == "caches")
    traced = _forward_jaxpr(cfg, **at)
    monkeypatch.setattr(transformer, "streams", lambda cfg, rows: 1)
    parents = _forward_jaxpr(cfg, **at)
    if case == "two_streams":
        assert _scan_carries(traced) == 3 and _scan_carries(parents) == 2
        # every product a stream but the attention's own two (scores,
        # values), which take both streams' rows as one call
        assert _products(_layer_scan(traced).params["jaxpr"].jaxpr) == (
            2 * _products(_layer_scan(parents).params["jaxpr"].jaxpr) - 2)
        return
    plain = lambda closed: re.sub(r"0x[0-9a-f]+", "0x", str(closed))
    assert plain(traced) == plain(parents)
    if case == "caches":  # a layer at a time, no scan over them
        assert not [eqn for eqn in _scans(traced.jaxpr)
                    if eqn.params["length"] == 3]
    else:
        assert _scan_carries(traced) == 2


def _scheduled_text(form):
    """A scan body with three ``tp`` reduces, in the ``-start/-done`` form or
    the chip compiler's fused one: one with a matmul fusion and a kernel's
    call between its start and its end, one with nothing that multiplies
    there, one synchronous."""
    pair = {
        "start_done": ("""
  %s1 = bf16[4,8]{1,0} all-reduce-start(%x), channel_id=1, replica_groups=[2,2]<=[4], use_global_device_ids=true, to_apply=%add
  %m1 = bf16[4,8]{1,0} fusion(%y), kind=kOutput, calls=%matmul
  %k1 = bf16[4,8]{1,0} custom-call(%y), custom_call_target="tpu_custom_call"
  %e1 = f32[4,8]{1,0} fusion(%y), kind=kLoop, calls=%elementwise
  %d1 = bf16[4,8]{1,0} all-reduce-done(%s1)
  %s2 = bf16[4,8]{1,0} all-reduce-start(%m1), channel_id=2, replica_groups=[2,2]<=[4], use_global_device_ids=true, to_apply=%add
  %e2 = f32[4,8]{1,0} fusion(%y), kind=kLoop, calls=%elementwise
  %d2 = bf16[4,8]{1,0} all-reduce-done(%s2)""", ""),
        "the_chips_fusion": ("""
  %async-collective-start.1 = (bf16[4,8]{1,0}, u32[]) fusion(%x), kind=kCustom, calls=%fused_start.1
  %fusion.7 = (bf16[4,8]{1,0}, bf16[4,8]{1,0}) fusion(%y, %async-collective-start.1), kind=kOutput, calls=%async_collective_fusion.7
  %k1 = bf16[4,8]{1,0} custom-call(%y), custom_call_target="tpu_custom_call"
  %e1 = f32[4,8]{1,0} fusion(%y), kind=kLoop, calls=%elementwise
  %async-collective-done.1 = bf16[4,8]{1,0} fusion(%fusion.7), kind=kCustom, calls=%fused_done.1
  %async-collective-start.2 = (bf16[4,8]{1,0}, u32[]) fusion(%k1), kind=kCustom, calls=%fused_start.2
  %e2 = f32[4,8]{1,0} fusion(%y), kind=kLoop, calls=%elementwise
  %async-collective-done.2 = bf16[4,8]{1,0} fusion(%async-collective-start.2), kind=kCustom, calls=%fused_done.2""", "".join(f"""
%fused_{role}.{n} (p: bf16[4,8]) -> bf16[4,8] {{
  %ar = bf16[4,8]{{1,0}} all-reduce(%p), channel_id={n}, replica_groups=[2,2]<=[4], use_global_device_ids=true, to_apply=%add
  ROOT %c = bf16[4,8]{{1,0}} custom-call(%ar), custom_call_target="AsyncCollective{role.title()}"
}}""" for n in (1, 2) for role in ("start", "done")) + """
%async_collective_fusion.7 (p: bf16[4,8], q: bf16[4,8]) -> (bf16[4,8], bf16[4,8]) {
  %ar = bf16[4,8]{1,0} all-reduce(%q), channel_id=1, replica_groups=[2,2]<=[4], use_global_device_ids=true, to_apply=%add
  %conv = bf16[4,8]{1,0} convolution(%p, %p), dim_labels=bf_io->bf
  ROOT %t = (bf16[4,8]{1,0}, bf16[4,8]{1,0}) tuple(%conv, %ar)
}"""),
    }[form]
    return """
%add (a: bf16[], b: bf16[]) -> bf16[] {
  ROOT %s = bf16[] add(%a, %b)
}
%matmul (p: bf16[4,8]) -> bf16[4,8] {
  ROOT %conv = bf16[4,8]{1,0} convolution(%p, %p), dim_labels=bf_io->bf
}
%elementwise (p: bf16[4,8]) -> f32[4,8] {
  ROOT %cv = f32[4,8]{1,0} convert(%p)
}""" + pair[1] + """
%body (p: (s32[], bf16[4,8])) -> (s32[], bf16[4,8]) {""" + pair[0] + """
  %sync = bf16[4,8]{1,0} all-reduce(%y), channel_id=9, replica_groups=[2,2]<=[4], use_global_device_ids=true, to_apply=%add
}
ENTRY %main (p: bf16[4,8]) -> bf16[4,8] {
  %w = (s32[], bf16[4,8]) while(%t), condition=%cond, body=%body
}
"""


@pytest.mark.parametrize("form", ["start_done", "the_chips_fusion"])
def test_between_counts_the_matmuls_under_a_collective(form):
    """Text in, numbers out: an asynchronous collective's ``between`` is the
    instructions that multiply scheduled between its start and its end — a
    fusion that holds a convolution or a dot, a Pallas kernel's call — in
    either form; one with nothing between, and a synchronous one, are not
    ``hidden``."""
    text, mesh = _scheduled_text(form), _mesh()
    rows = collectives(text, mesh)
    assert [(row["op"], row["axes"], row["loop"], row.get("between"))
            for row in rows] == [("all-reduce", ("tp",), True, 2),
                                 ("all-reduce", ("tp",), True, 0),
                                 ("all-reduce", ("tp",), True, None)]
    assert collective_census(text, mesh) == {("loop", "all-reduce", ("tp",)): {
        "calls": 3, "bytes": 3 * 4 * 8 * 2, "hidden": 1}}


@pytest.mark.parametrize("was, now, says", [
    ("  %async-collective-done.1 = bf16[4,8]{1,0} fusion(%fusion.7), "
     "kind=kCustom, calls=%fused_done.1", "", "no AsyncCollectiveDone"),
    ("AsyncCollectiveDone", "AsyncCollectiveJoin", "no start and no end"),
    ("AsyncCollectiveStart", "CollectiveStart", "starts"),
], ids=["no_end", "another_role", "no_start"])
def test_a_fused_chain_that_is_not_the_known_one_is_refused(was, now, says):
    """The chip compiler's fused form is private text. Where it is not the
    chain ``collectives`` knows — a start, fusions that carry it on, an end
    of the same channel — the census raises and does not count a reduce
    with nothing under it."""
    text = _scheduled_text("the_chips_fusion")
    assert was in text
    with pytest.raises(ValueError, match=says):
        collectives(text.replace(was, now), _mesh())


def _decode_programs(cfg):
    """The jaxprs of the serving step and of a prefill chunk that takes the
    step's rows along, at a toy size."""
    from ray_tpu.models.decode import (StepRows, init_paged_caches,
                                       paged_decode_step,
                                       paged_prefill_into_slot)

    slots, T, P, C = 2, 4, 8, 8
    params = init_params(cfg, jax.random.PRNGKey(0))
    caches = init_paged_caches(cfg, slots * P + 1, T, P)
    tables = jnp.arange(1, slots * P + 1, dtype=jnp.int32).reshape(slots, P)
    ints, idle = jnp.zeros(slots, jnp.int32), (jnp.zeros(slots, jnp.float32),
                                               jnp.zeros(slots, jnp.uint32))
    step = jax.make_jaxpr(partial(paged_decode_step, cfg, attn="reference"))(
        params, ints, ints, ints, tables, tables, caches, *idle)
    chunk = jax.make_jaxpr(
        partial(paged_prefill_into_slot, cfg, attn="reference"))(
        params, jnp.zeros((1, C), jnp.int32), jnp.int32(C), jnp.int32(0),
        tables[0], tables[0], caches, ints, jnp.int32(0), jnp.float32(0),
        jnp.uint32(0), StepRows(ints, ints, tables, tables, *idle))
    return str(step), str(chunk)


@pytest.mark.parametrize("block", list(BLOCKS))
def test_a_decode_program_traces_what_it_traced(block, monkeypatch):
    """No mesh in scope: no named result, no constraint — the same text as
    with this PR's branches out of reach."""
    from ray_tpu.models import transformer
    from ray_tpu.ops import losses

    cfg = _cfg(block, "reference", dtype=jnp.float32, max_seq_len=64)
    reachable = _decode_programs(cfg)
    for module in (transformer, losses):
        monkeypatch.setattr(module, "free_axes", lambda *a, **k: None)
    assert _decode_programs(cfg) == reachable
    for text in reachable:
        assert "shard_map" not in text and "sharding_constraint" not in text
        assert "name=attn_out" not in text
