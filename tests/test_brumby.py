"""Brumby-shaped models (ISSUE 43): every layer's mixer power retention of
degree 2, gated and normalised, on the state of a K/V head that a group of
query heads shares — and nothing in the model that holds a page.

The program (``ops/power_retention.py``'s two kernels, interpreted here;
``transformer.retention_mixer``; the contiguous and the paged forwards; the
scheduler) is held to ``perfbench/reference/brumby.py``, which computes the
ATTENTION form token against token and imports nothing of the program, in
float32 at 1e-4 of the largest logit. Both sides differ by the order of
their sums (a recurrence on 8256-row states in blocks against a masked
product), which reads about 2e-6 here; each named fault reads far more
(asserted).
"""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import brumby as ref
from ray_tpu.models import forward, init_params, logical_axes, transformer
from ray_tpu.models.decode import (RetentionState, init_caches,
                                   init_paged_caches,
                                   paged_prefill_into_slot,
                                   paged_verify_step)
from ray_tpu.models.presets import brumby_debug
from ray_tpu.ops import power_retention as pr
from tests import model_harness as harness
from tests.model_harness import rel as rel_err

TOL = 1e-4


def hp_of(cfg):
    """The reference's view of a program config (the source's keys)."""
    return {"rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "retention_eps": pr.EPS, "num_hidden_layers": cfg.num_layers}


@pytest.fixture(scope="module")
def toy():
    cfg = brumby_debug()
    params = init_params(cfg, jax.random.key(3))
    # norm scales and gates that are not the identity
    params = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.key(a.size), a.shape)
        if a.ndim <= 2 else a, params)
    tokens = jax.random.randint(jax.random.key(1), (2, 150), 0,
                                cfg.vocab_size)
    return cfg, params, tokens, ref.forward(params, tokens, hp_of(cfg))


def test_the_preset_is_the_published_shape_in_small():
    cfg = brumby_debug()
    assert set(cfg.kinds) == {transformer.RETENTION} and cfg.num_layers >= 2
    assert cfg.num_heads // cfg.kv_heads == 5      # 40 over 8, as published
    assert cfg.recurrent and not cfg.holds_pages
    assert brumby_debug(num_layers=3).kinds == (transformer.RETENTION,) * 3
    params = init_params(cfg, jax.random.key(0))
    named = jax.tree.map(lambda a, ax: len(ax) == a.ndim, params,
                         logical_axes(cfg),
                         is_leaf=lambda x: isinstance(x, tuple))
    assert all(jax.tree.leaves(named))
    assert params["blocks"]["attn"]["wc"].shape == (
        cfg.num_layers, cfg.embed_dim, cfg.kv_heads)
    assert "wg" not in params["blocks"]["attn"]


def test_the_state_is_the_symmetric_half_and_its_shape_has_one_source():
    """8256 rows a K/V head at the published width, in whole 128-lane rows
    (never the 16384 of the square), and every holder reads the one place."""
    tiles, lanes = pr.layout(128)
    assert 8256 <= tiles * lanes <= 9216 and lanes % 128 == 0
    cfg = brumby_debug()
    shapes = transformer.state_shapes(cfg, transformer.RETENTION, 3)
    assert shapes == pr.state_shapes(3, cfg.kv_heads, cfg.head_dim)
    half = cfg.head_dim * (cfg.head_dim + 1) // 2
    assert half <= shapes["s"][2] * shapes["s"][4] < half + 128
    for cache in (init_caches(cfg, 3, 64)
                  + init_paged_caches(cfg, 1, 64, 1, slots=3)):
        assert isinstance(cache, RetentionState)
        assert {n: a.shape for n, a in cache.arrays().items()} == shapes
    with pytest.raises(ValueError, match="keeps no state"):
        transformer.state_shapes(cfg, transformer.ATTENTION, 1)


@pytest.mark.parametrize("D", [16, 32, 64, 128])
def test_the_fold_holds_every_pair_once_by_weight(D):
    """The layout alone, no kernel: lane ``r D + a`` is the pair ``(a, (a +
    r) mod D)``, ``r = 0 .. D / 2``. Every pair of two values weighs 2 on
    the key side in all (once at 2, or twice at 1 where ``r = D / 2``), every
    square 1, the padding 0 with zero pairs, and the weighted product of two
    heads' pairs is ``(q . k)^2`` exactly."""
    tiles, lanes = pr.layout(D)
    half, fold = D * (D + 1) // 2, D * (D // 2 + 1)
    assert half <= fold <= tiles * lanes < half + 128 and lanes % 128 == 0
    if D == 128:
        assert (tiles, lanes) == (13, 640) and fold == tiles * lanes
    a, b = pr._fold_index(D)
    w = pr._weights(D).reshape(-1)
    assert a.size == fold and not w[fold:].any()
    total = np.zeros((D, D))
    np.add.at(total, (np.minimum(a, b), np.maximum(a, b)), w[:fold])
    assert np.array_equal(total, np.triu(2 * np.ones((D, D))) - np.eye(D))
    assert set(w[:D]) == {1.0} and set(w[D * (D // 2):fold]) == {1.0}
    rng = np.random.default_rng(D)
    q, k = rng.integers(-3, 4, (2, 5, D)).astype(np.float32)
    pq, pk = (np.asarray(pr._phi(jnp.asarray(u))).reshape(5, -1)
              for u in (q, k))
    assert np.array_equal(pq[:, :fold], q[:, a] * q[:, b])
    assert not pq[:, fold:].any() and not pk[:, fold:].any()
    assert np.array_equal((pq * w * pk).sum(-1), (q * k).sum(-1) ** 2)
    for m, index in zip(pr._selectors(D), (a, b)):   # what _phi picks by
        m = m.transpose(1, 0, 2).reshape(D, -1)
        assert np.array_equal(q @ m[:, :fold], q[:, index])
        assert not m[:, fold:].any()


@pytest.mark.parametrize("D,selectors", [(128, 0), (32, 2)],
                         ids=["d128_rotates", "d32_selects"])
def test_a_head_of_whole_lane_rows_takes_no_selection_operand(D, selectors):
    """Nothing runs: the chunk kernel's call, read from the jaxpr. At the
    published head size no operand has a selector's shape ``[tiles, D,
    lanes]``; a narrower head takes two."""
    G, S = 1, 128
    tiles, lanes = pr.layout(D)
    shape = jax.ShapeDtypeStruct
    rows = lambda heads: shape((1, S, heads, D), jnp.bfloat16)
    state = [shape(s, jnp.float32)
             for s in pr.state_shapes(1, G, D).values()]
    jaxpr = jax.make_jaxpr(pr.power_retention_chunk)(
        rows(5), rows(G), rows(G), shape((1, S, G), jnp.float32), *state,
        shape((), jnp.int32))

    def calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    call, = calls(jaxpr.jaxpr)
    assert call.params["name"] == "power_retention_chunk"
    shapes = [v.aval.shape for v in call.invars]
    assert shapes.count((tiles, D, lanes)) == selectors
    assert (tiles, 1, lanes) in shapes                 # the weights stay


def test_the_uncached_forward_matches_the_reference(toy):
    cfg, params, tokens, want = toy
    assert rel_err(forward(cfg, params, tokens), want) < TOL


# ------------------------------------------------------------ the kernels


def plain(q, k, v, gate, *, degree=2, gated=True, normalised=True):
    """The attention form in jax.numpy, with a fault to order. q [B, S, H,
    D], k, v [B, S, G, D], gate [B, S, G] -> [B, S, H, D] float32."""
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    run = jnp.cumsum(jnp.repeat(gate, rep, axis=2) if gated
                     else jnp.zeros(q.shape[:3]), axis=1)
    score = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(q.shape[-1])
    s = q.shape[1]
    seen = jnp.tril(jnp.ones((s, s), bool))
    decay = jnp.exp(jnp.where(
        seen, run.transpose(0, 2, 1)[..., None]
        - run.transpose(0, 2, 1)[:, :, None], -jnp.inf))
    a = score ** degree * decay
    num = jnp.einsum("bhqk,bkhd->bqhd", a, v)
    if not normalised:
        return num
    return num / (a.sum(-1).transpose(0, 2, 1)[..., None] + pr.EPS)


def operands(seed, B, S, H, G, D):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, G, D))
    v = jax.random.normal(ks[2], (B, S, G, D))
    gate = jax.nn.log_sigmoid(jax.random.normal(ks[3], (B, S, G)) + 2.0)
    return q, k, v, gate


def zero_state(B, G, D):
    return [jnp.zeros(shape, jnp.float32)
            for shape in pr.state_shapes(B, G, D).values()]


def steps(q, k, v, gate, s, z, active=None):
    """The step kernel, token by token."""
    B, out = q.shape[0], []
    active = jnp.ones((B,), jnp.int32) if active is None else active
    for t in range(q.shape[1]):
        o, s, z = pr.power_retention_step(q[:, t], k[:, t], v[:, t],
                                          gate[:, t], s, z, active)
        out.append(o)
    return jnp.stack(out, 1), s, z


@pytest.mark.parametrize("D,H,G,S", [(16, 4, 2, 40), (32, 10, 2, 150),
                                     (128, 5, 1, 140)],
                         ids=["d16", "d32_two_blocks", "published_d128"])
def test_the_recurrence_is_the_attention_form(D, H, G, S):
    """Both kernels against the attention form: five query heads on one
    state at the published head size, a chunk of more than one block."""
    q, k, v, gate = operands(D, 2, S, H, G, D)
    want = plain(q, k, v, gate)
    o, s, z = pr.power_retention_chunk(q, k, v, gate, *zero_state(2, G, D), S)
    assert rel_err(o, want) < TOL
    n = min(S, 24)
    o1, s1, z1 = steps(q[:, :n], k[:, :n], v[:, :n], gate[:, :n],
                       *zero_state(2, G, D))
    assert rel_err(o1, want[:, :n]) < TOL
    # a chunk IS its steps one by one: the same state behind them
    _, sn, zn = pr.power_retention_chunk(q[:, :n], k[:, :n], v[:, :n],
                                         gate[:, :n], *zero_state(2, G, D), n)
    assert rel_err(sn, s1) < TOL and rel_err(zn, z1) < TOL


def test_the_state_carries_over_chunks_of_unequal_length_and_padding():
    """Chunks of 17, 64 and 69 tokens, the first two padded to 24 and 80:
    the padding neither decays the state nor adds to it, so each chunk's
    state is the state after its real tokens EXACTLY (what one chunk over as
    many tokens leaves), and the outputs are the whole sequence's."""
    D, H, G, S = 32, 10, 2, 150
    q, k, v, gate = operands(7, 1, S, H, G, D)
    want = plain(q, k, v, gate)
    got, (s, z), at = [], zero_state(1, G, D), 0
    for real, padded in ((17, 24), (64, 80), (69, 69)):
        cut = lambda x: x[:, at:at + padded]
        o, s, z = pr.power_retention_chunk(cut(q), cut(k), cut(v), cut(gate),
                                           s, z, real)
        got.append(o[:, :real])
        at += real
        _, s_whole, z_whole = pr.power_retention_chunk(
            q[:, :at], k[:, :at], v[:, :at], gate[:, :at],
            *zero_state(1, G, D), at)
        assert rel_err(s, s_whole) < TOL and rel_err(z, z_whole) < TOL
    assert rel_err(jnp.concatenate(got, 1), want) < TOL
    # steps continue a chunk's state
    o, _, _ = steps(q[:, 140:], k[:, 140:], v[:, 140:], gate[:, 140:],
                    *pr.power_retention_chunk(
                        q[:, :140], k[:, :140], v[:, :140], gate[:, :140],
                        *zero_state(1, G, D), 140)[1:])
    assert rel_err(o, want[:, 140:]) < TOL


def test_an_inactive_rows_state_comes_back_bitwise():
    D, H, G = 32, 10, 2
    q, k, v, gate = operands(11, 3, 9, H, G, D)
    _, s, z = pr.power_retention_chunk(q[:, :8], k[:, :8], v[:, :8],
                                       gate[:, :8], *zero_state(3, G, D), 8)
    active = jnp.asarray([1, 0, 1], jnp.int32)
    _, s1, z1 = pr.power_retention_step(q[:, 8], k[:, 8], v[:, 8],
                                        gate[:, 8], s, z, active)
    assert np.array_equal(np.asarray(s1[1]), np.asarray(s[1]))
    assert np.array_equal(np.asarray(z1[1]), np.asarray(z[1]))
    assert not np.array_equal(np.asarray(s1[0]), np.asarray(s[0]))
    assert not np.array_equal(np.asarray(z1[2]), np.asarray(z[2]))


def test_five_query_heads_read_one_state():
    """The state depends on k, v and the gate alone: other query heads
    leave it bitwise, and each head's output is its own."""
    D, G = 32, 2
    q, k, v, gate = operands(13, 1, 40, 10, G, D)
    o, s, z = pr.power_retention_chunk(q, k, v, gate, *zero_state(1, G, D),
                                       40)
    o2, s2, z2 = pr.power_retention_chunk(q[:, :, ::-1], k, v, gate,
                                          *zero_state(1, G, D), 40)
    assert np.array_equal(np.asarray(s), np.asarray(s2))
    assert np.array_equal(np.asarray(z), np.asarray(z2))
    # head 9 reversed sits at 0, on K/V head 0 instead of 1: another output
    assert rel_err(o2[:, :, 0], plain(q[:, :, 9:], k[:, :, :1], v[:, :, :1],
                                      gate[:, :, :1])[:, :, 0]) < TOL
    assert s.shape[1] == G  # one state a K/V head, not one a query head


# ------------------------------------------------- named faults, each refused


def faulty(monkeypatch, fault):
    """The program with one thing wrong in its mixer."""
    chunk = transformer.power_retention_chunk

    def through_plain(**wrong):
        def run(q, k, v, gate, s, z, real_len):
            return plain(q, k, v, gate, **wrong).astype(q.dtype), s, z
        monkeypatch.setattr(transformer, "power_retention_chunk", run)

    if fault == "none_through_plain":
        through_plain()
    if fault == "degree_1":
        through_plain(degree=1)
    if fault == "normaliser_dropped":
        through_plain(normalised=False)
    if fault == "gate_dropped":
        monkeypatch.setattr(
            transformer, "power_retention_chunk",
            lambda q, k, v, gate, *rest: chunk(q, k, v, jnp.zeros_like(gate),
                                               *rest))
    if fault == "heads_grouped_wrongly":
        # query head h on K/V head h % G instead of h // (H / G)
        def regrouped(q, k, v, *rest):
            H, G = q.shape[2], k.shape[2]
            order = np.argsort(np.arange(H) % G, kind="stable")
            o, s, z = chunk(q[:, :, order], k, v, *rest)
            return o[:, :, np.argsort(order)], s, z
        monkeypatch.setattr(transformer, "power_retention_chunk", regrouped)
    if fault == "sqrt2_dropped":
        # a pair a < b counted once, not for (b, a) too
        real = pr._weights
        monkeypatch.setattr(pr, "_weights",
                            lambda d: np.minimum(real(d), 1.0))
        jax.clear_caches()  # the kernels' programs were traced with the 2


@pytest.mark.parametrize("fault", [
    "none_through_plain", "degree_1", "gate_dropped", "normaliser_dropped",
    "sqrt2_dropped", "heads_grouped_wrongly"])
def test_the_tolerance_refuses(toy, monkeypatch, fault):
    cfg, params, tokens, want = toy
    faulty(monkeypatch, fault)
    try:
        err = rel_err(forward(cfg, params, tokens[:1]), want[:1])
    finally:
        if fault == "sqrt2_dropped":
            monkeypatch.undo()
            jax.clear_caches()
    if fault == "none_through_plain":
        assert err < TOL    # the way the faults are planted plants none
    else:
        assert err > 3 * TOL, (fault, err)


# ---------------------------------------------- contiguous prefill + decode


def test_prefill_and_decode_step_match_the_full_forward(toy):
    cfg, params, tokens, want = toy
    n, total = 120, tokens.shape[1]
    got = harness.cached_logits(cfg, params, tokens[:, :-1], n, length=total)
    assert rel_err(got, want[:, n - 1:-1]) < TOL


# ------------------------------------------------------ the paged programs

C = 32  # the chunk


def programs(cfg):
    """The chunk's program and the step's, the harness's."""
    return harness.paged_programs(cfg, attn="reference", logits=True)


def chunks_into_slot(cfg, params, caches, prompt, slot, slots):
    """A prompt through chunks of C (the last one padded) into ``slot``;
    no page table anywhere: the model holds no page."""
    ids = jnp.zeros((slots,), jnp.int32)
    logits = None
    for at in range(0, len(prompt), C):
        part = prompt[at:at + C]
        tokens = np.zeros((1, C), np.int32)
        tokens[0, :len(part)] = part
        ids, caches, logits = programs(cfg)[0](
            params, tokens, np.int32(len(part)), np.int32(at), None, None,
            caches, ids, np.int32(slot), np.float32(0), np.uint32(0), None,
            np.int32(slot))
    return ids, caches, logits


def test_the_paged_programs_match_the_reference_without_a_page(toy):
    cfg, params, tokens, want = toy
    slots, n = 3, 100
    caches = init_paged_caches(cfg, 1, 128, 1, slots=slots)
    assert all(isinstance(c, RetentionState) for c in caches)
    row = np.asarray(tokens[0])
    ids, caches, logits = chunks_into_slot(cfg, params, caches, row[:n], 1,
                                           slots)
    assert rel_err(logits, want[0, n - 1]) < TOL
    # another sequence in slot 2, so that the step has two live rows
    other = np.asarray(tokens[1])
    _, caches, logits2 = chunks_into_slot(cfg, params, caches, other[:70], 2,
                                          slots)
    assert rel_err(logits2, want[1, 69]) < TOL
    active = jnp.asarray([0, 1, 1], jnp.int32)
    before = jax.tree.map(np.asarray, caches)
    cursors = np.asarray([0, n, 70], np.int32)
    for t in range(6):
        fed = jnp.asarray([0, row[n + t], other[70 + t]], jnp.int32)
        _, caches, logits = programs(cfg)[1](
            params, fed, active, jnp.asarray(cursors + t), None, None,
            caches, jnp.zeros(slots, jnp.float32),
            jnp.zeros(slots, jnp.uint32))
        assert rel_err(logits[1], want[0, n + t]) < TOL
        assert rel_err(logits[2], want[1, 70 + t]) < TOL
    # the slot that took no part has its states bitwise
    for old, new in zip(before, caches):
        for name, a in new.arrays().items():
            assert np.array_equal(np.asarray(a[0]), getattr(old, name)[0])


def test_a_chunk_at_position_zero_starts_from_a_zero_state(toy):
    """A retired slot's state is whatever its last sequence left: the next
    sequence's first chunk takes it as zero, with no reset in between."""
    cfg, params, tokens, want = toy
    clean = init_paged_caches(cfg, 1, 128, 1, slots=2)
    dirty = jax.tree.map(lambda a: a + 1.0, clean)
    row = np.asarray(tokens[0])[:40]
    _, _, a = chunks_into_slot(cfg, params, clean, row, 1, 2)
    _, left, b = chunks_into_slot(cfg, params, dirty, row, 1, 2)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert rel_err(b, want[0, 39]) < TOL
    assert float(left[0].s[0].min()) == 1.0   # the other slot: untouched


def test_what_such_a_model_refuses(toy):
    from ray_tpu.serve._private.continuous import ContinuousScheduler

    cfg, params, _, _ = toy
    kw = dict(slots=2, prefill_chunk=16, arena_len=64)
    with pytest.raises(ValueError, match="prefix_cache"):
        ContinuousScheduler(cfg, params, prefix_cache=True, **kw)
    with pytest.raises(ValueError, match="speculative"):
        ContinuousScheduler(cfg, params, drafter=object(), **kw)
    with pytest.raises(ValueError, match="needs slots"):
        init_paged_caches(cfg, 1, 64, 1)
    caches = init_paged_caches(cfg, 1, 64, 1, slots=2)
    with pytest.raises(ValueError, match="paged_verify_step"):
        paged_verify_step(cfg, params, jnp.zeros((2, 3), jnp.int32),
                          jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32),
                          None, None, caches, attn="reference")
    with pytest.raises(ValueError, match="state_slot"):
        paged_prefill_into_slot(
            cfg, params, jnp.zeros((1, 16), jnp.int32), np.int32(16),
            np.int32(0), None, None, caches, jnp.zeros(2, jnp.int32),
            np.int32(-1), np.float32(0), np.uint32(0), None,
            attn="reference")
    sched = ContinuousScheduler(cfg, params, **kw)  # the default: cache off
    try:
        assert "radix_nodes" not in sched.stats()
        with pytest.raises(ValueError, match="exports no prefix"):
            sched.export_prefix([1, 2, 3])
    finally:
        sched.shutdown()
    with pytest.raises(ValueError, match="layer_kinds"):
        brumby_debug(layer_kinds=("power-retention", "retention"))


# ------------------------------------------------------------ the scheduler


def test_the_scheduler_serves_a_model_without_pages(toy):
    """Five requests through three slots: the sequential greedy path's
    tokens (so a retired slot's state was taken as zero by the next), two
    compiled programs, no page handed out, and the counters' identities."""
    from ray_tpu.serve._private.continuous import ContinuousScheduler

    cfg, params, _, _ = toy
    rng = np.random.default_rng(1)
    prompts = [[int(x) for x in rng.integers(0, cfg.vocab_size, n)]
               for n in (5, 37, 16, 50, 9)]
    new = 6
    sched = ContinuousScheduler(cfg, params, slots=3, prefill_chunk=16,
                                arena_len=128)
    carried, plain = [], []  # live rows a chunk's program took; plain steps
    dispatch, step = sched._dispatch_chunk, sched._step
    sched._dispatch_chunk = lambda seq, tokens, real, rows: (
        carried.append(len(rows.live)), dispatch(seq, tokens, real, rows))[1]
    sched._step = lambda *args: (plain.append(1), step(*args))[1]
    sched._step._cache_size = step._cache_size

    async def main():
        return await asyncio.gather(*[harness.stream(sched, p, new)
                                      for p in prompts])

    try:
        served = asyncio.run(main())
        st = sched.stats()
    finally:
        sched.shutdown()
    for prompt, (tokens, end) in zip(prompts, served):
        assert end == ("end", "length")
        # the sequential path: argmax of ``decode_step`` a token at a time
        assert tokens == harness.oracle(cfg, params, prompt, new)
    assert st["compiled_programs"] == 2
    # a chunk's program takes the live rows along: one program a turn
    assert st["fused_turns"] == sum(1 for n in carried if n) > 0
    assert st["fused_step_rows"] == sum(carried)
    assert len(carried) == st["prefill_chunks"]
    assert len(plain) == st["decode_steps"] - st["fused_turns"] > 0
    # admitted by slot and arena_len alone: nothing of pages moved
    assert sched.max_prompt_len(28) == 100  # arena_len less the answer
    for key in ("usable_pages", "peak_pages_in_use", "pages_allocated_total",
                "attn_tokens_attended", "attn_tokens_fetched",
                "attn_bytes_moved"):
        assert st[key] == 0, key
    L = cfg.num_layers
    chunks = sum(-(-len(p) // 16) for p in prompts)
    assert st["prefill_chunks"] == chunks
    assert st["retention_chunk_calls"] == L * chunks
    assert st["retention_chunk_tokens"] == L * sum(map(len, prompts))
    assert st["retention_chunk_tokens"] == L * st["prefill_tokens"]
    # a live row a step is a token that is no sequence's first
    assert st["retention_step_rows"] == L * (st["tokens_generated"]
                                             - st["first_tokens"])
    assert st["state_slots"] == 3
    assert st["state_bytes"] == 4 * L * sum(
        int(np.prod(shape)) for shape in transformer.state_shapes(
            cfg, transformer.RETENTION, 3).values())
    assert "linear_step_rows" not in st and "sparse_rows" not in st
