"""Layers of two kinds in one model (ISSUE 32: MiniCPM-SALA — block-selected
sparse attention on the paged K/V pool, decayed linear attention on a fixed
state a slot) against the plain reference
``perfbench/reference/minicpm_sala.py``, at a toy size on the CPU in float32
on seeded weights, with ``dense_len`` and ``window_size`` scaled down so that
the selection is ACTIVE (150 tokens against a dense limit of 48).

Logits are compared, never sampled tokens. Top-k is discontinuous, so where
the system hands its choice of blocks out the reference is given it
(``selected=``) and its own choice is compared too. Tolerance 1e-4 of the
largest logit: both sides compute in float32 and differ by the order of
their sums (a chunked scan against a token scan, an online softmax over
chosen blocks against a masked dense one), which reads about 3e-7 here; each
fault below reads far more (asserted).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import minicpm_sala as ref
from ray_tpu.models import forward, logical_axes
from ray_tpu.models import transformer
from ray_tpu.models.decode import (LinearState, init_paged_caches,
                                   paged_prefill_into_slot,
                                   paged_verify_step)
from ray_tpu.models.presets import minicpm_sala_debug
from ray_tpu.ops.linear_attention import (linear_attention_chunk,
                                          linear_attention_step, slopes)
from ray_tpu.ops.sparse_attention import SparseSizes
from tests import model_harness as harness
from tests.model_harness import rel as rel_err, serve

TOL = 1e-4
T = 4        # page_tokens: the toy selection's kernel_stride
P = 64       # pages a slot: 256 tokens


def hp_of(cfg):
    """The reference's view of a program config (the source's keys)."""
    return {"hidden_size": cfg.embed_dim, "rms_norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta, "scale_emb": cfg.scale_emb,
            "scale_depth": cfg.scale_depth,
            "published_num_hidden_layers": cfg.scale_depth_layers,
            "dim_model_base": cfg.dim_model_base,
            "mixer_types": list(cfg.kinds),
            "lightning_slope_exponent": cfg.linear_slope_exponent,
            "sparse_config": dict(cfg.sparse_config)}


# seeded weights whose norm scales are not all ones (so a norm that is left
# out shows)
seeded = functools.partial(harness.seeded, keys=256)


@pytest.fixture(scope="module")
def toy():
    cfg = minicpm_sala_debug()
    params = seeded(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 150), 1,
                                cfg.vocab_size)
    want = ref.forward(params, tokens, hp_of(cfg))
    return cfg, params, tokens, want


# ------------------------------------------------------- the full forward


@pytest.mark.parametrize("scan_layers", [True, False],
                         ids=["stacked_by_period", "layers_apart"])
def test_forward_logits_match_the_reference(toy, scan_layers):
    cfg, _, tokens, _ = toy
    cfg = dataclasses.replace(cfg, scan_layers=scan_layers)
    params = seeded(cfg)
    logits, chosen = forward(cfg, params, tokens, return_selected=True)
    want, picked = ref.forward(params, tokens, hp_of(cfg),
                               return_selected=True)
    assert rel_err(logits, want) < TOL
    given = ref.forward(params, tokens, hp_of(cfg), selected=chosen)
    assert rel_err(logits, given) < TOL
    # the selection is at work, and the two sides agree on it
    theirs = np.stack([p[0] for p in picked])
    blocks = theirs.shape[-1]
    assert np.array_equal(np.asarray(chosen)[..., :blocks], theirs)
    last = theirs[:, :, -1]            # the query at position 149
    assert (last.sum(-1) == 6).all() and blocks == 10
    # the scan over periods is the walk over layers
    assert rel_err(forward(cfg, params, tokens), logits) < 1e-6
    # every weight has its logical axes, whichever way the layers lie
    named = jax.tree.map(lambda axes, w: len(axes) == w.ndim,
                         logical_axes(cfg), params,
                         is_leaf=lambda x: isinstance(x, tuple))
    assert all(jax.tree.leaves(named))


def faulty(monkeypatch, fault, cfg):
    """``cfg`` (and the program, patched) with one thing wrong."""
    if fault == "bfloat16":
        return dataclasses.replace(cfg, dtype=jnp.bfloat16)
    if fault == "wrong_decay":
        return dataclasses.replace(cfg, linear_slope_exponent=7.0)
    if fault == "selection_off":
        return dataclasses.replace(cfg, sparse_config=dict(
            cfg.sparse_config, dense_len=10 ** 6))
    if fault == "no_qk_norm":
        return dataclasses.replace(cfg, head_qk_norm=False)
    if fault == "no_gate":
        monkeypatch.setattr(
            transformer, "_gated_out", lambda cfg, p, x, o: jnp.einsum(
                "bshk,hkd->bsd", o, p["wo"].astype(cfg.dtype)))
    if fault == "no_output_norm":
        real = transformer.rms_norm
        monkeypatch.setattr(
            transformer, "rms_norm", lambda x, w, eps=1e-6: x
            if w.shape[0] == cfg.num_heads * cfg.head_dim else real(x, w, eps))
    if fault == "rope_on_sparse_layers":
        real_qkv = transformer._qkv
        monkeypatch.setattr(
            transformer, "_qkv", lambda cfg, p, x, rope, positions: real_qkv(
                cfg, p, x, transformer.COMPUTED, positions))
    return cfg


@pytest.mark.parametrize("fault", [
    "bfloat16", "wrong_decay", "selection_off", "no_qk_norm", "no_gate",
    "no_output_norm", "rope_on_sparse_layers"])
def test_the_tolerance_refuses(toy, monkeypatch, fault):
    cfg, params, tokens, want = toy
    bad = faulty(monkeypatch, fault, cfg)
    assert rel_err(forward(bad, params, tokens[:1]), want[:1]) > 3 * TOL


# ---------------------------------------------- contiguous prefill + decode


def test_prefill_and_decode_step_match_the_full_forward(toy):
    cfg, params, tokens, want = toy
    n, total = 120, tokens.shape[1]
    got = harness.cached_logits(cfg, params, tokens[:, :-1], n, length=total)
    assert rel_err(got, want[:, n - 1:-1]) < TOL


# ------------------------------------------------------ the paged programs


def paged_setup(cfg, slots):
    caches = init_paged_caches(cfg, slots * P + 1, T, P, slots=slots)
    tables = (1 + np.arange(slots * P, dtype=np.int32)).reshape(slots, P)
    return caches, jnp.asarray(tables)


def programs(cfg):
    """The chunk's program and the step's, the harness's (one compile each
    for the drive and for the cases below)."""
    return harness.paged_programs(cfg, attn="reference", logits=True)


def chunks_into(cfg, params, prompt, slot, caches, tables, chunk, slots,
                between=None):
    """Prefill ``prompt`` into ``slot`` in chunks of ``chunk``; returns the
    last chunk's logits and the caches. ``between(caches, cursor)`` runs
    after every chunk but the last."""
    logits = None
    for c0 in range(0, len(prompt), chunk):
        part = prompt[c0:c0 + chunk]
        padded = np.zeros((1, chunk), np.int32)
        padded[0, :len(part)] = part
        _, caches, logits = programs(cfg)[0](
            params, jnp.asarray(padded), np.int32(len(part)), np.int32(c0),
            tables[slot], tables[slot], caches, jnp.zeros(slots, jnp.int32),
            np.int32(-1), np.float32(0), np.uint32(0), None, np.int32(slot))
        if between is not None and c0 + chunk < len(prompt):
            caches = between(caches, c0 + len(part))
    return logits, caches


def _paged(request):
    """Two prompts through the paged programs (``harness.paged_drive``):
    chunks of 32 into slots 1 and 2 of 4 (slots 0 and 3 hold no sequence),
    each chunk ALONE (no step's rows: the fused turn is
    ``tests/test_serve_fused_turn.py``'s), then decode steps. (Every page
    but the garbage page is some slot's: there is none to poison.)"""
    cfg, params, tokens, _ = request.getfixturevalue("toy")
    caches, tables = paged_setup(cfg, 4)
    return dict(cfg=cfg, params=params, tokens=tokens, caches=caches,
                tables=np.asarray(tables), impl="reference", along=None,
                poisoned=False, lengths={1: 101, 2: 128}, chunk=32, steps=12)


paged_run = harness.paged_fixture(_paged)


@pytest.mark.parametrize("b,slot", [(0, 1), (1, 2)])
def test_paged_chunks_and_decode_match_the_full_forward(toy, paged_run, b,
                                                        slot):
    want = toy[3]
    n = paged_run["n"][slot]
    got = harness.slot_logits(paged_run, slot)
    assert rel_err(got, want[b, n - 1:n + 12]) < TOL


def test_slots_without_a_sequence_keep_a_zero_state(toy, paged_run):
    cfg = toy[0]
    for kind, c in zip(cfg.kinds, paged_run["caches"]):
        if kind == "lightning-attn":
            assert isinstance(c, LinearState)
            assert not np.asarray(c.s[0]).any() and not np.asarray(
                c.s[3]).any()
            assert np.asarray(c.s[1]).any()


@pytest.mark.parametrize("chunk", [1, 7, 64],
                         ids=["chunk1", "chunk7", "chunk64"])
def test_the_state_is_carried_across_chunks_of_any_size(toy, chunk):
    """Chunks of 1 (the one-row update), 7 (a ragged block) and 64 (whole
    blocks of the scan, the toy's 512) leave the same states and logits."""
    cfg, params, tokens, want = toy
    n = 77
    caches, tables = paged_setup(cfg, 2)
    logits, caches = chunks_into(cfg, params, np.asarray(tokens[0, :n]), 1,
                                 caches, tables, chunk, 2)
    assert rel_err(logits, want[0, n - 1]) < TOL
    whole, ref_caches = chunks_into(cfg, params, np.asarray(tokens[0, :n]),
                                    1, paged_setup(cfg, 2)[0], tables, 128, 2)
    for kind, a, b in zip(cfg.kinds, caches, ref_caches):
        if kind == "lightning-attn":
            assert rel_err(a.s[1], b.s[1]) < TOL


def test_a_step_between_two_chunks_leaves_the_prefilling_slot_alone(toy):
    """A decode step of slot 0 while slot 1 is mid-prompt: slot 1's states
    come back bitwise, and its prompt ends as if no step had run."""
    cfg, params, tokens, want = toy
    slots = 2
    caches, tables = paged_setup(cfg, slots)
    _, caches = chunks_into(cfg, params, np.asarray(tokens[1, :50]), 0,
                            caches, tables, 64, slots)

    def a_step(caches, cursor):
        before = [np.asarray(c.s[1]) for c in caches
                  if isinstance(c, LinearState)]
        _, after, _ = programs(cfg)[1](
            params, jnp.asarray([tokens[1, 50], 0], jnp.int32),
            jnp.asarray([1, 0], jnp.int32),
            jnp.asarray([50, cursor], jnp.int32), tables, tables, caches,
            jnp.zeros(slots, jnp.float32), jnp.zeros(slots, jnp.uint32))
        now = [np.asarray(c.s[1]) for c in after
               if isinstance(c, LinearState)]
        assert all(np.array_equal(x, y) for x, y in zip(before, now))
        assert any(x.any() for x in before)
        return after

    logits, _ = chunks_into(cfg, params, np.asarray(tokens[0, :90]), 1,
                            caches, tables, 32, slots, between=a_step)
    assert rel_err(logits, want[0, 89]) < TOL


def test_chunked_scan_is_the_token_scan():
    B, S, H, D = 2, 300, 4, 16
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q, k, v = (jax.random.normal(key, (B, S, H, D)) for key in keys[:3])
    state = jax.random.normal(keys[3], (B, H, D, D))
    slope = slopes(H)
    real = 290
    o, after = linear_attention_chunk(q, k, v, state, slope, jnp.int32(real))
    active = jnp.ones((B,), jnp.int32)
    outs = []
    for t in range(real):
        o_t, state = linear_attention_step(q[:, t], k[:, t], v[:, t], state,
                                           slope, active)
        outs.append(o_t)
    assert rel_err(o[:, :real], jnp.stack(outs, 1)) < TOL
    assert rel_err(after, state) < TOL
    # a row that is not active keeps its state bitwise
    _, kept = linear_attention_step(q[:, 0], k[:, 0], v[:, 0], state, slope,
                                    jnp.asarray([1, 0], jnp.int32))
    assert np.array_equal(np.asarray(kept[1]), np.asarray(state[1]))
    assert not np.array_equal(np.asarray(kept[0]), np.asarray(state[0]))


def test_the_counters_mirror_what_a_position_attends():
    sizes = SparseSizes(**dict(minicpm_sala_debug().sparse_config))
    t = np.arange(0, 400)
    n = sizes.chosen_blocks(t)
    assert (n[:48] == t[:48] // 16 + 1).all()          # dense: every block
    assert n[149] == 6 and n.max() <= sizes.max_chosen_blocks()
    assert (sizes.attended_tokens(t) <= t + 1).all()
    published = SparseSizes()
    assert published.chosen_blocks(8191) == 128
    assert published.chosen_blocks(33279) in (97, 98)
    assert published.max_chosen_blocks() == 129


# ------------------------------------------------------------ the scheduler


def scheduler(cfg, params, slots):
    from ray_tpu.serve._private.continuous import ContinuousScheduler

    return ContinuousScheduler(cfg, params, slots=slots, prefill_chunk=32,
                               arena_len=P * T, page_tokens=T,
                               kv_pages=slots * P + 1, prefix_cache=False,
                               attn="reference")


def test_scheduler_serves_both_kinds_and_a_reused_slot_starts_from_zero(toy):
    """Through ``ContinuousScheduler``: served tokens are the reference's
    choice or within TOL of it; then, ONE slot, a second request after the
    first — the slot's states are the first request's until the second's
    first chunk takes them as zero, and an admission dispatches nothing."""
    cfg, params, tokens, _ = toy
    prompts = [np.asarray(tokens[0, :101]).tolist(),
               np.asarray(tokens[1, :70]).tolist(),
               np.asarray(tokens[0, 5:45]).tolist()]
    sched = scheduler(cfg, params, 2)
    carried, plain = [], []  # live rows a chunk's program took; plain steps
    dispatch, step = sched._dispatch_chunk, sched._step
    sched._dispatch_chunk = lambda seq, tokens, real, rows: (
        carried.append(len(rows.live)), dispatch(seq, tokens, real, rows))[1]
    sched._step = lambda *args: (plain.append(1), step(*args))[1]
    sched._step._cache_size = step._cache_size
    try:
        served = serve(sched, prompts, 6)
        stats = sched.stats()
    finally:
        sched.shutdown()
    for prompt, out in zip(prompts, served):
        assert len(out) == 6
        assert harness.near_the_references_best(
            lambda seq: ref.forward(params, seq, hp_of(cfg)), prompt, out,
            tol=TOL)
    # layers of other kinds too: a chunk's program takes the live rows along
    assert stats["fused_turns"] == sum(1 for n in carried if n) > 0
    assert stats["fused_step_rows"] == sum(carried)
    assert len(carried) == stats["prefill_chunks"]
    assert len(plain) == stats["decode_steps"] - stats["fused_turns"] > 0
    assert stats["state_slots"] == 2
    assert stats["state_bytes"] == 6 * 2 * 4 * 16 * 16 * 4
    assert stats["linear_chunk_calls"] == 6 * stats["prefill_chunks"]
    assert stats["linear_step_rows"] == 6 * 5 * len(prompts)
    rows = sum(len(p) for p in prompts) + 5 * len(prompts)
    assert stats["sparse_rows"] == 2 * rows
    assert 0 < stats["sparse_rows_dense"] < stats["sparse_rows"]
    assert (0 < stats["sparse_tokens_attended"]
            < stats["sparse_tokens_context"])

    one = scheduler(cfg, params, 1)
    calls = {"n": 0, "in_admit": 0}
    for name in ("_prefill", "_step"):
        program = getattr(one, name)

        def counted(*args, program=program):
            calls["n"] += 1
            return program(*args)
        counted._cache_size = program._cache_size
        setattr(one, name, counted)
    admit = one._admit

    def watched():
        before = calls["n"]
        admit()
        calls["in_admit"] += calls["n"] - before
    one._admit = watched
    try:
        first = serve(one, prompts[:1], 6)
        again = serve(one, prompts[1:2], 6)
        stats = one.stats()
    finally:
        one.shutdown()
    assert first[0] == served[0] and again[0] == served[1]
    assert calls["in_admit"] == 0
    assert calls["n"] == stats["prefill_chunks"] + stats["decode_steps"]
    assert stats["compiled_programs"] == 2 and stats["admitted"] == 2


def test_what_cannot_continue_a_state_is_refused(toy):
    from ray_tpu.serve._private.continuous import ContinuousScheduler

    cfg, params, tokens, _ = toy
    kw = dict(slots=2, prefill_chunk=32, arena_len=P * T, page_tokens=T,
              attn="reference")
    with pytest.raises(ValueError, match="prefix_cache"):
        ContinuousScheduler(cfg, params, prefix_cache=True, **kw)
    with pytest.raises(ValueError, match="speculative"):
        ContinuousScheduler(cfg, params, prefix_cache=False,
                            drafter=object(), **kw)
    with pytest.raises(ValueError, match="page_tokens"):
        ContinuousScheduler(cfg, params, **dict(kw, page_tokens=8))
    with pytest.raises(ValueError, match="needs slots"):
        init_paged_caches(cfg, 2 * P + 1, T, P)
    caches, tables = paged_setup(cfg, 2)
    with pytest.raises(ValueError, match="paged_verify_step"):
        paged_verify_step(cfg, params, jnp.zeros((2, 3), jnp.int32),
                          jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32),
                          tables, tables, caches, attn="reference")
    with pytest.raises(ValueError, match="state_slot"):
        paged_prefill_into_slot(
            cfg, params, jnp.zeros((1, 32), jnp.int32), 32, np.int32(0),
            tables[0], tables[0], caches, jnp.zeros(2, jnp.int32),
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
        minicpm_sala_debug(layer_kinds=("minicpm4", "softmax"))
