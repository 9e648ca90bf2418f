"""The 'indexed_attention' kind (Keye-VL-2.0's language model: a learned
indexer picks the tokens a query attends, its keys cached a token beside K
and V) against the plain reference, ``perfbench/reference/keye.py``: float32
on the CPU at a toy size whose ``topk`` (16) is smaller than the contexts.

The system is held to the reference GIVEN both its discrete choices (the
experts' routes, the tokens attended) at 1e-4 of the largest logit, through
every forward: without a cache, the contiguous cache (prefill then decode),
and the paged chunk, step and fused turn. Named faults in the reference are
refused by the same comparison; the selection itself is held EXACTLY, a
planted tie included; a context of at most ``topk`` tokens equals the
'attention' kind on the same weights.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench.reference import keye as ref  # noqa: E402
from ray_tpu.models.decode import (init_caches,  # noqa: E402
                                   init_paged_caches, init_slot_caches)
from ray_tpu.models.presets import keye_debug  # noqa: E402
from ray_tpu.models.transformer import (INDEXED, LAYER_KINDS,  # noqa: E402
                                        init_params)
from ray_tpu.ops import indexed_attention as ia  # noqa: E402
from ray_tpu.ops.rotary import apply_rotary_at  # noqa: E402
from tests import model_harness as harness  # noqa: E402
from tests.model_harness import rel  # noqa: E402

TOL = 1e-4


def hp_of(cfg):
    """The reference's configuration object, keyed as the source keys it."""
    return {"num_hidden_layers": cfg.num_layers, "head_dim": cfg.head_dim,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "rope_scaling": {"mrope_section": list(cfg.mrope_section)},
            "sa_config": dataclasses.asdict(cfg.indexer),
            "num_experts_per_tok": cfg.moe_top_k,
            "norm_topk_prob": cfg.moe_renormalize}


# weights with every norm's scale and the index key's bias away from their
# trivial values, and an indexer that speaks up (its projections times 8: the
# seeded 0.02 leaves every score near 0)
seeded = functools.partial(
    harness.seeded, stir=("scale", "q_norm", "k_norm", "ik_bias"), by=0.3,
    times={"wi_": 8.0})


@pytest.fixture(scope="module")
def toy():
    cfg = keye_debug()
    params = seeded(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 72), 0,
                                cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        logits, routes = harness.forward_program(cfg, return_routes=True)(
            params, tokens)
        _, selected = harness.forward_program(cfg, return_selected=True)(
            params, tokens)
    return {"cfg": cfg, "params": params, "tokens": tokens,
            "logits": np.asarray(logits), "routes": np.asarray(routes),
            "selected": np.asarray(selected)}


def test_the_preset_has_what_the_architecture_forces():
    cfg = keye_debug(num_layers=3)
    assert INDEXED in LAYER_KINDS and cfg.kinds == (INDEXED,) * 3
    assert cfg.mlp == "moe" and cfg.moe_renormalize and cfg.head_qk_norm
    assert cfg.kv_heads < cfg.num_heads and cfg.period == 1
    assert sum(cfg.mrope_section) == cfg.head_dim // 2
    assert cfg.indexer.topk == 16 and cfg.holds_pages and not cfg.recurrent
    attn = init_params(cfg, jax.random.PRNGKey(0))["blocks"]["attn"]
    sizes = cfg.indexer
    assert attn["wi_q"].shape == (3, cfg.embed_dim, sizes.indexer_num_heads,
                                  sizes.indexer_head_dim)
    assert attn["wi_k"].shape == (3, cfg.embed_dim, sizes.indexer_head_dim)
    assert attn["wi_w"].shape == (3, cfg.embed_dim, sizes.indexer_num_heads)
    with pytest.raises(ValueError, match="needs sa_config"):
        keye_debug(sa_config=None)
    with pytest.raises(ValueError, match="slot arena holds keys and values"):
        init_slot_caches(cfg, 2, 32)


# ------------------------------------------------ three position streams


def test_three_equal_streams_are_plain_rope_and_unequal_ones_the_rule():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 32))
    pos = jnp.arange(9)[None] + jnp.asarray([[0], [5]])
    plain = apply_rotary_at(x, pos, 1e4)
    three = jnp.stack([pos, pos, pos])
    np.testing.assert_array_equal(
        apply_rotary_at(x, pos, 1e4, None, (4, 6, 6)), plain)
    np.testing.assert_allclose(
        apply_rotary_at(x, three, 1e4, None, (4, 6, 6)), plain, atol=1e-6)
    streams = jnp.stack([pos, 2 * pos + 1, 40 - pos])
    got = apply_rotary_at(x, streams, 1e4, None, (4, 6, 6))
    want = ref.rotate(x, streams.astype(jnp.float32), 1e4, [4, 6, 6], 32)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert np.abs(np.asarray(got - plain)).max() > 0.1
    # an index head of half the pairs takes the streams in proportion
    half = apply_rotary_at(x[..., :16], streams, 1e4, None, (4, 6, 6))
    np.testing.assert_allclose(half, ref.rotate(
        x[..., :16], streams.astype(jnp.float32), 1e4, [4, 6, 6], 32),
        atol=1e-5)


# ------------------------------------------------------------ selection


def _scores(seed, rows=24, keys=600):
    """Index scores as the reference computes them, with ties planted: two
    pairs of identical index keys, and a run of keys scored 0 by every
    head (a ReLU's favourite tie) that is the best of every other query."""
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    qi = jax.random.normal(k[0], (1, rows, 4, 16))
    w = jax.random.normal(k[1], (1, rows, 4))
    ki = jax.random.normal(k[2], (1, keys, 16))
    ki = ki.at[0, 9].set(ki[0, 5]).at[0, 300].set(ki[0, 17])
    ki = ki.at[0, 40:60].set(0.0)
    # every other query weighs all its heads down: its scores are <= 0 and
    # the run of zeros is its best
    w = w.at[0, ::2].set(-jnp.abs(w[0, ::2]))
    return qi, w, ki


@pytest.mark.parametrize("topk", [1, 16, 64, 599, 600, 2048])
def test_the_selection_is_the_references_ties_and_all(topk):
    qi, w, ki = _scores(3)
    rows, keys = qi.shape[1], ki.shape[1]
    with jax.default_matmul_precision("highest"):
        scores = ref.score_block(qi, w, ki)
    positions = (jnp.arange(rows, dtype=jnp.int32) * 25 + 20)[None]
    want = np.stack([np.asarray(ref.select_block(
        scores[:, r:r + 1], int(positions[0, r]), topk))[0, 0]
        for r in range(rows)])
    pad = jnp.pad(scores, ((0, 0), (0, 0), (0, 1024 - keys)),
                  constant_values=jnp.nan)  # what nobody computed
    tau, bound = ia.select(pad, positions, topk, True)
    got = np.asarray(ia.chosen(pad, positions[..., None], tau[..., None],
                               bound[..., None]))[0, :, :keys]
    np.testing.assert_array_equal(got, want)
    counts = np.minimum(np.asarray(positions[0]) + 1, topk)
    np.testing.assert_array_equal(got.sum(-1), counts)
    if topk == 16:
        # the planted ties were live: some row's cut falls on a tie
        tied = [(np.asarray(scores)[0, r] == float(tau[0, r])).sum()
                for r in range(rows)]
        assert max(tied) > 1


def _plain_cut(keys, t, topk, lanes):
    """The two numbers of one row as the kernel before ISSUE 53 came to
    them, written plainly (a sort): ``keys`` float32 [lanes of the table],
    the query at ``t``. ``tau``: the ``topk``-th largest of the keys at or
    before ``t`` (-inf where there are fewer than ``topk``: what lies behind
    the query ties below every score); ``bound``: where more keys lie ON the
    cut than the choice has room for, the index before which they belong,
    else every bit of an index set."""
    full = (1 << lanes.bit_length()) - 1
    live = keys[:t + 1] + np.float32(0.0)
    if t + 1 < topk:
        return -np.inf, topk if lanes > topk else full
    tau = np.sort(live)[::-1][topk - 1]
    need = topk - int((live > tau).sum())
    on = np.flatnonzero(live == tau)
    return tau, int(on[need]) if len(on) > need else full


def _select_case(name):
    """-> (scores [rows, lanes] float32, positions [rows], topk): what the
    selection's loops over a tile's live segments can get wrong."""
    W = ia._SELECT_LANES
    lanes = 3 * W + 512  # a table whose last segment is a short one
    rng = np.random.default_rng(sum(map(ord, name)))
    rows, topk = 16, 64
    scores = rng.standard_normal((rows, lanes)).astype(np.float32)
    positions = rng.integers(topk, lanes, rows)
    if name == "segment_edges":
        positions = np.array([511, 512, 513, W - 1, W, W + 1, 2 * W - 1,
                              2 * W, 2 * W + 1, 3 * W - 1, 3 * W, 3 * W + 1,
                              lanes - 2, lanes - 1, 0, 1])
    elif name == "a_steps_tile":  # 8 rows at contexts from 0 to the end
        positions = np.array([0, lanes - 1, 5, W, topk - 1, 3 * W + 7, 700,
                              2 * W - 1] * 2)
    elif name == "around_topk":  # t + 1 below, at and above topk
        positions = np.array([topk - 3, topk - 2, topk - 1, topk, topk + 1,
                              0, 1, 2 * topk] * 2)
    elif name == "a_reach_of_topk_lanes":
        topk = W
        positions = np.array([W - 2, W - 1, W, W + 1, 0, 5, 2 * W - 1,
                              2 * W] * 2)
    elif name == "topk_past_the_table":
        topk = lanes + 5
    elif name == "ties_across_a_segments_edge":
        # the cut falls on a run of equal scores that straddles an edge
        scores = np.where(scores > 1.0, scores, np.float32(0.25))
        scores[:, W - 40:W + 40] = 1.0
        positions = np.array([W + 3, W + 39, 2 * W, lanes - 1] * 4)
    elif name == "all_equal":
        scores[:] = 0.5
    elif name == "signed_zeros":
        scores = np.where(rng.random(scores.shape) < 0.5, -0.0,
                          0.0).astype(np.float32)
        scores[:, ::7] = rng.standard_normal(scores[:, ::7].shape)
    elif name == "negative_only":
        scores = -np.abs(scores) - 1
    elif name == "coarse":  # many ties everywhere
        scores = np.round(scores * 4) / 4
    else:
        assert name == "random", name
    return scores, positions, topk


@pytest.mark.parametrize("name", [
    "random", "segment_edges", "a_steps_tile", "around_topk",
    "a_reach_of_topk_lanes", "topk_past_the_table",
    "ties_across_a_segments_edge", "all_equal", "signed_zeros",
    "negative_only", "coarse"])
@pytest.mark.parametrize("behind", [np.nan, np.inf])
def test_the_selection_walks_a_tiles_own_context(name, behind):
    """ISSUE 53: the passes walk the segments up to a tile's last position
    and stop when every row's choice is decided — and come to the same
    choice, the same cut and the same bound as passes over the whole table.
    Every lane behind a row's position holds ``behind``: nobody computed
    it, and the kernel must not let it count."""
    scores, positions, topk = _select_case(name)
    rows, lanes = scores.shape
    idx = np.arange(lanes)
    dirty = np.where(idx[None] <= positions[:, None], scores,
                     np.float32(behind))
    pos = jnp.asarray(positions, jnp.int32)[None]
    tau, bound, passes = ia.select(jnp.asarray(dirty)[None], pos, topk, True,
                                   passes=True)
    want = [_plain_cut(scores[r], int(positions[r]), topk, lanes)
            for r in range(rows)]
    np.testing.assert_array_equal(np.asarray(tau)[0],
                                  np.float32([w[0] for w in want]))
    np.testing.assert_array_equal(np.asarray(bound)[0],
                                  [w[1] for w in want])
    got = np.asarray(ia.chosen(jnp.asarray(dirty)[None], pos[..., None],
                               tau[..., None], bound[..., None]))[0]
    plain = scores + np.float32(0.0)  # -0.0 orders as 0.0: ties by index
    taken = np.stack([np.asarray(ref.select_block(
        jnp.asarray(plain[None, r:r + 1]), int(positions[r]), topk))[0, 0]
        for r in range(rows)])
    np.testing.assert_array_equal(got, taken)
    # a tile whose rows' cuts fall on no tie stops early; one whose rows
    # all take every token runs the pass that makes the keys and one more
    passes = np.asarray(passes)[0]
    assert (passes[:8] == passes[0]).all() and (passes[8:] == passes[8]).all()
    if name == "random":
        assert passes.max() < 34
    if name == "topk_past_the_table":
        assert (passes == 2).all()


def test_the_score_kernel_is_the_references_scores():
    qi, w, ki = _scores(5, rows=40, keys=200)
    T, P = 8, 26
    pool = jnp.zeros((1 + P, T, ia.index_width(16))).at[1:].set(
        jnp.pad(ia.index_row(ki[0]), ((0, P * T - 200), (0, 0))).reshape(
            P, T, -1))
    tables = 1 + jnp.arange(P, dtype=jnp.int32)[None]
    positions = (160 + jnp.arange(40, dtype=jnp.int32))[None]
    with jax.default_matmul_precision("highest"):
        got = ia.index_scores(qi, w, pool, tables, positions, True)
        want = ref.score_block(qi, w, ki)
    assert got.shape == (1, 40, 512)
    np.testing.assert_allclose(got[0, :, :200], want[0], atol=1e-5)


# ------------------------------------- a context out of its scattered pages


def _scattered(seed, B, T=8, P=80, G=2, D=16, Di=16):
    """``B`` slots' contexts in pools whose pages lie SCATTERED (a seeded
    permutation) and IN ORDER: random keys, values and index keys of ``P *
    T`` tokens a slot, through tables [B, P]. The garbage page (0, which
    pads a table to whole key tiles) holds finite junk; every page no table
    names is NaN. Returns (the contiguous k, v [B, P * T, G, D] and ki [B,
    P * T, Di], {"scattered" | "in_order": (pools, tables)})."""
    key = jax.random.split(jax.random.PRNGKey(seed), 4)
    k = jax.random.normal(key[0], (B, P * T, G, D))
    v = jax.random.normal(key[1], (B, P * T, G, D))
    ki = jax.random.normal(key[2], (B, P * T, Di))
    N = 1 + B * P + 7
    layouts = {}
    for name, order in (
            ("scattered", np.random.default_rng(seed).permutation(N - 1)),
            ("in_order", np.arange(N - 1))):
        tables = 1 + order[:B * P].reshape(B, P).astype(np.int32)
        pools = []
        for rows in (k.reshape(B, P * T, -1), v.reshape(B, P * T, -1),
                     ia.index_row(ki)):
            pool = jnp.full((N, T, rows.shape[-1]), jnp.nan).at[0].set(
                1e3 * jax.random.normal(key[3], (T, rows.shape[-1])))
            pools.append(pool.at[tables].set(
                rows.reshape(B, P, T, rows.shape[-1])))
        layouts[name] = (pools, jnp.asarray(tables))
    return k, v, ki, layouts


@pytest.mark.parametrize("tiles", [
    {}, {"_ATTN_ROWS": 32, "_CELL_TILES": 2, "_SCORE_TOKENS": 16}],
    ids=["one_tile", "cells_of_two_tiles"])
def test_the_kernels_read_a_context_through_a_permuted_table(monkeypatch,
                                                              tiles):
    """ISSUE 60: ``index_score`` and ``indexed_chunk_attention`` take the
    pools and the table, and copy a key tile's pages in themselves. Two
    slots whose pages lie scattered over the pool, 40 queries each that end
    at 600 and at 530 of 640 tokens a table (across the edge of the first
    key tile, 512, and page edges; the second tile's tail is the garbage
    page), float32: the scores of every token a query sees and the attention
    over the kernel's own choice against their ``jax.numpy`` forms at 1e-6.
    Once with every query in ONE tile, once in tiles of 8 tokens, two a cell
    (five tiles: the third cell's second tile is padding alone) and score
    tiles of 16."""
    for name, value in tiles.items():
        monkeypatch.setattr(ia, name, value)
    H, topk = 4, 16
    ends = (600, 530)
    k, v, ki, layouts = _scattered(11, len(ends))
    pools, tables = layouts["scattered"]
    key = jax.random.split(jax.random.PRNGKey(12), 3)
    q = jax.random.normal(key[0], (2, 40, H, 16))
    qi = jax.random.normal(key[1], (2, 40, 16, 16))
    w = jax.random.normal(key[2], (2, 40, 16))
    positions = jnp.asarray([np.arange(e - 40, e) for e in ends], jnp.int32)
    with jax.default_matmul_precision("highest"):
        scores = ia.index_scores(qi, w, pools[2], tables, positions, True)
        assert scores.shape == (2, 40, 1024)
        want = ref.score_block(qi, w, ki)
        seen = np.arange(640)[None, None] <= np.asarray(positions)[..., None]
        assert rel(np.where(seen, scores[..., :640], 0.0),
                   np.where(seen, want, 0.0)) <= 1e-6
        tau, bound = ia.select(scores, positions, topk, True)
        take = ia.chosen(scores, positions[..., None], tau[..., None],
                         bound[..., None])
        assert (np.asarray(take).sum(-1) == topk).all()
        got = ia._chunk_attention(q, *pools[:2], tables, positions, scores,
                                  tau, bound, True)
        assert np.isfinite(np.asarray(got)).all()
        assert rel(got, np.asarray(ref.attend_block(
            q, k, v, take[..., :640]))) <= 1e-6


@pytest.mark.parametrize("rows", [40, 1], ids=["chunk", "step"])
def test_the_choice_through_a_permuted_table_is_the_choice_in_order(rows):
    """The op whole, three slots at 600, 530 and 77 tokens: pages scattered
    over the pool or one run in order, the choice (``return_selected``) is
    the same to the token and the output to the bit — where a page lies
    changes no product."""
    ends = (600, 530, 77)
    _, _, _, layouts = _scattered(21, len(ends))
    key = jax.random.split(jax.random.PRNGKey(22), 3)
    q = jax.random.normal(key[0], (3, rows, 4, 16))
    qi = jax.random.normal(key[1], (3, rows, 16, 16))
    w = jax.random.normal(key[2], (3, rows, 16))
    positions = jnp.asarray([np.arange(e - rows, e) for e in ends], jnp.int32)
    sizes = ia.IndexerSizes(indexer_num_heads=16, indexer_head_dim=16,
                            topk=16)
    with jax.default_matmul_precision("highest"):
        (a, chose_a), (b, chose_b) = (ia.indexed_attention(
            q, qi, w, *pools, tables, positions, positions[:, 0], sizes,
            impl="pallas", return_selected=True)
            for pools, tables in layouts.values())
    assert (np.asarray(chose_a).sum(-1) == 16).all()
    np.testing.assert_array_equal(chose_a, chose_b)
    np.testing.assert_array_equal(a, b)


def test_the_contiguous_cache_and_the_pool_hold_one_row(toy):
    """An index key lies in a row of whole lane tiles, the key and then
    zeros, in the serving pool and in the contiguous cache alike: the same
    prompt through ``prefill`` and through a paged chunk leaves the same
    rows."""
    cfg, params, tokens = toy["cfg"], toy["params"], toy["tokens"]
    Di = cfg.indexer.indexer_head_dim
    W = ia.index_width(Di)
    assert (W, ia.index_width(64), ia.index_width(129)) == (128, 128, 256)
    n, T, P = 16, 4, 8
    own = init_caches(cfg, 1, n)
    pool = init_paged_caches(cfg, 1 + P, T, P)
    assert all(c.ik.shape[1:] == (16, W) for c in own)
    assert all(c.ik.shape == (1 + P, T, W) for c in pool)
    table = 1 + jnp.arange(P, dtype=jnp.int32)[::-1]
    with jax.default_matmul_precision("highest"):
        _, own = harness.cached_programs(cfg)[0](params, tokens[:1, :n], own)
        _, pool, *_ = harness.paged_programs(cfg, attn="pallas")[0](
            params, tokens[:1, :n], np.int32(n), np.int32(0), table, table,
            pool, jnp.zeros((1,), jnp.int32), np.int32(-1), np.float32(0),
            np.uint32(0), None)
    for a, b in zip(own, pool):
        rows = np.asarray(b.ik[table[:n // T]]).reshape(n, W)
        np.testing.assert_allclose(np.asarray(a.ik[1])[:n], rows, atol=1e-6)
        assert np.abs(rows[:, :Di]).min() > 0 and not rows[:, Di:].any()


# ------------------------------------------------- the uncached forward


def test_forward_logits_match_the_reference_given_both_choices(toy):
    """With the layers kept apart here; the module's ``toy`` runs them
    stacked under ``scan`` and is held the same way first."""
    assert rel(toy["logits"], ref.forward(
        toy["params"], toy["tokens"], hp_of(toy["cfg"]), toy["routes"],
        toy["selected"])) <= TOL
    cfg = dataclasses.replace(toy["cfg"], scan_layers=False)
    params = seeded(cfg)
    with jax.default_matmul_precision("highest"):
        logits, routes = harness.forward_program(cfg, return_routes=True)(
            params, toy["tokens"])
        again, selected = harness.forward_program(
            cfg, return_selected=True)(params, toy["tokens"])
    np.testing.assert_allclose(again, logits, atol=1e-5)
    assert selected.shape[:3] == (cfg.num_layers, 2, 72)
    want = ref.forward(params, toy["tokens"], hp_of(cfg), np.asarray(routes),
                       np.asarray(selected))
    assert rel(logits, want) <= TOL
    # every query past topk attends exactly topk tokens, none ahead of it
    count = np.asarray(selected).sum(-1)
    np.testing.assert_array_equal(
        count[0, 0], np.minimum(np.arange(72) + 1, cfg.indexer.topk))
    ahead = np.triu(np.ones((72, 72), bool), 1)
    assert not (np.asarray(selected)[..., :72] & ahead).any()


def test_the_selection_is_the_references_own(toy):
    """No choice handed over: the reference's indexer picks the same tokens
    from its own float32 scores, in every layer."""
    _, _, taken = ref.forward_and_choices(
        toy["params"], toy["tokens"], hp_of(toy["cfg"]), toy["routes"])
    np.testing.assert_array_equal(taken, toy["selected"][..., :72])


def test_three_unequal_streams_reach_the_reference(toy):
    cfg, params, tokens = toy["cfg"], toy["params"], toy["tokens"]
    base = jnp.broadcast_to(jnp.arange(72)[None], (2, 72))
    streams = jnp.stack([base, base // 3, 71 - base % 7])
    with jax.default_matmul_precision("highest"):
        logits, routes = harness.forward_program(cfg, return_routes=True)(
            params, tokens, positions=streams)
        _, selected = harness.forward_program(cfg, return_selected=True)(
            params, tokens, positions=streams)
    want = ref.forward(params, tokens, hp_of(cfg), np.asarray(routes),
                       np.asarray(selected), streams)
    assert rel(logits, want) <= TOL
    assert rel(toy["logits"], want) > 100 * TOL  # the streams matter


# ---------------------------------------------------------- named faults


def _no_norm(m):
    m.setattr(ref, "layer_norm", lambda x, scale, bias, eps: x)


def _no_relu(m):
    m.setattr(ref, "relu", lambda x: x)


def _a_heads_weight_dropped(m):
    true = ref.head_weights
    m.setattr(ref, "head_weights",
              lambda *a: true(*a).at[..., 0].set(0.0))


def _topk_off_by_one(m):
    m.setattr(ref, "topk_of", lambda hp: int(hp["sa_config"]["topk"]) - 1)


def _selection_by_block(m):
    """Blocks of 4 tokens by their best score, the 4 best blocks."""
    def by_block(scores, first, topk):
        b, r, s = scores.shape
        t = first + jnp.arange(r)[:, None]
        seen = jnp.arange(s)[None, :] <= t
        masked = jnp.where(seen[None], scores, -jnp.inf)
        blocks = jnp.pad(masked, ((0, 0), (0, 0), (0, -s % 4)),
                         constant_values=-jnp.inf).reshape(b, r, -1, 4)
        best = jax.lax.top_k(blocks.max(-1), min(topk // 4,
                                                 blocks.shape[2]))[1]
        taken = jnp.zeros(blocks.shape[:3], bool).at[
            jnp.arange(b)[:, None, None], jnp.arange(r)[None, :, None],
            best].set(True)
        return jnp.logical_and(jnp.repeat(taken, 4, axis=-1)[..., :s],
                               seen[None])
    m.setattr(ref, "select_block", by_block)


def _bf16_scores(m):
    true = ref.score_block
    low = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    m.setattr(ref, "score_block",
              lambda qi, w, ki: low(true(low(qi), low(w), low(ki))))


def _experts_not_renormalized(m):
    true = ref.token_weights
    m.setattr(ref, "token_weights",
              lambda p, r, top_k, renorm: true(p, r, top_k, False))


INDEXER_FAULTS = [_no_norm, _no_relu, _a_heads_weight_dropped,
                  _topk_off_by_one, _selection_by_block, _bf16_scores]


@pytest.mark.parametrize("fault", [None] + INDEXER_FAULTS,
                         ids=lambda f: f.__name__ if f else "sound")
def test_a_fault_in_the_indexer_is_refused(toy, monkeypatch, fault):
    """Given the routes and NOT the tokens: the reference picks its own, so
    whatever its indexer does wrong moves its choice and its logits."""
    if fault:
        fault(monkeypatch)
    want = ref.forward(toy["params"], toy["tokens"], hp_of(toy["cfg"]),
                       toy["routes"])
    err = rel(toy["logits"], want)
    assert (err > TOL) if fault else (err <= TOL), err


def test_experts_left_unrenormalized_are_refused(toy, monkeypatch):
    _experts_not_renormalized(monkeypatch)
    want = ref.forward(toy["params"], toy["tokens"], hp_of(toy["cfg"]),
                       toy["routes"], toy["selected"])
    assert rel(toy["logits"], want) > TOL


def test_one_position_stream_for_three_is_refused(toy, monkeypatch):
    cfg, params, tokens = toy["cfg"], toy["params"], toy["tokens"]
    base = jnp.broadcast_to(jnp.arange(72)[None], (2, 72))
    streams = jnp.stack([base, base // 3, 71 - base % 7])
    with jax.default_matmul_precision("highest"):
        logits, routes = harness.forward_program(cfg, return_routes=True)(
            params, tokens, positions=streams)
        _, selected = harness.forward_program(cfg, return_selected=True)(
            params, tokens, positions=streams)
    true = ref.stream_positions
    monkeypatch.setattr(ref, "stream_positions", lambda p, b, s: jnp.stack(
        [true(p, b, s)[0]] * 3))
    want = ref.forward(params, tokens, hp_of(cfg), np.asarray(routes),
                       np.asarray(selected), streams)
    assert rel(logits, want) > TOL


# ------------------------------------- contexts of at most topk tokens


def test_a_context_within_topk_is_the_attention_kind(toy):
    cfg, params = toy["cfg"], toy["params"]
    dense = keye_debug(layer_kinds=("attention",) * cfg.num_layers)
    tokens = toy["tokens"][:, :cfg.indexer.topk]
    whole = {c: harness.forward_program(c) for c in (cfg, dense)}
    (fill, step), (dense_fill, dense_step) = (
        harness.cached_programs(c) for c in (cfg, dense))
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(whole[cfg](params, tokens),
                                   whole[dense](params, tokens), atol=2e-6)
        # and through the contiguous cache: a prompt, then a step
        ours, theirs = (init_caches(c, 2, 16) for c in (cfg, dense))
        a, ours = fill(params, tokens[:, :15], ours)
        b, theirs = dense_fill(params, tokens[:, :15], theirs)
        np.testing.assert_allclose(a, b, atol=2e-6)
        a, _ = step(params, tokens[:, 15:], ours)
        b, _ = dense_step(params, tokens[:, 15:], theirs)
        np.testing.assert_allclose(a, b, atol=2e-6)
        # one token more and the two part
        longer = toy["tokens"][:, :40]
        assert np.abs(np.asarray(whole[cfg](params, longer)
                                 - whole[dense](params, longer))).max() > 1e-3


# ------------------------------------------------ the contiguous cache


@pytest.mark.parametrize("n", [9, 41])
def test_prefill_and_decode_step_match_the_reference(toy, n):
    """The cached forward hands no choice over; at float32 it makes the
    uncached forward's, which the reference is given."""
    cfg, params, tokens = toy["cfg"], toy["params"], toy["tokens"]
    want = ref.forward(params, tokens, hp_of(cfg), toy["routes"],
                       toy["selected"])
    assert rel(harness.cached_logits(cfg, params, tokens, n),
               want[:, n - 1:]) <= TOL


# ------------------------------------------------------ the paged programs


def _paged(request):
    """Two prompts through the paged programs (``harness.paged_drive``).
    Slot 1 takes a 53-token prompt in chunks of 16 (past topk, over three
    chunk boundaries, ending inside a chunk); slot 2 then a 33-token prompt
    (a page's first token last) whose chunks take slot 1's decode row along
    (the fused turn); then plain steps of both. Slots 0 and 3 hold no
    sequence, and every page no table names is FILLED WITH NaN in every
    layer's three arrays, as a released page would be: whatever read one
    would show."""
    cfg = keye_debug()
    slots, T, P = 4, 4, 24
    return dict(
        cfg=cfg, params=seeded(cfg), impl=request.param,
        tokens=jax.random.randint(jax.random.PRNGKey(9), (2, 80), 0,
                                  cfg.vocab_size),
        caches=init_paged_caches(cfg, slots * P + 1 + 8, T, P),
        tables=harness.slot_tables(slots, P, (1, 2)),
        lengths={1: 53, 2: 33}, chunk=16, steps=6, moe_info=True,
        selected=True)


paged_run = harness.paged_fixture(_paged, impls=["pallas"])


@pytest.mark.parametrize("slot", [1, 2])
def test_paged_chunks_steps_and_fused_turns_match_the_reference(paged_run,
                                                                slot):
    run = paged_run
    cfg, n, end = run["cfg"], run["n"][slot], run["cursor"][slot]
    seq = run["tokens"][run["row"][slot]][None, :end]
    routes = np.concatenate(run["routes"][slot], 1)[:, None]
    # a program's choice spans its table's context: the sequence's part
    picked = np.concatenate(
        [p[..., :end] for p in run["picked"][slot]], 1)[:, None]
    assert routes.shape[2] == end == picked.shape[2]
    np.testing.assert_array_equal(
        picked.sum(-1)[0, 0], np.minimum(np.arange(end) + 1,
                                         cfg.indexer.topk))
    got = harness.slot_logits(run, slot)
    want = ref.forward(run["params"], seq, hp_of(cfg), routes, picked)[0]
    assert rel(got, want[n - 1:]) <= TOL


def test_the_paged_programs_left_the_poisoned_pages_alone(paged_run):
    assert all(set(harness.pools(c)) == {"k", "v", "ik"}
               for c in paged_run["caches"])
    harness.poisoned_pages_left_alone(paged_run)


# ------------------------------------------------------------ the scheduler


def near_the_references_best(cfg, params, prompt, out):
    return harness.near_the_references_best(
        lambda seq: ref.forward(params, seq, hp_of(cfg)), prompt, out)


def test_the_scheduler_serves_the_kind_and_counts_its_work():
    cfg = keye_debug()
    params = seeded(cfg)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(9), (4, 80), 0,
                                           cfg.vocab_size))
    new = 8
    prompts = [tokens[i, :n].tolist() for i, n in enumerate((70, 9, 33, 24))]
    served, stats = harness.served(
        cfg, params, prompts, new, slots=3, prefill_chunk=16, arena_len=96,
        page_tokens=4, prefix_cache=False)
    for prompt, out in zip(prompts, served):
        assert len(out) == new
        assert near_the_references_best(cfg, params, prompt, out)
    # every query row of a sequence, prompt and answer but the last token
    rows = [c for p in prompts for c in range(len(p) + new - 1)]
    steps = [len(p) + i for p in prompts for i in range(new - 1)]
    L, k = cfg.num_layers, cfg.indexer.topk
    assert stats["indexed_tokens_context"] == L * sum(c + 1 for c in rows)
    assert stats["indexed_tokens_scored"] == stats["indexed_tokens_context"]
    assert stats["indexed_tokens_attended"] == L * sum(
        min(c + 1, k) for c in rows)
    assert stats["indexed_step_tokens_attended"] == L * sum(
        min(c + 1, k) for c in steps)
    assert stats["indexed_step_tokens_context"] == L * sum(
        c + 1 for c in steps)
    assert stats["fused_turns"] > 0 and stats["pages_in_use"] == 0
    assert "sparse_rows" not in stats  # another kind's
    # the selection's walk: a toy table of one segment is walked whole, a
    # tile of 8 rows a call: a chunk's 16 rows are two, the 3 slots one,
    # in a plain step and in every chunk's program, rows live or not
    calls = 3 * stats["prefill_chunks"] + (
        stats["decode_steps"] - stats["fused_turns"])
    lanes = ia.context_tokens(24, 4)
    assert stats["indexed_select_lanes_table"] == L * 8 * lanes * calls
    assert stats["indexed_select_lanes"] == stats["indexed_select_lanes_table"]


@pytest.mark.parametrize("qk,cursors,idle", [
    (512, [0], 0), (512, [16384], 0), (512, [49152], 0),
    (1, [20000, 45000, 17000, 30000, 41000], 3), (1, [2047], 7), (1, [], 8)])
def test_the_counters_mirror_the_selections_walk(qk, cursors, idle):
    """``indexed_select_lanes``: rows x lanes the kernel's passes walk, from
    the function ``select`` prefetches its reach from, over the positions
    the program hands it (a chunk's tokens; the slots, a row that is not
    live at 0); ``indexed_select_lanes_table``: rows x the table's lanes,
    what passes over the whole table walked. At the cell's sizes."""
    from ray_tpu.models import llama_debug
    from ray_tpu.serve._private.work import Work

    sizes = dict(slots=8, page_tokens=16, pages_per_slot=3104, itemsize=2,
                 lane="reference")
    cfg = keye_debug()
    work = Work(cfg, **sizes)
    work.record(qk, cursors, idle, real=qk - 12 if qk > 1 else None)
    got = work.stats()
    positions = (cursors[0] + np.arange(qk) if qk > 1
                 else np.array(cursors + [0] * idle))
    rows = -(-len(positions) // 8) * 8
    walked = np.asarray(ia.select_lanes(jnp.asarray(positions), 49664))
    assert got["indexed_select_lanes"] == cfg.num_layers * 8 * walked.sum()
    assert got["indexed_select_lanes_table"] == cfg.num_layers * rows * 49664
    assert 0 < got["indexed_select_lanes"] <= cfg.num_layers * rows * (
        49664 + ia._SELECT_LANES)
    if qk == 1:  # the step's one tile walks up to its last live row
        W = ia._SELECT_LANES
        assert walked.tolist() == [(max(cursors, default=0) // W + 1) * W]
    other = Work(llama_debug(), **sizes)
    other.record(qk, cursors, idle)
    assert not [key for key in other.stats() if key.startswith("indexed_")]


def test_a_spliced_prefix_brings_its_index_keys_along():
    """The prefix cache serves the kind: the second request splices the
    first's pages — K, V and the index keys under one table — and answers
    as a scheduler without the cache does."""
    cfg = keye_debug()
    params = seeded(cfg)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (96,), 0,
                                           cfg.vocab_size)).tolist()
    first, second = tokens[:64], tokens[:48] + tokens[70:90]
    answers = {}
    for cached in (True, False):
        answers[cached], stats = harness.served(
            cfg, params, (first, second), 6, together=False, slots=2,
            prefill_chunk=16, arena_len=96, page_tokens=4,
            prefix_cache=cached)
        if cached:
            assert stats["prefix_hits"] == 1
            assert stats["prefix_hit_tokens"] >= 44
    assert answers[True] == answers[False]
    assert near_the_references_best(cfg, params, second, answers[True][1])


def test_the_scheduler_refuses_what_the_kind_cannot_have():
    from ray_tpu.serve._private.continuous import ContinuousScheduler

    cfg = keye_debug()
    params = init_params(cfg, jax.random.PRNGKey(0))
    kw = dict(slots=2, prefill_chunk=16, arena_len=64, page_tokens=4,
              attn="reference")
    with pytest.raises(ValueError, match="speculative decoding cannot serve "
                                         "a model with 'indexed_attention'"):
        ContinuousScheduler(cfg, params, drafter=object(), **kw)
    sched = ContinuousScheduler(cfg, params, prefix_cache=True, **kw)
    try:
        with pytest.raises(ValueError, match="not its index keys"):
            sched.export_prefix([1, 2, 3, 4])
    finally:
        sched.shutdown()
