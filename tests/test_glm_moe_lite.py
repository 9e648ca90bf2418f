"""The 'latent_attention' kind and the feed-forward pattern GLM-4.7-Flash
forces (a leading dense layer, then experts behind a sigmoid router chosen
by score + bias, renormalized and scaled, beside a shared expert) against
the plain reference, ``perfbench/reference/glm_moe_lite.py``: float32 on the
CPU at a toy size.

The reference is UNABSORBED (every token's keys and values rebuilt from its
latent); the program's cached forwards attend the latents with the
up-projections absorbed. The system is held to it at 1e-4 of the largest
logit through every forward: without a cache, the contiguous cache (prefill
then decode), and the paged chunk, step and fused turn, by the ``jax.numpy``
path and by the kernel interpreted. Named faults planted in the reference
are refused by the same comparison.
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

from perfbench.reference import glm_moe_lite as ref  # noqa: E402
from ray_tpu.models.decode import (init_caches,  # noqa: E402
                                   init_paged_caches, init_slot_caches)
from ray_tpu.models.presets import (glm_moe_lite_debug,  # noqa: E402
                                    moe_debug)
from ray_tpu.models.transformer import (LATENT, LAYER_KINDS,  # noqa: E402
                                        forward, init_params, layer_params,
                                        logical_axes)
from ray_tpu.ops import moe  # noqa: E402
from ray_tpu.ops.latent_attention import (join, latent_attention,  # noqa: E402
                                          latent_tiles, pool_width)
from tests import model_harness as harness  # noqa: E402
from tests.model_harness import rel  # noqa: E402

TOL = 1e-4


def hp_of(cfg):
    """The reference's configuration object, keyed as the source keys it."""
    return {"num_hidden_layers": cfg.num_layers,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "qk_nope_head_dim": cfg.latent_nope_dim,
            "kv_lora_rank": cfg.latent_kv_rank,
            "first_k_dense_replace": cfg.moe_dense_layers,
            "num_experts_per_tok": cfg.moe_top_k,
            "norm_topk_prob": cfg.moe_renormalize,
            "routed_scaling_factor": cfg.moe_routed_scale,
            "n_shared_experts": cfg.moe_shared_experts}


# weights with every norm's scale away from 1 (a norm left out, or one scale
# taken for another, then shows)
seeded = functools.partial(
    harness.seeded, stir=("scale", "q_a_norm", "kv_norm"), by=0.3)



@pytest.fixture(scope="module")
def toy():
    cfg = glm_moe_lite_debug()
    params = seeded(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 72), 0,
                                cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        logits, routes = forward(cfg, params, tokens, return_routes=True)
    return {"cfg": cfg, "params": params, "tokens": tokens,
            "logits": np.asarray(logits), "routes": np.asarray(routes)}


def test_the_preset_has_what_the_architecture_forces():
    cfg = glm_moe_lite_debug()
    assert LATENT in LAYER_KINDS and set(cfg.kinds) == {LATENT}
    assert cfg.head_dim == cfg.latent_nope_dim + cfg.latent_rope_dim
    assert [cfg.mlp_of(i) for i in range(3)] == ["swiglu", "moe", "moe"]
    assert (cfg.lead_layers, cfg.expert_layers, cfg.period) == (1, 2, 1)
    assert cfg.mlp_width("swiglu") == 96 and cfg.mlp_width("moe") == 48
    params = init_params(cfg, jax.random.PRNGKey(0))
    assert set(params["blocks"]) == {"lead", "body"}
    dense, expert = (layer_params(cfg, params, i)["mlp"] for i in (0, 1))
    assert set(dense) == {"w_gate", "w_up", "w_down"}
    assert dense["w_gate"].shape == (64, 96)
    assert set(expert) == {"w_router", "e_bias", "w_gate", "w_up", "w_down",
                           "ws_gate", "ws_up", "ws_down"}
    assert expert["e_bias"].dtype == jnp.float32
    assert float(jnp.abs(expert["e_bias"]).min()) > 0
    attn = layer_params(cfg, params, 2)["attn"]
    assert {k: v.shape for k, v in attn.items()} == {
        "wq_a": (64, 48), "q_a_norm": (48,), "wq_b": (48, 4, 32),
        "wkv_a": (64, 40), "kv_norm": (32,), "wkv_b": (32, 4, 56),
        "wo": (4, 32, 64)}
    # the axes' tree is the parameters', leading layers and all
    axes = logical_axes(cfg)
    assert jax.tree.structure(
        jax.tree.map(lambda a: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes,
                     is_leaf=lambda a: isinstance(a, tuple)))
    # what a token leaves in the cache: a latent and one rotated key, in a
    # row of whole lane tiles
    assert init_caches(cfg, 1, 16)[0].ckr.shape[-1] == pool_width(32, 8)
    assert (pool_width(32, 8), pool_width(512, 64)) == (128, 640)
    with pytest.raises(ValueError, match="moe_dense_layers"):
        glm_moe_lite_debug(moe_dense_layers=3)
    with pytest.raises(ValueError, match="latent_q_rank"):
        glm_moe_lite_debug(latent_rope_dim=0)


def test_the_softmax_router_did_not_move_by_a_bit():
    """``route`` in its softmax branch is the arithmetic ``moe_layer`` had:
    the probabilities' top k, divided by their sum clipped at 1e-9."""
    logits = jax.random.normal(jax.random.PRNGKey(3), (40, 8), jnp.float32)
    for renorm in (True, False):
        probs, idx, vals = moe.route(logits, 3, renorm)
        want_p = jax.nn.softmax(logits, axis=-1)
        want_v, want_i = jax.lax.top_k(want_p, 3)
        if renorm:
            want_v = want_v / jnp.maximum(want_v.sum(-1, keepdims=True), 1e-9)
        for got, want in ((probs, want_p), (idx, want_i), (vals, want_v)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    cfg = moe_debug()
    assert cfg.moe_scoring == "softmax" and cfg.lead_layers == 0
    assert "e_bias" not in jax.tree_util.keystr(
        jax.tree_util.tree_flatten_with_path(
            init_params(cfg, jax.random.PRNGKey(0)))[0][0][0])
    with pytest.raises(ValueError, match="unknown router scoring"):
        moe.route(logits, 3, True, "tanh")
    # nor did a seed's weights: the four matrices come from the four keys
    # they always came from, whatever else the layer is given
    key = jax.random.PRNGKey(11)
    plain = moe.init_moe_params(key, 16, 8, 4)
    more = moe.init_moe_params(key, 16, 8, 4, choice_bias=True, shared_dim=8)
    init = jax.nn.initializers.normal(0.02, jnp.float32)
    want = init(jax.random.split(key, 4)[1], (4, 16, 8))
    for p in (plain, more):
        np.testing.assert_array_equal(np.asarray(p["w_gate"]),
                                      np.asarray(want))
    assert set(more) - set(plain) == {"e_bias", "ws_gate", "ws_up",
                                      "ws_down"}


def test_the_bias_chooses_and_does_not_weigh():
    logits = jax.random.normal(jax.random.PRNGKey(3), (64, 8), jnp.float32)
    bias = jnp.asarray([0.5, -0.5, 0.2, 0, 0, -0.2, 0.3, 0], jnp.float32)
    scores, idx, vals = moe.route(logits, 3, True, "sigmoid", bias, 1.8)
    s = np.asarray(jax.nn.sigmoid(logits))
    np.testing.assert_array_equal(np.asarray(scores), s)
    want = np.argsort(-(s + np.asarray(bias)), axis=-1, kind="stable")[:, :3]
    np.testing.assert_array_equal(np.asarray(idx), want)
    w = np.take_along_axis(s, want, -1)
    np.testing.assert_allclose(np.asarray(vals),
                               1.8 * w / w.sum(-1, keepdims=True), rtol=1e-6)
    plain = np.asarray(moe.route(logits, 3, True, "sigmoid")[1])
    assert (np.sort(plain) != np.sort(want)).any(-1).mean() > 0.2


def test_forward_logits_match_the_reference(toy):
    """Given the routes, and left to its own: at float32 the reference's
    biased choice is the program's."""
    cfg = toy["cfg"]
    given = ref.forward(toy["params"], toy["tokens"], hp_of(cfg),
                        toy["routes"])
    assert rel(toy["logits"], given) <= TOL
    own, scores = ref.forward_and_router(toy["params"], toy["tokens"],
                                         hp_of(cfg))
    assert rel(toy["logits"], own) <= TOL
    assert toy["routes"].shape == (cfg.expert_layers, 2, 72, cfg.moe_top_k)
    # and the bias is large enough to matter at seeded weights
    unbiased = np.asarray(jax.lax.top_k(scores, cfg.moe_top_k)[1])
    assert (np.sort(unbiased) != np.sort(toy["routes"])).any(-1).mean() > 0.2


def test_the_layouts_agree(toy):
    """Layers kept apart (``scan_layers`` False) and stacked, leading layer
    apart, are one model; the reference reads both."""
    cfg = dataclasses.replace(toy["cfg"], scan_layers=False)
    flat = {str(i): layer_params(toy["cfg"], toy["params"], i)
            for i in range(cfg.num_layers)}
    params = {**toy["params"], "blocks": flat}
    with jax.default_matmul_precision("highest"):
        logits = forward(cfg, params, toy["tokens"])
    assert rel(logits, toy["logits"]) <= 1e-6
    want = ref.forward(params, toy["tokens"], hp_of(cfg), toy["routes"])
    assert rel(toy["logits"], want) <= TOL


# ------------------------------------------------------------- named faults


def _hp(**changed):
    return lambda m, hp: {**hp, **changed}


def _bias_dropped_from_the_choice(m, hp):
    true = ref.token_weights
    m.setattr(ref, "token_weights",
              lambda s, b, r, hp: true(s, jnp.zeros_like(b), r, hp))
    return hp


def _bias_left_in_the_weights(m, hp):
    true = ref.token_weights
    m.setattr(ref, "token_weights", lambda s, b, r, hp: true(s + b, 0 * b,
                                                             r, hp))
    return hp


def _softmax_for_sigmoid(m, hp):
    m.setattr(jax.nn, "sigmoid", lambda x: jax.nn.softmax(x, axis=-1))
    return hp


def _rotated_key_not_shared(m, hp):
    """Every head but the first sees another rotated key."""
    true, n = ref.attend, hp["qk_nope_head_dim"]

    def attend(q, k, v):
        own = jnp.roll(k[:, :, 1:, n:], 1, axis=-1)
        return true(q, k.at[:, :, 1:, n:].set(own), v)

    m.setattr(ref, "attend", attend)
    return hp


def _key_cached_before_rope(m, hp):
    """What a cache filled before the rotation would hold: kr unturned."""
    true = ref.rotate
    m.setattr(ref, "rotate",
              lambda x, theta: x if x.shape[2] == 1 else true(x, theta))
    return hp


def _latent_cached_before_its_norm(m, hp):
    true, rank = ref.rms_norm, hp["kv_lora_rank"]
    m.setattr(ref, "rms_norm", lambda x, scale, eps: x
              if scale.shape[-1] == rank else true(x, scale, eps))
    return hp


def _scaled_by_the_unrotated_part(m, hp):
    """1/sqrt(qk_nope_head_dim) in place of 1/sqrt(nope + rope)."""
    true, n = ref.attend, hp["qk_nope_head_dim"]
    m.setattr(ref, "attend", lambda q, k, v: true(
        q * jnp.sqrt(q.shape[-1] / n), k, v))
    return hp


FAULTS = {
    "bias_dropped_from_the_choice": (_bias_dropped_from_the_choice, False),
    "bias_left_in_the_weights": (_bias_left_in_the_weights, True),
    "no_renormalization": (_hp(norm_topk_prob=False), True),
    "scaling_factor_1": (_hp(routed_scaling_factor=1.0), True),
    "shared_expert_left_out": (_hp(n_shared_experts=0), True),
    "softmax_for_sigmoid": (_softmax_for_sigmoid, True),
    "rotated_key_not_shared": (_rotated_key_not_shared, True),
    "key_cached_before_rope": (_key_cached_before_rope, True),
    "latent_cached_before_its_norm": (_latent_cached_before_its_norm, True),
    "scaled_by_the_unrotated_part": (_scaled_by_the_unrotated_part, True),
}


@pytest.mark.parametrize("fault", [None] + list(FAULTS))
def test_a_named_fault_is_refused(toy, monkeypatch, fault):
    """Each fault planted in the reference moves its logits past the limit
    the sound reference stays within — given the program's routes, but for
    the fault that IS the choice, where the reference makes its own."""
    hp, given = hp_of(toy["cfg"]), True
    if fault:
        plant, given = FAULTS[fault]
        hp = plant(monkeypatch, hp)
    want = ref.forward(toy["params"], toy["tokens"], hp,
                       toy["routes"] if given else None)
    err = rel(toy["logits"], want)
    assert (err > TOL) if fault else (err <= TOL), err


def test_the_dense_layer_cannot_be_routed(toy):
    """A dense layer has no router to route by: a reference told that layer
    0 is an expert layer finds none, and a program built so has another
    tree."""
    cfg = toy["cfg"]
    flat = {**toy["params"], "blocks": {
        str(i): layer_params(cfg, toy["params"], i)
        for i in range(cfg.num_layers)}}
    with pytest.raises(KeyError, match="w_router"):
        ref.forward(flat, toy["tokens"],
                    {**hp_of(cfg), "first_k_dense_replace": 0})
    routed = dataclasses.replace(cfg, moe_dense_layers=0)
    assert routed.mlp_of(0) == "moe"
    params = jax.eval_shape(lambda: init_params(routed,
                                                jax.random.PRNGKey(0)))
    assert "w_router" in params["blocks"]["mlp"]
    assert "w_router" not in toy["params"]["blocks"]["lead"]["mlp"]


# ------------------------------------------------------ the contiguous cache


@pytest.mark.parametrize("n", [9, 41])
def test_prefill_and_decode_step_match_the_reference(toy, n):
    """The cached forward attends the latents ABSORBED; the reference
    rebuilds keys and values."""
    cfg, params, tokens = toy["cfg"], toy["params"], toy["tokens"]
    want = ref.forward(params, tokens, hp_of(cfg), toy["routes"])
    assert rel(harness.cached_logits(cfg, params, tokens, n),
               want[:, n - 1:]) <= TOL


# ------------------------------------------------------------- the kernel


@pytest.mark.parametrize("S,K,H,lengths", [
    (4, 1, 4, [37, -1, 0, 95]),          # a step: idle row, first token
    (1, 16, 4, [0]), (1, 16, 4, [41]),   # a chunk, cold and behind a prefix
    (1, 70, 20, [30]),                   # rows past one matmul's: sub-tiles
], ids=["step", "chunk_cold", "chunk_warm", "chunk_20_heads"])
def test_the_kernel_is_the_plain_path(S, K, H, lengths):
    """``impl='pallas'`` (interpreted here) against the ``jax.numpy`` path:
    pages through a shuffled table, the pages no table names filled with
    NaN."""
    args, _ = _kernel_case(S, K, H, lengths, 16)
    assert args[2].shape == (1 + S * 16 + 5, 8, 128)
    run = lambda impl: jax.jit(functools.partial(
        latent_attention, sm_scale=0.17, impl=impl))(*args)
    with jax.default_matmul_precision("highest"):
        want, got = run("reference"), run("pallas")
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    for s, n in enumerate(lengths):
        if n + K <= 0:
            assert not np.asarray(got[s]).any()
    with pytest.raises(ValueError, match="unknown latent attention impl"):
        latent_attention(*args, sm_scale=0.17, impl="flash")


def _kernel_case(S, K, H, lengths, P, sm_scale=0.17, T=8):
    """Seeded queries and a pool of [1 + S * P + 5] pages of ``T`` tokens
    (rank 32, rope 8: rows of 128 lanes) through a shuffled table whose
    entries past a slot's allocation name the garbage page; every page no
    table names is NaN, as a released one would be."""
    rank, rope = 32, 8
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    n_pages = 1 + S * P + 5
    c = jax.random.normal(keys[0], (n_pages, T, rank), jnp.float32)
    kr = jax.random.normal(keys[1], (n_pages, T, rope), jnp.float32)
    perm = np.random.RandomState(0).permutation(np.arange(1, 1 + S * P))
    tables = perm.reshape(S, P).astype(np.int32)
    for s, n in enumerate(lengths):
        tables[s, max(-(-(n + K) // T), 0):] = 0
    poisoned = np.setdiff1d(np.arange(1, n_pages), np.unique(tables))
    pool = join(c, kr).at[poisoned].set(jnp.nan)
    q_c = jax.random.normal(keys[2], (S, K, H, rank), jnp.float32)
    q_r = jax.random.normal(keys[3], (S, K, H, rope), jnp.float32)
    return (q_c, q_r, pool, jnp.asarray(tables),
            jnp.asarray(lengths, jnp.int32)), poisoned


# a table of 160 pages of 8: blocks of 512 tokens, the third cut short by
# the table's end; 20 heads, so 64 tokens are five matmuls of 256 rows
@pytest.mark.parametrize("K,cursor,cell_vmem", [
    (70, 0, None),        # cold: the one block reaches into the cell
    (70, 300, None),      # the chunk's first position mid-block
    (70, 510, None),      # its second token is the block's last: no block
    #                       is free, and block 0 unmasked would show row 0
    #                       the chunk's own next token
    (70, 511, None),      # on a block's last token: block 0 is free
    (70, 512, None),      # on a block's first token
    (70, 1090, None),     # two free blocks, the table's short last one
    (200, 400, 16 << 20),  # K no multiple of the cell: two cells of 128
    #                        tokens, the second's last groups without a row
    (330, 0, 16 << 20),   # three cells, cold: a cell's free blocks are
    #                       the tokens of the cells before it
], ids=["cold", "mid_block", "no_block_free", "block_last", "block_first",
        "table_end", "cells_k200", "cells_cold"])
def test_a_cell_walks_its_blocks_once_and_masks_where_it_must(
        monkeypatch, K, cursor, cell_vmem):
    """One cell holds several ``_SUB_ROWS`` groups (70 tokens x 20 heads:
    128 tokens a cell, ten groups, two in flight), and where the cell's
    VMEM is made small, several cells a call: against ``_latent_reference``
    at every place a chunk's first position can lie in a block. The first
    masked block is exactly the first that needs a mask: one block earlier
    and a row would be refused a token it may see, one later and it would
    attend the chunk's own later tokens or the garbage page's."""
    from ray_tpu.ops import latent_attention as la

    H, P = 20, 160
    if cell_vmem is not None:
        monkeypatch.setattr(la, "_CELL_VMEM", cell_vmem)
    assert la.latent_tiles(K, H, 8, P, 128, 4) == (64, 128)
    args, poisoned = _kernel_case(1, K, H, [cursor], P)
    with jax.default_matmul_precision("highest"):
        want = la._latent_reference(*args, 0.17)
        # the jitted wrapper read ``_CELL_VMEM`` when it was traced
        got = la._latent_pallas.__wrapped__(*args, 0.17, True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert np.isnan(np.asarray(args[2])[poisoned]).all()


def _step_as_it_was(q_c, q_r, pool, tables, lengths, sm_scale):
    """K = 1 through the kernel AS PR 55 WROTE IT (its body and its call,
    cut to the one tile of H rows a slot the step is), interpreted: what
    ``test_the_step_is_bitwise_what_it_was`` holds the kernel to."""
    from jax import lax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from ray_tpu.ops.paged_attention import NEG_INF, tile_sizes

    S, _, H, rank = q_c.shape
    T, W, P = pool.shape[1], pool.shape[2], tables.shape[1]
    B, _ = tile_sizes(1, H, T, P, W * pool.dtype.itemsize // 2)
    BT = B * T

    def kernel(lengths_ref, tables_ref, order_ref, n_live_ref, q_ref,
               pool_ref, o_ref, buf_ref, sems, first_buf, m_scr, l_scr,
               acc_scr):
        c, n_live = pl.program_id(0), n_live_ref[0]
        s = order_ref[c]

        def block_copies(s, b, buf, wait=False):
            for i in range(B):
                j = b * B + i
                pid = 0 if wait else jnp.where(
                    j < P, tables_ref[s, jnp.minimum(j, P - 1)], 0)
                cp = pltpu.make_async_copy(
                    pool_ref.at[pid], buf_ref.at[buf, pl.ds(i * T, T)],
                    sems.at[buf])
                cp.wait() if wait else cp.start()

        @pl.when(c == 0)
        def _():
            first_buf[0] = 0

            @pl.when(n_live > 0)
            def _():
                block_copies(s, 0, 0)

        base = first_buf[0]
        nb = jnp.clip(lax.div(lengths_ref[s] + 1 + BT - 1, jnp.int32(BT)),
                      1, -(-P // B))
        nb = jnp.where(c < n_live, nb, 0)
        s_next = order_ref[jnp.minimum(c + 1, pl.num_programs(0) - 1)]
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

        def body(b, _):
            buf = lax.rem(base + b, 2)
            more = b + 1 < nb

            @pl.when(jnp.logical_or(more, c + 1 < n_live))
            def _():
                block_copies(jnp.where(more, s, s_next),
                             jnp.where(more, b + 1, 0), 1 - buf)

            block_copies(s, b, buf, wait=True)
            kpos = b * BT + lax.broadcasted_iota(jnp.int32, (1, BT), 1)
            s_ = lax.dot_general(
                q_ref[0, 0], buf_ref[buf], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            latents = buf_ref[buf, :, :rank]
            row_pos = lengths_ref[s] + lax.broadcasted_iota(
                jnp.int32, (H, 1), 0) // H
            s_ = jnp.where(kpos <= row_pos, s_ * sm_scale, NEG_INF)
            m = m_scr[...]
            m_new = jnp.maximum(m, jnp.max(s_, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            pr = jnp.exp(s_ - m_new)
            l_scr[...] = (l_scr[...] * alpha
                          + jnp.sum(pr, axis=-1, keepdims=True))
            acc_scr[...] = acc_scr[...] * alpha + lax.dot_general(
                pr.astype(latents.dtype), latents, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[...] = m_new

        lax.fori_loop(0, nb, body, None)
        first_buf[0] = lax.rem(base + nb, 2)
        l = l_scr[...]
        o_ref[0, 0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)

    live = lengths + 1 > 0
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    cell = lambda c, *refs: (refs[2][c], 0, 0, 0)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(S,),
            in_specs=[pl.BlockSpec((1, 1, H, W), cell),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, 1, H, rank), cell),
            scratch_shapes=[pltpu.VMEM((2, BT, W), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)] + [
                pltpu.VMEM((H, n), jnp.float32) for n in (1, 1, rank)]),
        out_shape=jax.ShapeDtypeStruct((S, 1, H, rank), q_c.dtype),
        interpret=True,
    )(lengths, tables, order, jnp.sum(live, dtype=jnp.int32)[None],
      join(q_c, q_r).astype(pool.dtype), pool)
    return out


def test_the_step_is_bitwise_what_it_was():
    """K = 1 is one cell of H rows a slot, every block masked, the scale on
    the scores: PR 55's arithmetic, bit for bit (a scale that is no power
    of two: folded into the query it would show), slots idle, at their
    first token and past the table's short last block."""
    args, _ = _kernel_case(4, 1, 4, [37, -1, 0, 1200], 160)
    assert latent_tiles(1, 4, 8, 160, 128, 4) == (64, 1)
    with jax.default_matmul_precision("highest"):
        got = latent_attention(*args, sm_scale=0.17, impl="pallas")
        want = jax.jit(functools.partial(_step_as_it_was, sm_scale=0.17))(
            *args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.asarray(got[0]).any() and not np.asarray(got[1]).any()


def test_the_tiles_are_the_paged_kernels_rule():
    # the cell's shapes: 512 tokens a block, the paged kernel's rule; the
    # step one cell of a slot's 20 rows, as that rule has it too
    assert latent_tiles(1, 20, 16, 4128, 640, 2) == (32, 1)
    # a chunk's 512 queries are ONE cell (10,240 rows, forty matmuls of 256
    # a block; the paged rule's 64 tokens made eight cells, each a walk of
    # the slot's context), and the check prompt's 4,113 nine cells of 512
    assert latent_tiles(512, 20, 16, 4128, 640, 2) == (32, 512)
    assert latent_tiles(4113, 20, 16, 4128, 640, 2) == (32, 512)
    # whole matmuls of 256 rows (64 tokens of 20 heads); under that one
    assert latent_tiles(70, 20, 16, 4128, 640, 2) == (32, 128)
    assert latent_tiles(12, 20, 16, 4128, 640, 2) == (32, 12)
    # a cell that VMEM cannot hold whole: the tokens spread evenly
    assert latent_tiles(8192, 20, 16, 4128, 640, 2) == (32, 512)
    assert latent_tiles(600, 20, 16, 4128, 640, 2) == (32, 320)


# ------------------------------------------------------ the paged programs


def _paged(request):
    """Two prompts through the paged programs (``harness.paged_drive``).
    Slot 1 takes a 53-token prompt in chunks of 16 (over three chunk
    boundaries, ending inside a chunk); slot 2 then a 33-token prompt (a
    page's first token last) whose chunks take slot 1's decode row along
    (the fused turn); then plain steps of both. Slots 0 and 3 hold no
    sequence, and every page no table names is FILLED WITH NaN in every
    layer's pool, as a released page would be: whatever read one would
    show."""
    cfg = glm_moe_lite_debug()
    slots, T, P = 4, 4, 24
    return dict(
        cfg=cfg, params=seeded(cfg), impl=request.param,
        tokens=jax.random.randint(jax.random.PRNGKey(9), (2, 80), 0,
                                  cfg.vocab_size),
        caches=init_paged_caches(cfg, slots * P + 1 + 8, T, P),
        tables=harness.slot_tables(slots, P, (1, 2)),
        lengths={1: 53, 2: 33}, chunk=16, steps=6, moe_info=True)


paged_run = harness.paged_fixture(_paged, impls=["reference", "pallas"])


@pytest.mark.parametrize("slot", [1, 2])
def test_paged_chunks_steps_and_fused_turns_match_the_reference(paged_run,
                                                                slot):
    run = paged_run
    cfg, n, end = run["cfg"], run["n"][slot], run["cursor"][slot]
    seq = run["tokens"][run["row"][slot]][None, :end]
    routes = np.concatenate(run["routes"][slot], 1)[:, None]
    assert routes.shape[2] == end
    assert all(r["routes"].shape[0] == cfg.expert_layers
               for r in run["info"])
    got = harness.slot_logits(run, slot)
    want = ref.forward(run["params"], seq, hp_of(cfg), routes)[0]
    assert rel(got, want[n - 1:]) <= TOL


def test_the_paged_programs_left_the_poisoned_pages_alone(paged_run):
    assert all(set(harness.pools(c)) == {"ckr"} for c in paged_run["caches"])
    harness.poisoned_pages_left_alone(paged_run)


# ------------------------------------------------------------ the scheduler


def near_the_references_best(cfg, params, prompt, out):
    return harness.near_the_references_best(
        lambda seq: ref.forward(params, seq, hp_of(cfg)), prompt, out)


def test_the_scheduler_serves_the_kind_and_counts_its_work():
    from ray_tpu.serve._private.work import token_bytes

    cfg = glm_moe_lite_debug()
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
    L = cfg.num_layers
    rows = [c for p in prompts for c in range(len(p) + new - 1)]
    steps = [len(p) + i for p in prompts for i in range(new - 1)]
    assert stats["latent_tokens_context"] == L * sum(c + 1 for c in rows)
    assert stats["latent_step_tokens_context"] == L * sum(
        c + 1 for c in steps)
    assert stats["latent_chunk_pairs"] == L * sum(
        c + 1 for p in prompts for c in range(len(p)))
    # a step's row reads its context, a chunk its context once; each writes
    # its own tokens: 160 B a token and layer (32 + 8 float32 values), which
    # a page holds in a row of 128 lanes
    bytes_a_token = 160
    assert token_bytes(cfg, LATENT, 4) == 512
    chunk_ends = [min(c0 + 16, len(p)) for p in prompts
                  for c0 in range(0, len(p), 16)]
    assert stats["latent_bytes_moved"] == L * bytes_a_token * (
        sum(c + 1 for c in steps) + sum(chunk_ends) + len(rows))
    assert stats["fused_turns"] > 0 and stats["pages_in_use"] == 0
    assert "indexed_tokens_context" not in stats  # another kind's
    # the reference lane fetches every row's longest context in whole
    # blocks whatever the tiles: the fill share is what it was (the
    # kernel's lane, once a cell:
    # ``test_a_latent_chunk_fetches_its_blocks_once_a_cell``)
    assert 0 < stats["attn_tokens_attended"] <= stats["attn_tokens_fetched"]
    # the experts: every live row of every EXPERT layer-call (the dense
    # layer routes nothing) took top-k experts and the shared one
    assert stats["moe_rows_routed"] == (
        cfg.expert_layers * cfg.moe_top_k * len(rows))
    assert stats["moe_shared_rows"] == cfg.expert_layers * len(rows)


def test_another_models_counters_are_what_they_were():
    from ray_tpu.serve._private.work import Work

    sizes = dict(slots=8, page_tokens=16, pages_per_slot=64, itemsize=2,
                 lane="reference")
    work = Work(moe_debug(), **sizes)
    work.record(1, [100, 200], 6)
    got = work.stats()
    assert not [k for k in got if k.startswith("latent_")
                or k == "moe_shared_rows"]
    latent = Work(glm_moe_lite_debug(), **sizes)
    latent.record(1, [100, 200], 6)
    # whole blocks of pages through the tables, a latent and a key a token
    stats = latent.stats()
    assert stats["attn_tokens_attended"] == 101 + 201
    assert stats["attn_bytes_moved"] == 3 * 256 * (
        stats["attn_tokens_fetched"] + 8)


# a step of two live rows beside six idle, a chunk of 300 real tokens behind
# a prefix of 1024 and a cold chunk, through the kernel's lane at pages of 16
# under a table of 128: (attended, fetched, bytes) as PR 55's tree counted
@pytest.mark.parametrize("preset,was", [
    ("moe_debug", (3886, 5120, 1574912)),
    ("mellum_debug", (3886, 5120, 6578176)),          # window beside full
    ("minicpm_sala_debug", (2492, 24576, 6555648)),   # block-selected
    ("keye_debug", (63, 2080, 1593344)),              # token-selected
    ("glm_moe_lite_debug", (2350, 3072, 3151872)),    # PR 55: 6958, 9216
], ids=["plain", "window", "sparse", "indexed", "latent"])
def test_a_latent_chunk_fetches_its_blocks_once_a_cell(preset, was):
    """``Work.record`` hands ``streamed_tokens`` the latent kernel's own
    tiles and no other kind's: every other kind counts what it counted, and
    a latent chunk the blocks up to its end ONCE (PR 55 counted them once a
    tile of the paged kernel's rule: 128 tokens of the toy's 4 heads, four
    tiles a chunk; at GLM's 20 heads eight)."""
    from ray_tpu.models import presets
    from ray_tpu.ops.paged_attention import streamed_tokens
    from ray_tpu.serve._private.work import Work

    work = Work(getattr(presets, preset)(), slots=8, page_tokens=16,
                pages_per_slot=128, itemsize=2, lane="pallas")
    work.record(1, [100, 200], 6)
    work.record(512, [1024], 0, 300)
    work.record(512, [0], 0)
    stats = work.stats()
    assert tuple(stats["attn_" + key] for key in (
        "tokens_attended", "tokens_fetched", "bytes_moved")) == was
    if preset != "glm_moe_lite_debug":
        return
    # the three calls: two rows' blocks of 512, then 1536 and 512 tokens
    assert was[1] == 2 * 512 + 1536 + 512
    # at the cell's sizes: a 512 chunk at 44,544 of a 4128-page table
    at = ("pallas", 512, [44544], 0, 20, 16, 4128, 640)
    tiles = latent_tiles(512, 20, 16, 4128, 640, 2)
    assert streamed_tokens(*at) == (358656, 360448)      # eight tiles
    assert streamed_tokens(*at, None, tiles) == (45056, 45056)
    assert streamed_tokens(*at, None, (32, 64)) == streamed_tokens(*at)
    # and the step is one cell a slot under either rule
    step = ("pallas", 1, [44544, 100], 6, 20, 16, 4128, 640)
    assert streamed_tokens(*step) == streamed_tokens(
        *step, None, latent_tiles(1, 20, 16, 4128, 640, 2)) == (44646, 45568)


def test_a_spliced_prefix_continues_to_the_same_logits():
    """The prefix cache serves the kind: the second request splices the
    first's pages — latents and rotated keys under one table — and answers
    as a scheduler without the cache does."""
    cfg = glm_moe_lite_debug()
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

    cfg = glm_moe_lite_debug()
    params = init_params(cfg, jax.random.PRNGKey(0))
    kw = dict(slots=2, prefill_chunk=16, arena_len=64, page_tokens=4,
              attn="reference")
    with pytest.raises(ValueError, match="speculative decoding cannot serve "
                                         "a model with 'latent_attention'"):
        ContinuousScheduler(cfg, params, drafter=object(), **kw)
    with pytest.raises(ValueError, match="holds keys and values alone"):
        init_slot_caches(cfg, 2, 64)
    sched = ContinuousScheduler(cfg, params, prefix_cache=True, **kw)
    try:
        with pytest.raises(ValueError, match="latents and rotated keys"):
            sched.export_prefix([1, 2, 3, 4])
    finally:
        sched.shutdown()


def test_value_heads_of_another_width_than_the_keys():
    """Heads whose values are narrower than their keys (as other latent
    models have them) take the uncached forward's padded path; the cached
    forward, absorbed, knows no such difference and agrees."""
    cfg = glm_moe_lite_debug(latent_v_dim=16, num_layers=2)
    params = seeded(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 24), 0,
                                cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        want = forward(cfg, params, tokens)
    assert rel(harness.cached_logits(cfg, params, tokens, 17),
               np.asarray(want[:, 16:])) <= TOL
