"""The 'indexed_latent_attention' kind and the router DeepSeek-V3.2-Exp
forces (latent attention whose queries attend the latents a learned indexer
picks, the index queries out of the query's bottleneck, YaRN on every
rotated part; group-limited sigmoid routing, of which a chip holds a share)
against the plain reference, ``perfbench/reference/deepseek_v32.py``:
float32 on the CPU at a toy size.

The reference is UNABSORBED, its index scores dense and its selection a
stable sort; the program scores through the kernel ``index_score``, selects
by counting (``indexed_select``), compacts the choice without a sort,
gathers the chosen rows through the page table and attends them absorbed.
The system is held to it at 1e-4 of the largest logit through every forward
— without a cache, the contiguous cache, the paged chunk, step and fused
turn by the ``jax.numpy`` path and by the kernel interpreted, a spliced
prefix — with the SELECTION and the ROUTES equal, ties and all. Named
faults are refused by the same comparison. This file: the model's own
forwards, the faults of the reference, the router; the op, the paged
programs and the scheduler are ``tests/test_deepseek_v32_paged.py``'s.
"""

from __future__ import annotations

import dataclasses
import itertools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench.reference import deepseek_v32 as ref  # noqa: E402
from ray_tpu.models.presets import (deepseek_v32_debug,  # noqa: E402
                                    glm_moe_lite_debug)
from ray_tpu.models.transformer import (INDEXED_LATENT,  # noqa: E402
                                        LAYER_KINDS, forward, init_params,
                                        layer_params, logical_axes)
from ray_tpu.ops import moe  # noqa: E402
from ray_tpu.ops.indexed_attention import IndexerSizes  # noqa: E402
from tests import model_harness as harness  # noqa: E402
from tests.model_harness import rel  # noqa: E402
from tests.test_glm_moe_lite import seeded  # noqa: E402

TOL = 1e-4


def hp_of(cfg):
    """The reference's configuration object, keyed as the source keys it."""
    return {"num_hidden_layers": cfg.num_layers,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "rope_scaling": {k: v for k, v in cfg.rope_scaling},
            "qk_nope_head_dim": cfg.latent_nope_dim,
            "qk_rope_head_dim": cfg.latent_rope_dim,
            "kv_lora_rank": cfg.latent_kv_rank,
            "index_n_heads": cfg.indexer.indexer_num_heads,
            "index_head_dim": cfg.indexer.indexer_head_dim,
            "index_topk": cfg.indexer.topk,
            "first_k_dense_replace": cfg.moe_dense_layers,
            "n_routed_experts": cfg.experts_held,
            "experts_held_first": cfg.moe_held_first,
            "n_group": cfg.moe_groups, "topk_group": cfg.moe_top_groups,
            "num_experts_per_tok": cfg.moe_top_k,
            "norm_topk_prob": cfg.moe_renormalize,
            "routed_scaling_factor": cfg.moe_routed_scale,
            "n_shared_experts": cfg.moe_shared_experts}


def stirred(cfg, seed=0):
    """``seeded`` weights (every norm's scale away from 1), the index key's
    LayerNorm bias away from 0 too."""
    params = seeded(cfg, seed)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 2), 16))

    def stir(path, leaf):
        if "ik_bias" in jax.tree_util.keystr(path):
            return leaf + 0.3 * jax.random.normal(next(keys), leaf.shape)
        return leaf

    return jax.tree_util.tree_map_with_path(stir, params)


def near_the_references_best(cfg, params, prompt, out):
    return harness.near_the_references_best(
        lambda seq: ref.forward(params, seq, hp_of(cfg)), prompt, out)


@pytest.fixture(scope="module")
def toy():
    cfg = deepseek_v32_debug()
    params = stirred(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 72), 0,
                                cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        logits, routes = forward(cfg, params, tokens, return_routes=True)
        _, selected = forward(cfg, params, tokens, return_selected=True)
    return {"cfg": cfg, "params": params, "tokens": tokens,
            "logits": np.asarray(logits), "routes": np.asarray(routes),
            "selected": np.asarray(selected)}


def test_the_preset_has_what_the_architecture_forces():
    cfg = deepseek_v32_debug()
    assert INDEXED_LATENT in LAYER_KINDS
    assert set(cfg.kinds) == {INDEXED_LATENT} and cfg.holds_pages
    assert not cfg.recurrent and cfg.head_dim == 24 + 8
    assert (cfg.moe_groups, cfg.moe_top_groups, cfg.moe_dense_layers) == (
        4, 2, 1)
    assert cfg.indexer == IndexerSizes(indexer_num_heads=4,
                                       indexer_head_dim=16, topk=24)
    rule, factor = cfg.latent_rope
    assert rule["rope_type"] == "yarn" and rule["attention_factor"] == 1.0
    assert factor == pytest.approx((0.1 * np.log(40) + 1) ** 2)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    attn = params["blocks"]["body"]["attn"]
    # the index queries read the QUERY'S BOTTLENECK (48), not the input (64)
    assert attn["wi_q"].shape == (2, 48, 4, 16)
    assert attn["wi_k"].shape == (2, 64, 16) and attn["wi_w"].shape[1:] == (
        64, 4)
    axes = logical_axes(cfg)
    assert jax.tree.structure(axes, is_leaf=lambda a: isinstance(
        a, tuple)) == jax.tree.structure(params)
    with pytest.raises(ValueError, match="index_num_heads"):
        deepseek_v32_debug(index_topk=0)
    with pytest.raises(ValueError, match="rotated part"):
        deepseek_v32_debug(index_head_dim=4)


def test_the_other_kinds_weights_are_drawn_as_they_were():
    """The indexer's weights come from keys of their own: a latent layer's
    seven matrices are bitwise the same with it and without, and a model of
    the latent kind alone holds nothing new."""
    glm = glm_moe_lite_debug(latent_v_dim=16, norm_eps=1e-6, num_layers=2)
    mine = deepseek_v32_debug(moe_num_experts=8, moe_groups=1,
                              moe_top_groups=1, moe_routed_scale=1.8,
                              rope_scaling=None, num_layers=2)
    a = init_params(glm, jax.random.PRNGKey(3))
    b = init_params(mine, jax.random.PRNGKey(3))
    theirs = a["blocks"]["body"]["attn"]
    assert set(theirs) == {"wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_norm",
                           "wkv_b", "wo"}
    for name, w in theirs.items():
        assert (np.asarray(w) == np.asarray(
            b["blocks"]["body"]["attn"][name])).all(), name
    for name, w in a["blocks"]["body"]["mlp"].items():
        assert (np.asarray(w) == np.asarray(
            b["blocks"]["body"]["mlp"][name])).all(), name
    # and the plain rotation is what it was: no rule, no factor
    assert glm.latent_rope == (None, 1.0)


@pytest.mark.parametrize("scan", [True, False], ids=["stacked", "apart"])
def test_forward_logits_selection_and_routes_match_the_reference(toy, scan):
    cfg, tokens = toy["cfg"], toy["tokens"]
    params = toy["params"]
    if not scan:
        cfg = dataclasses.replace(cfg, scan_layers=False)
        params = {**params, "blocks": {
            str(i): layer_params(toy["cfg"], params, i)
            for i in range(cfg.num_layers)}}
        with jax.default_matmul_precision("highest"):
            logits = np.asarray(forward(cfg, params, tokens))
        assert rel(logits, toy["logits"]) <= 1e-6
    want, took, taken = ref.forward_and_choices(params, tokens, hp_of(cfg))
    assert rel(toy["logits"], want) <= TOL
    assert (toy["routes"] == took).all()
    S = tokens.shape[1]
    assert (toy["selected"][..., :S] == taken).all()
    assert not toy["selected"][..., S:].any()
    # the toy selects: rows past topk attend exactly topk tokens
    assert (taken.sum(-1)[..., 24:] == 24).all()
    assert (taken.sum(-1)[..., :24] == np.arange(1, 25)).all()


# ------------------------------------------------------------- named faults


def _patched(name, value):
    def plant(m, hp):
        m.setattr(ref, name, value)
        return hp
    return plant


def _scale_without_the_yarn_factor(m, hp):
    true = ref.yarn
    m.setattr(ref, "yarn", lambda dim, hp: true(dim, hp)[:2] + (1.0,))
    return hp


def _bias_left_in_the_weights(m, hp):
    true = ref.token_weights
    m.setattr(ref, "token_weights", lambda s, r, hp: true(s + 0.1, r, hp))
    return hp


# fault -> (how it is planted in the reference, the choices it is GIVEN:
# those the fault does not make itself)
FAULTS = {
    "indexer_reads_the_input": (_patched(
        "index_source", lambda cq, h: h[..., :cq.shape[-1]]), "routes"),
    "whole_index_head_rotated": (_patched(
        "index_rope_dim", lambda hp: hp["index_head_dim"]), "routes"),
    "plain_rope": (lambda m, hp: {**hp, "rope_scaling": None}, "both"),
    "scale_without_the_yarn_factor": (_scale_without_the_yarn_factor,
                                      "both"),
    "group_score_by_the_max": (_patched(
        "group_scores", lambda by_group: by_group.max(-1)), "selected"),
    "bias_left_in_the_weights": (_bias_left_in_the_weights, "both"),
}


@pytest.mark.parametrize("fault", [None] + list(FAULTS))
def test_a_named_fault_is_refused(toy, monkeypatch, fault):
    """Each fault planted in the reference moves its logits past the limit
    the sound reference stays within — given the program's choices, but for
    the choice the fault IS, where the reference makes its own. (The two
    faults of the pool are the paged run's, further down.)"""
    hp, given = hp_of(toy["cfg"]), "both"
    if fault:
        plant, given = FAULTS[fault]
        hp = plant(monkeypatch, hp)
    want = ref.forward(
        toy["params"], toy["tokens"], hp,
        toy["routes"] if given in ("both", "routes") else None,
        toy["selected"] if given in ("both", "selected") else None)
    err = rel(toy["logits"], want)
    assert (err > TOL) if fault else (err <= TOL), err


# ----------------------------------------------------------- the group step


def _by_enumeration(biased, groups, kept, k):
    """The rule of ISSUE 61 by brute force, a row at a time: a group's score
    the sum of its two largest entries, every subset of ``kept`` groups
    tried and the best kept (ties to the lower groups), then the k largest
    of its experts, ties to the lower index."""
    out = []
    size = biased.shape[1] // groups
    for row in np.asarray(biased, np.float32):
        score = [np.float32(np.sort(row[g * size:(g + 1) * size])[-2:].sum())
                 for g in range(groups)]
        # the best subset: its groups' scores, best first, the largest such
        # list there is (float32, as the router adds them)
        best = max(itertools.combinations(range(groups), kept),
                   key=lambda c: (sorted((score[g] for g in c),
                                         reverse=True), [-g for g in c]))
        inside = [e for g in best for e in range(g * size, (g + 1) * size)]
        out.append(sorted(inside, key=lambda e: (-row[e], e))[:k])
    return np.asarray(out)


@pytest.mark.parametrize("experts,groups,kept,k", [(16, 4, 2, 3),
                                                   (32, 8, 4, 8),
                                                   (16, 4, 4, 3)])
def test_the_group_step_is_the_enumerations(experts, groups, kept, k):
    key = jax.random.PRNGKey(experts + kept)
    logits = jax.random.normal(key, (96, experts), jnp.float32)
    # quantised: ties among scores, groups and experts occur
    logits = jnp.round(logits * 2) / 2
    bias = 0.1 * jnp.round(jax.random.normal(jax.random.fold_in(key, 1),
                                             (experts,)) * 4)
    scores, idx, vals = moe.route(logits, k, True, moe.SIGMOID, bias, 2.5,
                                  groups, kept)
    want = _by_enumeration(scores + bias, groups, kept, k)
    assert (np.asarray(idx) == want).all()
    hp = {"n_group": groups, "topk_group": kept, "num_experts_per_tok": k,
          "norm_topk_prob": True, "routed_scaling_factor": 2.5}
    assert (np.asarray(ref.choose(scores[None], bias, hp))[0] == want).all()
    weights = np.asarray(ref.token_weights(scores[None], idx[None], hp))[0]
    assert np.allclose(np.take_along_axis(weights, np.asarray(idx), 1),
                       np.asarray(vals), rtol=1e-6)
    # the weights are the UNBIASED scores over their sum, times the factor
    assert np.allclose(np.asarray(vals).sum(-1), 2.5, rtol=1e-5)


def test_one_group_is_the_router_it_was():
    """``n_group`` 1 adds nothing to the program: the same jaxpr as a call
    that names no groups, one top-k in it."""
    logits = jnp.zeros((8, 16), jnp.float32)
    bias = jnp.zeros((16,), jnp.float32)
    was = jax.make_jaxpr(lambda l, b: moe.route(
        l, 3, True, moe.SIGMOID, b, 1.8))(logits, bias)
    now = jax.make_jaxpr(lambda l, b: moe.route(
        l, 3, True, moe.SIGMOID, b, 1.8, 1, 1))(logits, bias)
    assert str(was) == str(now) and str(now).count("top_k") == 1
    grouped = jax.make_jaxpr(lambda l, b: moe.route(
        l, 3, True, moe.SIGMOID, b, 1.8, 4, 2))(logits, bias)
    assert str(grouped).count("top_k") == 3
    with pytest.raises(ValueError, match="groups"):
        moe.route(logits, 3, True, moe.SIGMOID, bias, 1.8, 5, 2)


def test_the_sixteen_shares_add_up_to_the_uncut_layer(toy):
    """One expert layer cut over SIXTEEN chips, an expert each: every share
    routes over all 16, weighs over all its top-k and computes its own
    expert's part plus the shared expert; the parts, the shared expert
    counted once, are the uncut reference's layer."""
    cfg, params = toy["cfg"], toy["params"]
    mlp = jax.tree.map(lambda a: a[0], params["blocks"]["body"]["mlp"])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 40, cfg.embed_dim))
    kw = dict(num_experts=16, top_k=cfg.moe_top_k, renormalize=True,
              dtype=jnp.float32, scoring=moe.SIGMOID,
              routed_scale=cfg.moe_routed_scale, expert_groups=4,
              top_groups=2)
    shared_alone = {k: v for k, v in mlp.items()}
    with jax.default_matmul_precision("highest"):
        whole, _, counts, routes = moe.moe_layer(mlp, x, **kw)
        parts, landed = [], 0
        for j in range(16):
            share = {k: (v[j:j + 1] if k in ("w_gate", "w_up", "w_down")
                         else v) for k, v in shared_alone.items()}
            y, _, c, r = moe.moe_layer(share, x, held=(j, 1), **kw)
            assert (np.asarray(r) == np.asarray(routes)).all()
            parts.append(y)
            landed += int(c.sum())
        none = {k: (v[:1] * 0 if k in ("w_gate", "w_up", "w_down") else v)
                for k, v in shared_alone.items()}
        shared, _, _, _ = moe.moe_layer(none, x, held=(0, 1), **kw)
    assert landed == 2 * 40 * cfg.moe_top_k == int(counts.sum())
    total = sum(parts) - 15 * shared
    hp = {**hp_of(cfg), "n_routed_experts": 16}
    with jax.default_matmul_precision("highest"):
        want, took = ref.expert_layer(x, mlp, (), hp)
    assert (np.asarray(took) == np.asarray(routes)).all()
    scale = float(np.abs(np.asarray(want)).max())
    assert np.abs(np.asarray(total) - np.asarray(want)).max() <= TOL * scale
    assert np.abs(np.asarray(whole) - np.asarray(want)).max() <= TOL * scale
    # a share alone is NOT the layer
    assert np.abs(np.asarray(parts[0]) - np.asarray(want)).max() > (
        100 * TOL * scale)


# ------------------------------------------------------ the contiguous cache


@pytest.mark.parametrize("n", [9])
def test_prefill_and_decode_step_match_the_reference(toy, n):
    """The cached forward attends the picked latents ABSORBED, gathered out
    of its own pool; the reference rebuilds keys and values. (A prompt
    within ``topk``: the steps behind it cross it.)"""
    cfg, params, tokens = toy["cfg"], toy["params"], toy["tokens"]
    want = ref.forward(params, tokens, hp_of(cfg), toy["routes"],
                       toy["selected"])
    assert rel(harness.cached_logits(cfg, params, tokens, n),
               want[:, n - 1:]) <= TOL
