"""The OLMoE-shaped block (ISSUE 26: dropless top-k experts over grouped
matmuls, router weights not renormalized, RMSNorm on the whole projected q
and k) against the plain reference ``perfbench/reference/olmoe.py``, at a
toy size on the CPU in float32 on seeded weights.

Logits are compared, never sampled tokens. Top-k is discontinuous, so the
reference is given the system's own routes (*routed*) and its own choice is
compared by margin. Tolerance 1e-4 of the largest logit: both sides compute
in float32 and differ by the order of their sums (grouped matmuls over
sorted rows against one dense matmul an expert), which reads about 3e-7
here; a forward pass in bfloat16 reads about 1e-2, a dropped row, weights
that are renormalized or a missing q/k norm far more (asserted below).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import olmoe as ref
from ray_tpu.models import (forward, init_params, llama_debug, loss_fn,
                            moe_debug)
from ray_tpu.models.decode import (init_paged_caches, paged_decode_step,
                                   paged_verify_step)
from ray_tpu.models.transformer import _qkv
from ray_tpu.ops import moe as moe_ops
from ray_tpu.ops.moe import Tiles, init_moe_params, moe_layer, tile_sizes
from ray_tpu.ops.rotary import apply_rotary, rope_frequencies
from tests import model_harness as harness
from tests.model_harness import rel as rel_err

TOL = 1e-4


def hp_of(cfg):
    """The reference's view of a program config (the source's keys)."""
    return {"rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "num_experts": cfg.moe_num_experts,
            "num_experts_per_tok": cfg.moe_top_k,
            "norm_topk_prob": cfg.moe_renormalize,
            "num_hidden_layers": cfg.num_layers}


# seeded weights whose norm scales are not all ones (so a norm that is left
# out shows)
seeded = harness.seeded


def sys_forward(cfg, params, tokens):
    with jax.default_matmul_precision("highest"):
        return forward(cfg, params, tokens, return_routes=True)


@pytest.fixture(scope="module")
def toy():
    cfg = moe_debug(max_seq_len=256)
    params = seeded(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 48), 0,
                                cfg.vocab_size)
    return cfg, params, tokens


# ------------------------------------------------------- the full forward


@pytest.mark.parametrize("scan_layers", [True, False],
                         ids=["stacked", "layers_apart"])
def test_forward_logits_match_the_reference(scan_layers):
    cfg = moe_debug(scan_layers=scan_layers)
    params = seeded(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 40), 0,
                                cfg.vocab_size)
    logits, routes = sys_forward(cfg, params, tokens)
    assert routes.shape == (cfg.num_layers, 2, 40, cfg.moe_top_k)
    want, probs = ref.forward_and_router(params, tokens, hp_of(cfg), routes)
    assert rel_err(logits, want) < TOL
    # in float32 the system takes the experts the reference would
    flips, margin = ref.routing_margin(probs, routes)
    assert margin < 1e-6 and flips < 0.01
    assert rel_err(logits, ref.forward(params, tokens, hp_of(cfg))) < TOL


@pytest.mark.parametrize("fused_ce", [False, True], ids=["plain", "fused"])
def test_loss_and_gradients_match_the_reference(toy, fused_ce):
    """Same tolerance, on the loss and on every leaf's gradient (as a share
    of the leaf's largest entry), with the Switch auxiliary term in both."""
    cfg, params, tokens = toy
    cfg = dataclasses.replace(cfg, fused_ce=fused_ce)
    with jax.default_matmul_precision("highest"):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, {"tokens": tokens}),
            has_aux=True)(params)
    want, want_grads = jax.value_and_grad(
        lambda p: ref.loss(p, tokens, hp_of(cfg), cfg.moe_aux_weight))(params)
    assert abs(float(loss) - float(want)) < TOL * abs(float(want))
    assert float(metrics["moe_aux"]) > 0
    errs = jax.tree.map(rel_err, grads, want_grads)
    assert max(jax.tree.leaves(errs)) < TOL, errs


VARIANTS = {
    # what the routed tolerance has to refuse
    "bfloat16_for_float32": lambda cfg: dataclasses.replace(
        cfg, dtype=jnp.bfloat16),
    "renormalized_weights": lambda cfg: dataclasses.replace(
        cfg, moe_renormalize=True),
}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_the_routed_tolerance_refuses(toy, variant):
    cfg, params, tokens = toy
    logits, routes = sys_forward(VARIANTS[variant](cfg), params, tokens)
    want = ref.forward(params, tokens, hp_of(cfg), routes)
    assert rel_err(logits, want) > 10 * TOL


def test_the_routed_tolerance_refuses_a_missing_qk_norm(toy):
    cfg, params, tokens = toy
    bare = dataclasses.replace(cfg, qk_norm=False)
    logits, routes = sys_forward(bare, params, tokens)
    want = ref.forward(params, tokens, hp_of(cfg), routes)
    assert rel_err(logits, want) > 10 * TOL


def test_the_routed_tolerance_refuses_a_dropped_row(toy):
    """One (token, expert) pair of one layer left out of the reference's
    sum: the system, which drops nothing, is then far from it."""
    cfg, params, tokens = toy
    logits, routes = sys_forward(cfg, params, tokens)
    dropped = routes.at[0, 0, 5, 0].set(routes[0, 0, 5, 1])  # a pair twice
    want = ref.forward(params, tokens, hp_of(cfg), dropped)
    assert rel_err(logits, want) > 10 * TOL


# ------------------------------------------------ decoding through caches


def test_prefill_and_decode_step_match_the_full_forward(toy):
    cfg, params, tokens = toy
    n = 30
    want = ref.forward(params, tokens, hp_of(cfg))
    got = harness.cached_logits(cfg, params, tokens[:, :-1], n,
                                length=tokens.shape[1])
    assert rel_err(got, want[:, n - 1:-1]) < TOL


def paged_setup(cfg, slots, T=8, P=16):
    caches = init_paged_caches(cfg, slots * P + 1, T, P)
    tables = (1 + np.arange(slots * P, dtype=np.int32)).reshape(slots, P)
    return caches, jnp.asarray(tables)


def _paged(request):
    """Two prompts through the paged programs in the in-place reference
    lane (``harness.paged_drive``): prefill chunks of 16 into slots 1 and 2
    of 4 (slots 0 and 3 hold no sequence), then decode steps, collecting
    logits, routes and counts. The step's rows a chunk's program takes
    along: none decodes yet, so none is active, none is routed to an expert
    and none is counted. (Every page but the garbage page is some slot's:
    there is none to poison.)"""
    cfg, params, tokens = request.getfixturevalue("toy")
    caches, tables = paged_setup(cfg, 4)
    return dict(cfg=cfg, params=params, tokens=tokens, caches=caches,
                tables=np.asarray(tables), impl="reference", along=False,
                poisoned=False, lengths={1: 21, 2: 32}, chunk=16, steps=6,
                moe_info=True)


paged_run = harness.paged_fixture(_paged)


@pytest.mark.parametrize("b,slot", [(0, 1), (1, 2)])
def test_paged_chunks_and_decode_match_the_full_forward(toy, paged_run, b,
                                                        slot):
    cfg, params, tokens = toy
    n = paged_run["n"][slot]
    seq = tokens[b:b + 1, :n + 6]
    routes = jnp.asarray(np.concatenate(paged_run["routes"][slot], 1))[:, None]
    want = ref.forward(params, seq, hp_of(cfg), routes)[0]
    got = harness.slot_logits(paged_run, slot)
    assert rel_err(got, want[n - 1:n + 6]) < TOL
    assert rel_err(got, ref.forward(params, seq, hp_of(cfg))[0][n - 1:]) < TOL


def test_paged_programs_count_live_rows_only(toy, paged_run):
    """Rows of slots without a sequence and a chunk's padding reach no
    expert: the counts are live rows x k x layers, exactly."""
    cfg = toy[0]
    chunks = paged_run["info"][:2 + 2]
    assert all(info["routes"].shape[2] == 16 + 4 for info in chunks)
    counted = sum(int(info["counts"].sum()) for info in paged_run["info"])
    live = sum(paged_run["cursor"].values())
    assert live == 21 + 32 + 2 * 6
    assert counted == live * cfg.moe_top_k * cfg.num_layers


def test_paged_verify_step_matches_the_full_forward(toy, paged_run):
    """A K = 4 window for slot 1 (3 of its rows used) and slot 2 (all 4),
    called directly: logits against the reference's full forward, unused
    rows and rows of slots without a sequence not counted."""
    cfg, params, tokens = toy
    K, used = 4, np.asarray([0, 3, 4, 0], np.int32)
    window = np.zeros((4, K), np.int32)
    starts = {s: paged_run["n"][s] + 6 for s in (1, 2)}
    for b, s in enumerate((1, 2)):
        window[s, :used[s]] = tokens[b, starts[s]:starts[s] + used[s]]
    with jax.default_matmul_precision("highest"):
        logits, _, moe = jax.jit(functools.partial(
            paged_verify_step, cfg, attn="reference", moe_info=True))(
            params, jnp.asarray(window), jnp.asarray(used),
            np.asarray([0, starts[1], starts[2], 0], np.int32),
            paged_run["tables"], paged_run["tables"], paged_run["caches"])
    assert int(moe["counts"].sum()) == 7 * cfg.moe_top_k * cfg.num_layers
    for b, s in enumerate((1, 2)):
        seq = tokens[b:b + 1, :starts[s] + used[s]]
        want = ref.forward(params, seq, hp_of(cfg))[0][starts[s]:]
        assert rel_err(logits[s, :used[s]], want) < TOL


def test_moe_info_refuses_a_dense_model(toy):
    cfg, params, _ = toy
    caches, tables = paged_setup(cfg, 2)
    dense = dataclasses.replace(cfg, mlp="swiglu")
    with pytest.raises(ValueError, match="mlp='moe'"):
        paged_decode_step(dense, params, jnp.zeros(2, jnp.int32),
                          jnp.ones(2, jnp.int32), jnp.zeros(2, jnp.int32),
                          tables, tables, caches, jnp.zeros(2, jnp.float32),
                          jnp.zeros(2, jnp.uint32), attn="reference",
                          moe_info=True)


def test_scheduler_serves_the_expert_model_and_drops_no_row(toy):
    """Through ``ContinuousScheduler`` (prefill chunks + paged decode, some
    slots idle, padded chunks): every served token is the reference's
    choice or within TOL of it, and the device's expert counts add up to
    live rows x k x layers."""
    cfg, params, tokens = toy
    prompts = [np.asarray(tokens[0, :21]).tolist(),
               np.asarray(tokens[1, :37]).tolist(),
               np.asarray(tokens[0, 5:14]).tolist()]
    served, stats = harness.served(
        cfg, params, prompts, 5, slots=4, prefill_chunk=16, arena_len=128,
        page_tokens=8, kv_pages=65)
    for prompt, out in zip(prompts, served):
        assert len(out) == 5
        assert harness.near_the_references_best(
            lambda seq: np.asarray(ref.forward(params, seq, hp_of(cfg))),
            prompt, out, tol=TOL)
    live = sum(len(p) for p in prompts) + 4 * len(prompts)
    assert stats["moe_live_rows"] == live
    assert stats["moe_rows_routed"] == live * cfg.moe_top_k * cfg.num_layers
    assert stats["moe_layer_calls"] == cfg.num_layers * (
        stats["decode_steps"] + stats["prefill_chunks"])
    assert 0 < stats["moe_experts_hit"] <= (stats["moe_layer_calls"]
                                            * cfg.moe_num_experts)
    assert stats["moe_max_expert_rows"] >= (stats["moe_rows_routed"]
                                            / cfg.moe_num_experts)
    # the kernel's walk: a chunk's program that took rows along is two
    # layer-calls a layer and ONE kernel call; a visit that carries rows is
    # at least one an expert hit in the call, at most the grid's
    assert stats["fused_turns"] > 0
    assert stats["moe_kernel_calls"] == cfg.num_layers * (
        stats["decode_steps"] + stats["prefill_chunks"]
        - stats["fused_turns"])
    assert 0 < stats["moe_visits"] <= stats["moe_grid_visits"]
    assert stats["moe_grid_visits"] >= stats["moe_kernel_calls"] * (
        cfg.moe_num_experts)


# ------------------------------------------------------ the expert layer


def layer_reference(p, x, top_k, renormalize, routes=None):
    """One expert layer by the reference's own functions (float32)."""
    with jax.default_matmul_precision("highest"):
        h = x.astype(jnp.float32)
        probs = jax.nn.softmax(h @ p["w_router"], axis=-1)
        w = ref.token_weights(probs, routes, top_k, renormalize)
        y = jnp.zeros_like(h)
        for e in range(p["w_router"].shape[1]):
            y = ref.add_expert(y, h, w, p, (e,))
    return y


def run_layer(p, x, **kw):
    with jax.default_matmul_precision("highest"):
        return moe_layer(p, x, num_experts=p["w_router"].shape[-1],
                         dtype=jnp.float32, **kw)


@pytest.mark.parametrize("renormalize", [False, True],
                         ids=["olmoe", "renormalized"])
def test_layer_matches_the_reference(renormalize):
    p = init_moe_params(jax.random.PRNGKey(0), 32, 48, 8)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, 32))
    y, _, counts, routes = run_layer(p, x, top_k=3, renormalize=renormalize)
    assert rel_err(y, layer_reference(p, x, 3, renormalize, routes)) < 1e-5
    assert int(counts.sum()) == 2 * 24 * 3


@pytest.mark.parametrize("case", ["all_rows_to_the_same_experts",
                                  "an_expert_with_no_row"])
def test_forced_imbalance_loses_no_row(case):
    """No capacity: experts that take every row, and experts that take
    none, both give the reference's output."""
    E, k, n = 8, 2, 40
    p = init_moe_params(jax.random.PRNGKey(0), 16, 24, E)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (1, n, 16))) + 0.1
    if case == "all_rows_to_the_same_experts":
        # positive inputs: a large positive column wins every row
        router = p["w_router"].at[:, 5].set(3.0).at[:, 2].set(2.0)
        want_counts = [0, 0, n, 0, 0, n, 0, 0]
    else:
        router = p["w_router"].at[:, 4].set(-5.0)
        want_counts = None
    p = {**p, "w_router": router}
    y, _, counts, routes = run_layer(p, x, top_k=k, renormalize=False)
    assert int(counts.sum()) == n * k
    if want_counts is not None:
        assert counts.tolist() == want_counts
    else:
        assert int(counts[4]) == 0 and int((counts > 0).sum()) > 2
    assert rel_err(y, layer_reference(p, x, k, False, routes)) < 1e-5


def test_rows_masked_by_valid_change_no_output_and_no_count():
    p = init_moe_params(jax.random.PRNGKey(0), 32, 48, 8)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    valid = jnp.arange(16)[None] < jnp.asarray([[16], [9]])
    y, aux, counts, _ = run_layer(p, x, top_k=3, renormalize=False,
                                  valid=valid)
    # the live rows alone, as a batch of their own
    y0, _, c0, _ = run_layer(p, x[:1], top_k=3, renormalize=False)
    y1, _, c1, _ = run_layer(p, x[1:, :9], top_k=3, renormalize=False)
    assert counts.tolist() == (c0 + c1).tolist()
    assert int(counts.sum()) == 25 * 3
    np.testing.assert_allclose(y[0], y0[0], rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(y[1, :9], y1[0], rtol=1e-6, atol=1e-7)
    assert not np.asarray(y[1, 9:]).any()
    # garbage in the masked rows (a retired slot's stale state) is inert
    noisy = x.at[1, 9:].set(jnp.nan)
    y2, aux2, counts2, _ = run_layer(p, noisy, top_k=3, renormalize=False,
                                     valid=valid)
    assert counts2.tolist() == counts.tolist()
    np.testing.assert_array_equal(np.asarray(y2[0]), np.asarray(y[0]))
    np.testing.assert_array_equal(np.asarray(y2[1, :9]), np.asarray(y[1, :9]))


# ------------------- the serving path's experts: the kernel (ISSUE 37)
#
# A call that passes the stack and a layer runs ``expert_mlp`` (the Pallas
# kernel ``moe_grouped_matmul``, interpreted here); the call without runs
# ``jax.lax.ragged_dot``. Both against each other and against the reference
# given the routes, in float32 at 1e-4.

# name -> (rows, experts, top_k, layers in the stack, layer, router, live)
KERNEL_CASES = {
    "8_pairs": (1, 16, 8, 1, 0, None, None),
    "256_pairs": (32, 64, 8, 1, 0, None, None),
    "4096_pairs": (512, 64, 8, 1, 0, None, None),
    "a_layer_in_the_middle_of_a_stack": (32, 64, 8, 3, 1, None, None),
    "the_last_layer_of_a_stack": (24, 8, 3, 4, 3, None, None),
    "experts_without_rows": (32, 64, 8, 2, 1, "most_empty", None),
    "one_expert_takes_every_row": (40, 8, 2, 2, 0, "one_wins", None),
    "rows_that_are_not_live": (32, 64, 8, 2, 1, None, 19),
    "no_row_is_live": (16, 8, 3, 2, 1, None, 0),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_the_kernel_matches_ragged_dot_and_the_reference(case):
    rows, E, k, layers, layer, router, live = KERNEL_CASES[case]
    d, f = 32, 48
    stack = [init_moe_params(jax.random.PRNGKey(7 + i), d, f, E)
             for i in range(layers)]
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (1, rows, d))) + 0.1
    if router == "most_empty":   # positive inputs: 52 experts never win
        stack[layer]["w_router"] = stack[layer]["w_router"].at[:, 12:].set(
            -5.0)
    if router == "one_wins":
        stack[layer]["w_router"] = stack[layer]["w_router"].at[:, 5].set(3.0)
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *stack)
    valid = None if live is None else (jnp.arange(rows) < live)[None]
    kw = dict(top_k=k, renormalize=False, valid=valid)
    y, _, counts, routes = run_layer(stacked, x, layer=layer, **kw)
    y_ragged, _, c_ragged, r_ragged = run_layer(stack[layer], x, **kw)
    n_live = rows if live is None else live
    assert counts.tolist() == c_ragged.tolist()
    assert int(counts.sum()) == n_live * k      # dropless, live rows only
    assert routes.tolist() == r_ragged.tolist()
    if router == "most_empty":
        assert int((counts > 0).sum()) <= 12
    if router == "one_wins":
        assert int(counts[5]) == rows
    assert not np.asarray(y[0, n_live:]).any()  # zeros out
    if n_live == 0:
        return
    want = layer_reference(stack[layer], x, k, False, routes)[0, :n_live]
    assert float(jnp.abs(want).max()) > 1e-5  # it compares something
    assert rel_err(y[0, :n_live], want) < TOL
    assert rel_err(y[0, :n_live], y_ragged[0, :n_live]) < TOL
    # a fault the tolerance refuses: bf16 operands through the same kernel
    y16 = moe_layer(stacked, x, num_experts=E, dtype=jnp.bfloat16,
                    layer=layer, **kw)[0]
    if rows * k >= 256:
        assert rel_err(y16[0, :n_live], want) > 10 * TOL


_HIT = (0, 10, 0, 33, 1, 0, 0, 5)
# name -> (dtype, tiles, hidden width, activation, rows a group, the visits
# that carry rows). d 128, so 128 columns a tile are 2 column tiles of 256
# and 4 of 512; 80 pairs in row tiles of 8 are a grid of 10 + 8 - 1 visits
MLP_CASES = {
    "f32": (jnp.float32, None, 256, "swiglu", _HIT, None),
    "bf16": (jnp.bfloat16, None, 256, "swiglu", _HIT, None),
    "f32_column_tiles": (jnp.float32, Tiles(8, 128), 256, "swiglu", _HIT, 10),
    "bf16_column_tiles": (jnp.bfloat16, Tiles(32, 128), 256, "swiglu", _HIT,
                          5),
    # ISSUE 64: a padding visit holds its weight block still
    "2_column_tiles_most_visits_padding": (
        jnp.float32, Tiles(8, 128), 256, "swiglu", (0, 3, 0, 0, 2, 0, 0, 0),
        2),
    "4_column_tiles_most_visits_padding": (
        jnp.float32, Tiles(8, 128), 512, "swiglu", (0, 0, 0, 0, 0, 0, 4, 1),
        2),
    "relu2_2_column_tiles_most_visits_padding": (
        jnp.float32, Tiles(8, 128), 256, "relu2", (2, 0, 0, 0, 0, 3, 0, 0),
        2),
    "relu2_4_column_tiles": (jnp.bfloat16, Tiles(32, 128), 512, "relu2",
                             _HIT, 5),
    "2_column_tiles_no_held_pair": (
        jnp.float32, Tiles(8, 128), 256, "swiglu", (0,) * 8, 0),
    "relu2_4_column_tiles_no_held_pair": (
        jnp.float32, Tiles(8, 128), 512, "relu2", (0,) * 8, 0),
    "4_column_tiles_one_group_takes_every_pair": (
        jnp.float32, Tiles(8, 128), 512, "swiglu", (0, 0, 0, 80, 0, 0, 0, 0),
        10),
    "relu2_2_column_tiles_one_group_takes_every_pair": (
        jnp.float32, Tiles(16, 128), 256, "relu2", (0,) * 7 + (80,), 5),
    "4_column_tiles_a_group_across_two_row_tiles": (
        jnp.float32, Tiles(32, 128), 512, "swiglu", (5, 40, 0, 0, 0, 0, 0, 3),
        4),
    "relu2_2_column_tiles_a_group_across_two_row_tiles": (
        jnp.float32, Tiles(16, 128), 256, "relu2", (0, 12, 9, 0, 0, 0, 0, 0),
        3),
}


@pytest.mark.parametrize("case", list(MLP_CASES))
def test_expert_mlp_is_ragged_dot_thrice(case):
    """The op alone, on a stack with an offset, a SwiGLU expert's three
    products and a 'relu2' expert's two; with the hidden width cut into
    column tiles the down product accumulates in float32, and what the
    walk's padding visits fetch (ISSUE 64) changes no number: most visits
    padding, every visit padding, none, a group across two row tiles. Rows
    past the last group are undefined and touch no live row."""
    dtype, tiles, f, activation, counts, visits = MLP_CASES[case]
    G, d, pairs, first = 8, 128, 80, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    xs = jax.random.normal(ks[0], (pairs, d), jnp.float32).astype(dtype)
    gated = activation == "swiglu"
    w = [(0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
         for key, shape in zip(ks[1:], [
             (3 * G, d, f), (3 * G, d, f) if gated else (3 * G, f, d),
             (3 * G, f, d)])]
    if not gated:
        w[0] = None
    counts = jnp.asarray(counts, jnp.int32)
    live = int(counts.sum())
    with jax.default_matmul_precision("highest"):
        got = moe_ops.expert_mlp(xs, *w, counts, first, tiles=tiles)
        gate, up, down = (a if a is None else a[first:first + G] for a in w)
        hidden = (jax.nn.silu(jax.lax.ragged_dot(xs, gate, counts))
                  * jax.lax.ragged_dot(xs, up, counts) if gated else
                  jnp.square(jax.nn.relu(jax.lax.ragged_dot(
                      xs, up.swapaxes(1, 2), counts))))
        want = jax.lax.ragged_dot(hidden, down, counts)
        again = moe_ops.expert_mlp(xs.at[live:].set(jnp.nan), *w, counts,
                                   first, tiles=tiles)
    assert got.shape == (pairs, d) and got.dtype == dtype
    if tiles is not None:
        n_tiles = -(-pairs // tiles.rows)
        assert f // tiles.cols in (2, 4)
        assert visits < n_tiles + G - 1 and visits == int(moe_ops._visits(
            counts, jnp.int32(first), n_tiles, tiles.rows)[4][0])
    if not live:
        return
    # bf16: the kernel rounds silu(gate) * up once where XLA rounds thrice
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    assert rel_err(got[:live], want[:live]) < tol
    assert float(jnp.abs(want[:live].astype(jnp.float32)).max()) > (
        0.1 if gated else 0.02)
    np.testing.assert_array_equal(np.asarray(again[:live], np.float32),
                                  np.asarray(got[:live], np.float32))


@pytest.mark.parametrize("seed", range(4))
def test_the_walk_reads_every_hit_expert_once_and_no_other(seed):
    """The grid's walk, replayed on the host: every row of every group is
    stored exactly once, an expert's visits are consecutive (the pipeline
    keeps its weights: one read), an expert without rows is not visited,
    and the padding repeats the last visit."""
    rng = np.random.default_rng(seed)
    E, rows, n_tiles, first_group = 64, 16, 16, 64 * seed
    bias = rng.normal(0, 0.6, E)
    counts = np.zeros(E, np.int32)
    for _ in range(int(rng.integers(1, 33))):
        counts[np.argsort(-(bias + rng.gumbel(0, 1, E)))[:8]] += 1
    group, tile, start, end, total = (np.asarray(a) for a in moe_ops._visits(
        jnp.asarray(counts), jnp.int32(first_group), n_tiles, rows))
    total = int(total[0])
    assert group.shape == (n_tiles + E - 1,) and total <= group.shape[0]
    stored = np.zeros(n_tiles * rows, np.int32)
    for g, t, s, e in zip(group[:total], tile[:total], start[:total],
                          end[:total]):
        lo, hi = max(s, t * rows), min(e, (t + 1) * rows)
        assert lo < hi                       # a visit has a row to store
        stored[lo:hi] += 1
    assert (stored[:counts.sum()] == 1).all()
    assert not stored[counts.sum():].any()
    visited = group[:total] - first_group
    assert set(visited) == set(np.flatnonzero(counts))
    assert int((np.diff(visited) != 0).sum()) + 1 == (counts > 0).sum()
    assert (group[total:] == group[total - 1]).all()
    assert (tile[total:] == tile[total - 1]).all()


# 270 of a fused turn's 4,160 pairs held on 16 of DeepSeek-V3.2's 256
# experts, the fullest group 78 rows (``deepseek_v32_longdocs``' routing)
_HELD_270 = (78, 3, 0, 31, 12, 0, 22, 9, 41, 0, 17, 5, 26, 8, 14, 4)
# name -> (pairs, d, f, rows a group, the visits that carry rows)
WALKS = {
    "deepseek_a_sixteenth_held": (4160, 7168, 2048, _HELD_270, 15),
    "deepseek_no_pair_held": (4160, 7168, 2048, (0,) * 16, 0),
    "deepseek_every_pair_held": (4160, 7168, 2048, (260,) * 16, 48),
    "olmoe_one_column_tile": (4096, 2048, 1024, (64,) * 60 + (0, 256, 0, 0),
                              62),
    "olmoe_no_row_live": (256, 2048, 1024, (0,) * 64, 0),
}


def _blocks_fetched(index_map, group, total, grid, f_tiles):
    """The grid steps whose weight block is not the step's before: what the
    pipeline copies, the opening block among them."""
    steps = [tuple(int(b) for b in index_map(i, j, group, total, f_tiles))
             for i in range(grid) for j in range(f_tiles)]
    return 1 + sum(a != b for a, b in zip(steps, steps[1:]))


@pytest.mark.parametrize("case", list(WALKS))
def test_a_visit_without_rows_moves_no_weights(case):
    """ISSUE 64, the walk counted on the host: the weight blocks' index maps
    over the whole grid, at DeepSeek's shape (an expert of 7168 x 2048 does
    not fit VMEM: 8 column tiles under a grid of 33 + 16 - 1 visits). A
    visit that carries rows fetches its expert's 8 tiles; a padding visit
    none, where the maps as they were (``j`` in every visit) fetch 8 x 48 =
    384 whatever the counts; a walk without a real visit fetches its opening
    block. At one column tile a block changes where the group does, as it
    always did."""
    pairs, d, f, counts, visits = WALKS[case]
    t = tile_sizes(pairs, len(counts), d, f, 2)
    n_tiles, f_tiles = -(-pairs // t.rows), f // t.cols
    grid = n_tiles + len(counts) - 1
    group, _, _, _, total = (np.asarray(a) for a in moe_ops._visits(
        jnp.asarray(counts, jnp.int32), jnp.int32(32), n_tiles, t.rows))
    assert int(total[0]) == visits and group.shape == (grid,)
    as_they_were = {moe_ops.hidden_block: lambda i, j, g, *_: (g[i], 0, j),
                    moe_ops.down_block: lambda i, j, g, *_: (g[i], j, 0)}
    for index_map, before in as_they_were.items():
        fetched = _blocks_fetched(index_map, group, total, grid, f_tiles)
        was = _blocks_fetched(before, group, total, grid, f_tiles)
        if case.startswith("deepseek"):
            assert (t, grid, f_tiles) == (Tiles(128, 256), 48, 8)
            assert fetched == max(visits * f_tiles, 1) and was == 384
        else:
            assert f_tiles == 1
            runs = max(sum(c > 0 for c in counts), 1)
            assert fetched == was == runs <= max(visits, 1)


def test_the_walks_counters_repeat_the_kernels_arithmetic(toy):
    """``Work.routed``'s ``moe_visits`` is ``_visits``' ``total`` on the same
    counts and the same tiles, ``moe_grid_visits`` the grid the kernel is
    given, and a chunk's program that took the step's rows along — two
    groups of rows, two layer-calls a layer — is ONE kernel call a layer
    over both groups' counts."""
    from ray_tpu.serve._private.work import Work

    cfg = toy[0]
    L, E, k = cfg.num_layers, cfg.moe_num_experts, cfg.moe_top_k
    rng = np.random.default_rng(0)
    chunk, slots = 16, 4
    counts = np.zeros((L, 2, E), np.int32)
    for layer in range(L):
        for group, live in enumerate((13, 3)):
            for _ in range(live):
                counts[layer, group, rng.permutation(E)[:k]] += 1
    work = Work(cfg, slots=slots, page_tokens=8, pages_per_slot=16,
                lane="reference", itemsize=4)
    routes = np.zeros((L, 1, chunk + slots, k), np.int32)
    work.routed(({"counts": counts, "routes": routes},), 16)
    pairs = (chunk + slots) * k
    rows = tile_sizes(pairs, E, cfg.embed_dim, cfg.mlp_width("moe"),
                      jnp.dtype(cfg.dtype).itemsize).rows
    n_tiles = -(-pairs // rows)
    walks = [moe_ops._visits(jnp.asarray(c.sum(0)), jnp.int32(0), n_tiles,
                             rows) for c in counts]
    got = work.stats()
    assert got["moe_layer_calls"] == 2 * L and got["moe_kernel_calls"] == L
    assert got["moe_visits"] == sum(int(w[4][0]) for w in walks)
    assert got["moe_grid_visits"] == sum(w[0].shape[0] for w in walks)
    assert 0 < got["moe_visits"] <= got["moe_grid_visits"]
    # a step's program: a group of rows, a kernel call a layer
    work.routed(({"counts": counts[:, 1],
                  "routes": routes[:, :slots, :1]},), 3)
    assert work.stats()["moe_kernel_calls"] == 2 * L


def test_tile_sizes_is_a_function_of_static_shapes():
    """Row tiles from the pair count; the whole hidden width where an
    expert fits the VMEM it is given, whole-lane column tiles else."""
    decode = tile_sizes(256, 64, 2048, 1024, 2)
    chunk = tile_sizes(4096, 64, 2048, 1024, 2)
    assert decode == tile_sizes(256, 64, 2048, 1024, 2) == Tiles(64, 1024)
    assert chunk == Tiles(128, 1024)
    for budget in (12 << 20, 24 << 20, 48 << 20):
        t = tile_sizes(4096, 64, 2048, 1024, 2, vmem_bytes=budget)
        assert moe_ops._vmem_bytes(t, 2048, 2) <= budget, (budget, t)
        assert 1024 % t.cols == 0 and t.cols % 128 == 0
    # Mixtral's experts (4096 x 14336) do not fit whole: column tiles
    big = tile_sizes(4096, 8, 4096, 14336, 2)
    assert 14336 % big.cols == 0 and big.cols < 14336
    assert moe_ops._vmem_bytes(big, 4096, 2) <= 48 << 20
    # never more rows than there are pairs, whole sublane tiles of the dtype
    assert tile_sizes(24, 8, 64, 128, 2).rows == 32
    assert tile_sizes(24, 8, 64, 128, 4).rows == 24
    assert tile_sizes(8, 16, 32, 48, 4) == Tiles(8, 48)


# --------------------------------------- what the dense models keep


@pytest.mark.parametrize("preset,overrides", [
    ("llama_debug", {}),
    ("gpt2_small", dict(num_layers=1, embed_dim=64, num_heads=4,
                        vocab_size=256, max_seq_len=64))],
    ids=["llama_shaped", "gpt2_shaped"])
def test_one_projection_reproduces_the_dense_block_bit_for_bit(preset,
                                                               overrides):
    """With qk_norm off the shared projection is the three einsums and the
    rotation every forward wrote out before, to the bit."""
    from ray_tpu.models import presets

    cfg = getattr(presets, preset)(**overrides)
    assert not cfg.qk_norm and cfg.moe_renormalize
    params = init_params(cfg, jax.random.PRNGKey(0))
    p = jax.tree.map(lambda a: a[0], params["blocks"])["attn"]
    assert "q_norm" not in p
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, cfg.embed_dim),
                          cfg.dtype)
    rope = (rope_frequencies(cfg.head_dim, cfg.max_seq_len, cfg.rope_theta)
            if cfg.pos == "rope" else None)
    got = _qkv(cfg, p, x, rope, None)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(cfg.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(cfg.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(cfg.dtype))
    if rope is not None:
        q = apply_rotary(q, *rope, None)
        k = apply_rotary(k, *rope, None)
    for a, b in zip(got, (q, k, v)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_llama_forward_matches_the_mistral_reference():
    """The dense Llama-shaped path through the shared projection, against
    the benchmark's dense reference."""
    from perfbench.reference import mistral

    cfg = llama_debug()
    params = seeded(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 24), 0,
                                cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        logits = forward(cfg, params, tokens)
    want = mistral.forward(params, tokens, {
        "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
        "num_hidden_layers": cfg.num_layers})
    assert rel_err(logits, want) < TOL


def test_tensor_parallel_refuses_qk_norm():
    from ray_tpu.models.transformer import tp_block_shard_spec

    with pytest.raises(ValueError, match="qk_norm"):
        tp_block_shard_spec(llama_debug(qk_norm=True))
