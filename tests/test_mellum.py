"""The Mellum-2-shaped block (ISSUE 46: 'sliding_attention' layers beside
'full_attention' ones 3:1, a RoPE rule a kind — plain and YaRN —, a head
size that is a field, renormalized top-k experts) against the plain
reference ``perfbench/reference/mellum.py``, at a toy size on the CPU in
float32 on seeded weights: the uncached forward, the contiguous cache, and
the paged chunk, step and fused turn with a page pool a kind, of which the
window layers' forgets what lies behind the window.

Logits are compared, never sampled tokens; tolerance 1e-4 of the largest
logit (both sides float32: they differ by the order of their sums, which
reads about 3e-7 here). The toy's window is 24 tokens and its pages hold 4,
so contexts below, at and past the window and chunk boundaries are all
within 80 tokens.
"""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import mellum as ref
from ray_tpu.models import (forward, init_params, llama_debug, mellum_debug,
                            moe_debug)
from ray_tpu.models.decode import (StepRows, init_caches,
                                   init_paged_caches, paged_decode_step,
                                   paged_prefill_into_slot,
                                   paged_verify_step, prefill)
from ray_tpu.models.transformer import ATTENTION, SLIDING
from ray_tpu.ops.paged_attention import (paged_attention, streamed_tokens,
                                         tile_sizes)
from ray_tpu.ops.rotary import rule_frequencies
from tests import model_harness as harness
from tests.model_harness import rel as rel_err, serve

# weights whose norm scales are not all ones
seeded = harness.seeded
TOL = 1e-4
PUBLISHED_YARN = {
    "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
    "original_max_position_embeddings": 8192, "beta_fast": 32,
    "beta_slow": 1, "attention_factor": 1.2772588722239782}


def hp_of(cfg):
    """The reference's view of a program config (the source's keys)."""
    return {"rms_norm_eps": cfg.norm_eps, "head_dim": cfg.head_dim,
            "num_experts": cfg.moe_num_experts,
            "num_experts_per_tok": cfg.moe_top_k,
            "norm_topk_prob": cfg.moe_renormalize,
            "num_hidden_layers": cfg.num_layers,
            "sliding_window": cfg.sliding_window,
            "rope_parameters": {kind: dict(rule)
                                for kind, rule in cfg.rope_parameters},
            "layer_types": ["full_attention" if kind == ATTENTION else kind
                            for kind in cfg.kinds],
            "mlp_layer_types": ["sparse"] * cfg.num_layers}



def sys_forward(cfg, params, tokens):
    with jax.default_matmul_precision("highest"):
        return forward(cfg, params, tokens, return_routes=True)


@pytest.fixture(scope="module")
def toy():
    cfg = mellum_debug()
    params = seeded(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 80), 0,
                                cfg.vocab_size)
    return cfg, params, tokens


# --------------------------------------------------------- the config


def test_the_preset_has_what_the_architecture_forces():
    cfg = mellum_debug()
    assert cfg.kinds == (SLIDING, SLIDING, SLIDING, ATTENTION) * 2
    assert cfg.head_dim == 32 != cfg.embed_dim // cfg.num_heads
    assert cfg.moe_renormalize and cfg.period == 4
    assert cfg.rope_rule(ATTENTION)["rope_type"] == "yarn"
    assert cfg.rope_rule(SLIDING)["rope_type"] == "default"
    assert cfg.window(SLIDING) == 24 and cfg.window(ATTENTION) is None
    # a config is hashable (it is a static argument of every program)
    assert hash(cfg) == hash(mellum_debug())
    # every other preset's head size is what it was
    assert llama_debug().head_dim == (llama_debug().embed_dim
                                      // llama_debug().num_heads)
    with pytest.raises(ValueError, match="sliding_window"):
        mellum_debug(sliding_window=0)
    with pytest.raises(ValueError, match="layer_kinds"):
        mellum_debug(layer_kinds=("sliding",) * 8)


def test_yarn_frequencies_are_the_public_rule_at_the_published_sizes():
    """By hand: dim(r) = 128 ln(8192 / (2 pi r)) / (2 ln 500000); low =
    floor(dim(32)) = 18, high = ceil(dim(1)) = 35; pairs up to 18 keep their
    frequency, pairs from 35 on are divided by 16, a linear ramp between."""
    inv_freq, factor = rule_frequencies(128, PUBLISHED_YARN)
    base = 500000.0 ** (-np.arange(64) * 2.0 / 128)
    dim = lambda r: 128 * np.log(8192 / (2 * np.pi * r)) / (
        2 * np.log(500000))
    assert (int(np.floor(dim(32))), int(np.ceil(dim(1)))) == (18, 35)
    np.testing.assert_allclose(inv_freq[:19], base[:19], rtol=1e-6)
    np.testing.assert_allclose(inv_freq[35:], base[35:] / 16, rtol=1e-6)
    i = 26
    ramp = (i - 18) / (35 - 18)
    np.testing.assert_allclose(
        inv_freq[i], (1 - ramp) * base[i] + ramp * base[i] / 16, rtol=1e-6)
    assert factor == PUBLISHED_YARN["attention_factor"]
    # the attention factor the rule implies where none is given
    rule = {k: v for k, v in PUBLISHED_YARN.items()
            if k != "attention_factor"}
    assert abs(rule_frequencies(128, rule)[1] - factor) < 1e-12
    plain, one = rule_frequencies(128, {"rope_type": "default",
                                        "rope_theta": 500000})
    np.testing.assert_allclose(plain, base, rtol=1e-6)
    assert one == 1.0
    with pytest.raises(ValueError, match="rope_type"):
        rule_frequencies(128, {"rope_type": "ntk", "rope_theta": 1e4})


# ------------------------------------------------------- the full forward


@pytest.mark.parametrize("scan_layers", [True, False],
                         ids=["stacked", "layers_apart"])
def test_forward_logits_match_the_reference(scan_layers):
    cfg = mellum_debug(scan_layers=scan_layers)
    params = seeded(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(9), (2, 70), 0,
                                cfg.vocab_size)
    logits, routes = sys_forward(cfg, params, tokens)
    assert routes.shape == (cfg.num_layers, 2, 70, cfg.moe_top_k)
    want = ref.forward(params, tokens, hp_of(cfg), np.asarray(routes))
    assert rel_err(logits, want) < TOL
    assert rel_err(logits, ref.forward(params, tokens, hp_of(cfg))) < TOL


@pytest.mark.parametrize("n", [70, 64, 33], ids=[
    "ends_inside_a_block", "whole_blocks", "a_row_past_two_blocks"])
def test_a_long_forward_attends_its_windows_in_blocks_of_query_rows(
        monkeypatch, n):
    """Past ``_CACHED_QUERY_BLOCK`` rows a window layer without a cache
    attends a block of query rows at a time against the keys that block's
    windows reach (the check's pass GIVEN the routes runs 4.4k tokens beside
    the pools): the same logits, the same routes and the same gradient as
    the one masked [S, S] product."""
    from ray_tpu.models import transformer

    cfg = mellum_debug(num_layers=4)
    params = seeded(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, n), 0,
                                cfg.vocab_size)

    def loss(params):
        with jax.default_matmul_precision("highest"):
            return jnp.mean(forward(cfg, params, tokens) ** 2)

    whole, routes = sys_forward(cfg, params, tokens)
    whole_grad = jax.grad(loss)(params)["embed"]["table"]
    monkeypatch.setattr(transformer, "_CACHED_QUERY_BLOCK", 16)
    assert cfg.sliding_window > 16
    logits, blocked_routes = sys_forward(cfg, params, tokens)
    np.testing.assert_array_equal(routes, blocked_routes)
    assert rel_err(logits, whole) < TOL
    assert rel_err(logits, ref.forward(params, tokens, hp_of(cfg))) < TOL
    assert rel_err(jax.grad(loss)(params)["embed"]["table"],
                   whole_grad) < TOL


FAULTS = {
    "window_one_too_long": lambda c: dataclasses.replace(
        c, sliding_window=c.sliding_window + 1),
    "window_one_too_short": lambda c: dataclasses.replace(
        c, sliding_window=c.sliding_window - 1),
    "yarn_factor_dropped": lambda c: dataclasses.replace(
        c, rope_parameters={
            **{k: dict(r) for k, r in c.rope_parameters},
            "full_attention": {**c.rope_rule(ATTENTION), "factor": 1.0}}),
    "attention_factor_dropped": lambda c: dataclasses.replace(
        c, rope_parameters={
            **{k: dict(r) for k, r in c.rope_parameters},
            "full_attention": {**c.rope_rule(ATTENTION),
                               "attention_factor": 1.0}}),
    "sliding_rule_in_a_full_layer": lambda c: dataclasses.replace(
        c, rope_parameters={
            "sliding_attention": c.rope_rule(SLIDING),
            "full_attention": c.rope_rule(SLIDING)}),
    "weights_not_renormalized": lambda c: dataclasses.replace(
        c, moe_renormalize=False),
    "bfloat16": lambda c: dataclasses.replace(c, dtype=jnp.bfloat16),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_tolerance_refuses(toy, fault):
    cfg, params, tokens = toy
    bad = FAULTS[fault](cfg)
    logits, routes = sys_forward(bad, params, tokens)
    want = ref.forward(params, tokens, hp_of(cfg), np.asarray(routes))
    assert rel_err(logits, want) > 5 * TOL


def test_a_head_size_taken_from_the_width_is_refused(toy):
    """``head_dim`` left out is ``embed_dim // num_heads`` (16 here, as 72
    at the published widths): other shapes, so the weights do not even
    fit."""
    cfg, params, tokens = toy
    narrow = dataclasses.replace(cfg, head_dim=None)
    assert narrow.head_dim == 16
    want = init_params(narrow, jax.random.PRNGKey(0))
    assert (jax.tree.map(jnp.shape, want["blocks"]["p0"]["attn"])
            != jax.tree.map(jnp.shape, params["blocks"]["p0"]["attn"]))


# ------------------------------------------------- the contiguous cache


@pytest.mark.parametrize("n", [10, 24, 25, 47], ids=[
    "below_the_window", "at_the_window", "past_the_window", "twice_past"])
def test_prefill_and_decode_step_match_the_reference(toy, n):
    cfg, params, tokens = toy
    tokens = tokens[:, :n + 9]
    want = ref.forward(params, tokens, hp_of(cfg))
    got = harness.cached_logits(cfg, params, tokens[:, :-1], n,
                                length=tokens.shape[1])
    assert rel_err(got, want[:, n - 1:-1]) < TOL


def test_a_long_cached_prefill_attends_in_blocks_of_query_rows(monkeypatch):
    """Past ``_CACHED_QUERY_BLOCK`` query rows the cached prefill attends a
    block of them at a time (the cell's 4352-token check would otherwise
    hold gigabytes of scores): the same logits."""
    from ray_tpu.models import transformer

    cfg = mellum_debug(num_layers=4)
    params = seeded(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (1, 77), 0,
                                cfg.vocab_size)
    want = ref.forward(params, tokens, hp_of(cfg))[:, -1]
    monkeypatch.setattr(transformer, "_CACHED_QUERY_BLOCK", 16)
    with jax.default_matmul_precision("highest"):
        logits, _ = prefill(cfg, params, tokens,
                            init_caches(cfg, 1, tokens.shape[1] + 3))
    assert rel_err(logits, want) < TOL


# ------------------------------------------------------ the paged kernel


def dense_window_attention(q, k, v, lengths, window):
    """q [S,K,H,D] at positions lengths[s] + i against contiguous k, v
    [S,N,Hkv,D], by the mask alone."""
    S, K, H, D = q.shape
    group = H // k.shape[2]
    k, v = (jnp.repeat(a, group, axis=2) for a in (k, v))
    i = lengths[:, None, None] + jnp.arange(K)[None, :, None]
    j = jnp.arange(k.shape[1])[None, None, :]
    seen = j <= i
    if window is not None:
        seen = jnp.logical_and(seen, j > i - window)
    s = jnp.einsum("skhd,snhd->shkn", q, k) / np.sqrt(D)
    s = jnp.where(seen[:, None], s, -jnp.inf)
    return jnp.einsum("shkn,snhd->skhd", jax.nn.softmax(s, axis=-1), v)


@pytest.mark.parametrize("K", [1, 5, 16], ids=["step", "window5", "chunk"])
@pytest.mark.parametrize("window", [8, 24, None])
def test_paged_attention_under_a_window(K, window):
    """Kernel (interpreted) against its reference implementation and both
    against the mask alone, rows at contexts below, at and past the window,
    one row idle; the pages wholly behind each row's window hold NaN and are
    off its table."""
    S, H, Hkv, D, T, P = 4, 4, 2, 32, 4, 24
    keys = jax.random.split(jax.random.PRNGKey(K), 3)
    lengths = np.asarray([3, 23 if window else 40, -K, 61], np.int32)
    q = jax.random.normal(keys[0], (S, K, H, D), jnp.float32)
    k, v = (jax.random.normal(key, (S, P * T, Hkv, D), jnp.float32)
            for key in keys[1:])
    tables = (1 + np.arange(S * P, dtype=np.int32)).reshape(S, P)
    pool = lambda a: jnp.concatenate(
        [jnp.zeros((1, T, Hkv * D)), a.reshape(S * P, T, Hkv * D)])
    k_pool, v_pool = pool(k), pool(v)
    if window is not None:
        for s in range(S):
            behind = max(int(lengths[s]) - window + 1, 0) // T
            released = tables[s, :behind].copy()
            tables[s, :behind] = 0
            k_pool = k_pool.at[released].set(jnp.nan)
            v_pool = v_pool.at[released].set(jnp.nan)
    want = dense_window_attention(q, k, v, jnp.asarray(lengths), window)
    want = jnp.where((lengths + K > 0)[:, None, None, None], want, 0.0)
    out = {impl: paged_attention(q, k_pool, v_pool, jnp.asarray(tables),
                                 jnp.asarray(lengths), impl=impl,
                                 window=window)
           for impl in ("reference", "pallas")}
    for impl, got in out.items():
        assert np.isfinite(np.asarray(got)).all(), impl
        np.testing.assert_allclose(got, want, atol=2e-5, err_msg=impl)
    np.testing.assert_allclose(out["pallas"], out["reference"], atol=2e-6)


def test_a_window_keeps_the_blocks_within_half_of_it():
    # pages of 16 tokens, rows of 1 KB: 512-token blocks, window or none
    assert tile_sizes(1, 8, 16, 2112, 1024) == (32, 1)
    assert tile_sizes(1, 8, 16, 2112, 1024, 1024) == (32, 1)
    assert tile_sizes(512, 8, 16, 2112, 1024, 1024)[0] == 32
    # the toy: pages of 4, a window of 24 -> blocks of 2 pages
    assert tile_sizes(1, 2, 4, 64, 256, 24)[0] == 2
    with pytest.raises(ValueError, match="window"):
        paged_attention(jnp.zeros((1, 1, 2, 8)), jnp.zeros((2, 4, 16)),
                        jnp.zeros((2, 4, 16)), jnp.zeros((1, 2), jnp.int32),
                        jnp.zeros((1,), jnp.int32), window=0)


def test_streamed_tokens_under_a_window_by_hand():
    """Pages of 4, blocks of 2 pages (8 tokens), window 24, group 2. A step
    row at cursor 50 attends 27..50 (24 keys) and streams blocks 3..6 (32
    tokens); at cursor 10 it attends 0..10 and streams blocks 0..1."""
    args = (2, 4, 64, 256)
    assert streamed_tokens("pallas", 1, [50], 0, *args, 24) == (24, 32)
    assert streamed_tokens("pallas", 1, [10], 3, *args, 24) == (11, 16)
    assert streamed_tokens("pallas", 1, [50, 10], 0, *args, 24) == (35, 48)
    # the reference walks every row, idle ones too, over the longest walk
    assert streamed_tokens("reference", 1, [50, 10], 2, *args, 24) == (
        35, 4 * 4 * 8)
    # a chunk of 16 at cursor 40 (one query tile): the first row's window
    # opens at 17, the last row is at 55 -> blocks 2..6
    assert streamed_tokens("pallas", 16, [40], 0, *args, 24) == (39, 40)
    # without a window nothing changed: one block of 64 pages
    assert streamed_tokens("pallas", 1, [50], 0, *args) == (51, 256)


# ------------------------------------------------- the paged programs


class Pager:
    """The scheduler's bookkeeping for the two pools, by hand: a full pool
    whose tables only grow, and a window pool of which a slot holds the
    pages its window still covers. A released page is FILLED WITH NaN in
    every window layer's pool and never handed out again, so whatever read
    it would show."""

    def __init__(self, cfg, slots, T, P):
        self.cfg, self.T, self.P, self.slots = cfg, T, P, slots
        self.window_next = 1
        self.full = np.zeros((slots, P), np.int32)
        self.window = np.zeros((slots, P), np.int32)
        self.held = {s: [] for s in range(slots)}  # logical pages held
        self.peak = 0

    def ensure(self, caches, slot, cursor, upto):
        need = -(-upto // self.T)
        for j in range(need):
            if not self.full[slot, j]:
                self.full[slot, j] = 1 + slot * self.P + j
        first_kept = max(cursor - self.cfg.sliding_window + 1, 0) // self.T
        gone = [j for j in self.held[slot] if j < first_kept]
        if gone:
            pages = self.window[slot, gone].copy()
            self.window[slot, gone] = 0
            self.held[slot] = [j for j in self.held[slot] if j >= first_kept]
            caches = [dataclasses.replace(
                c, k=c.k.at[pages].set(jnp.nan), v=c.v.at[pages].set(jnp.nan))
                if kind == SLIDING else c
                for c, kind in zip(caches, self.cfg.kinds)]
        for j in range(max(self.held[slot], default=-1) + 1, need):
            if j >= first_kept:
                self.window[slot, j] = self.window_next
                self.window_next += 1
                self.held[slot].append(j)
        self.peak = max(self.peak, len(self.held[slot]))
        return caches

    def tables(self, slot=None):
        rows = slice(None) if slot is None else slot
        both = {ATTENTION: jnp.asarray(self.full[rows]),
                SLIDING: jnp.asarray(self.window[rows])}
        return both, both


def _paged(request):
    """Two prompts through the paged programs (``harness.paged_drive``), a
    pool a kind. Slot 1 takes a 53-token prompt in chunks of 16 (past the
    window, over three chunk boundaries, ending inside a chunk); slot 2 then
    a 20-token prompt (below the window) whose two chunks take slot 1's
    decode row along (the fused turn); then plain steps of both, slot 2
    crossing the window. Slots 0 and 3 hold no sequence. The pages are the
    ``Pager``'s: what it releases it poisons, so the drive poisons none."""
    cfg = mellum_debug(num_layers=4)
    slots, T, P = 4, 4, 64
    pager, full_pages = Pager(cfg, slots, T, P), []
    return dict(
        cfg=cfg, params=seeded(cfg), impl=request.param,
        tokens=jax.random.randint(jax.random.PRNGKey(9), (2, 80), 0,
                                  cfg.vocab_size),
        caches=init_paged_caches(cfg, slots * P + 1, T, P, window_pages=400),
        tables=pager.tables, ensure=pager.ensure, poisoned=False,
        after=lambda: full_pages.append(int((pager.full > 0).sum())),
        lengths={1: 53, 2: 20}, chunk=16, steps=8, moe_info=True,
        keep={"pager": pager, "full_pages": full_pages})


paged_run = harness.paged_fixture(_paged, impls=["reference", "pallas"])


@pytest.mark.parametrize("slot", [1, 2])
def test_paged_chunks_steps_and_fused_turns_match_the_reference(paged_run,
                                                                slot):
    run = paged_run
    cfg, n, end = run["cfg"], run["n"][slot], run["cursor"][slot]
    seq = run["tokens"][run["row"][slot]][None, :end]
    routes = np.concatenate(run["routes"][slot], 1)[:, None]
    assert routes.shape[2] == end
    got = harness.slot_logits(run, slot)
    want = ref.forward(run["params"], seq, hp_of(cfg), routes)[0]
    assert rel_err(got, want[n - 1:end]) < TOL
    own = ref.forward(run["params"], seq, hp_of(cfg))[0]
    assert rel_err(got, own[n - 1:]) < TOL


def test_the_window_pool_forgets_and_the_full_pool_grows(paged_run):
    run = paged_run
    cfg, pager = run["cfg"], run["pager"]
    # window + chunk tokens and a page, whatever the context
    assert pager.peak <= -(-(cfg.sliding_window + 16) // pager.T) + 1
    assert len(pager.held[1]) <= -(-cfg.sliding_window // pager.T) + 1
    assert run["full_pages"] == sorted(run["full_pages"])
    assert run["full_pages"][-1] == sum(
        -(-c // pager.T) for c in run["cursor"].values())
    # what was released was poisoned, in the window layers' pools alone
    for c, kind in zip(run["caches"], cfg.kinds):
        assert bool(jnp.isnan(c.k).any()) == (kind == SLIDING)


def test_the_paged_programs_refuse_what_they_cannot_run(toy):
    cfg = mellum_debug(num_layers=4)
    with pytest.raises(ValueError, match="window_pages"):
        init_paged_caches(cfg, 9, 4, 8)


# --------------------------------------------------------- the scheduler


def test_the_scheduler_serves_both_pools_and_releases_behind_the_window():
    """Through ``ContinuousScheduler``: five prompts of 9 to 70 tokens over
    three slots, 12 new tokens each. Every served token is the reference's
    choice or within TOL of it; the window pool never held more than
    ``window + chunk`` tokens and a page a slot while the full pool held
    whole contexts; the counters are what the cursors say."""
    from ray_tpu.serve._private.continuous import ContinuousScheduler

    cfg = mellum_debug(num_layers=4)
    params = seeded(cfg)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(9), (5, 80), 0,
                                           cfg.vocab_size))
    T, C, slots, new = 4, 16, 3, 12
    prompts = [tokens[i, :n].tolist()
               for i, n in enumerate((70, 9, 33, 24, 57))]
    sched = ContinuousScheduler(cfg, params, slots=slots, prefill_chunk=C,
                                arena_len=128, page_tokens=T,
                                attn="reference")
    try:
        served = serve(sched, prompts, new)
        stats = sched.stats()
        assert sched.compiled_programs() == 2
    finally:
        sched.shutdown()
    for prompt, out in zip(prompts, served):
        assert len(out) == new
        assert harness.near_the_references_best(
            lambda seq: ref.forward(params, seq, hp_of(cfg)), prompt, out,
            tol=TOL)
    a_slot = -(-(cfg.sliding_window + C) // T) + 1
    assert a_slot == 11
    assert sched._pools[1].arena.usable_pages == slots * a_slot
    assert 0 < stats["kv_peak_pages_in_use_window"] <= slots * a_slot
    # the longest context alone is more pages than a window slot may hold
    assert stats["kv_peak_pages_in_use_full"] > -(-81 // T) > a_slot
    assert stats["window_pages_released"] > 0
    assert stats["kv_pages_in_use_window"] == 0 == stats["pages_in_use"]
    assert 0 < stats["window_tokens_held"] < stats["window_tokens_unreleased"]
    # by work: three window layers and one full one; a step row at cursor c
    # reads min(c + 1, 24) keys a window layer and c + 1 a full one
    steps = [(len(p) + i) for p in prompts for i in range(new - 1)]
    assert stats["full_attn_step_keys"] == sum(c + 1 for c in steps)
    assert stats["window_attn_step_keys"] == 3 * sum(
        min(c + 1, 24) for c in steps)
    rows = [c for p in prompts for c in range(len(p))]
    assert stats["full_attn_chunk_pairs"] == sum(c + 1 for c in rows)
    assert stats["window_attn_chunk_pairs"] == 3 * sum(
        min(c + 1, 24) for c in rows)
    live = sum(len(p) for p in prompts) + (new - 1) * len(prompts)
    assert stats["moe_rows_routed"] == live * cfg.moe_top_k * cfg.num_layers


def test_the_scheduler_refuses_what_window_layers_cannot_have():
    from ray_tpu.serve._private.continuous import ContinuousScheduler

    cfg = mellum_debug(num_layers=4)
    params = init_params(cfg, jax.random.PRNGKey(0))
    kw = dict(slots=2, prefill_chunk=16, arena_len=64, page_tokens=4,
              attn="reference")
    with pytest.raises(ValueError, match="prefix_cache=True cannot serve a "
                                         "model with 'sliding_attention'"):
        ContinuousScheduler(cfg, params, prefix_cache=True, **kw)
    with pytest.raises(ValueError, match="speculative decoding cannot serve "
                                         "a model with 'sliding_attention'"):
        ContinuousScheduler(cfg, params, drafter=object(), **kw)
    sched = ContinuousScheduler(cfg, params, **kw)
    try:
        assert sched.stats().get("prefix_hits") is None  # no radix cache
        with pytest.raises(ValueError, match="exports no prefix"):
            sched.export_prefix([1, 2, 3, 4])
    finally:
        sched.shutdown()
    tables = {ATTENTION: jnp.zeros((2, 16), jnp.int32),
              SLIDING: jnp.zeros((2, 16), jnp.int32)}
    caches = init_paged_caches(cfg, 9, 4, 16, window_pages=9)
    # the verify program itself runs a window model (nothing is released
    # under it here); it is the scheduler that cannot rewind a release
    logits, _ = paged_verify_step(
        cfg, params, jnp.zeros((2, 3), jnp.int32), jnp.ones(2, jnp.int32),
        jnp.zeros(2, jnp.int32), tables, tables, caches, attn="reference")
    assert logits.shape == (2, 3, cfg.vocab_size)


# ------------------------------------- models without a window: unchanged

# sha256 of the StableHLO text the two paged programs lower to on the CPU at
# the parent of this change (commit 33fb262), by (preset, program, lane):
# made by ``lowered_digest`` below, run there. The kernel and the forward are
# shared with every serving cell, so a model without window layers must not
# see this change at all.
PARENT_JAX = "0.9.0"
PARENT_DIGESTS = {
    "moe_debug.step.reference":
        "072d3e0227df82a254d09d2bfa50e81c7085bc280bc5732b05dfa5100ef33c7b",
    "moe_debug.step.pallas":
        "4b18de840b120bd7614e6cc0a8efdcd4d3960454ac9d74b81e9de7cd097f1c4a",
    "moe_debug.chunk.reference":
        "ff18aa92e167de71142ed9852637e9fcd46e7bf3aa6eeaddfd6468d1914e6e65",
    "moe_debug.chunk.pallas":
        "865bc28790be483b18ac394f8065b73f58e39f36a3219bc054f38d168f064ed9",
    "llama_debug.step.reference":
        "4a9ac784f3a053a3eb028802d3497181c954336af59d47f21915b405248ecc44",
    "llama_debug.step.pallas":
        "ca072865e2dbf043c64f6107136c98c4f9f87da1605040de3aac9dfc2b2bc5a1",
    "llama_debug.chunk.reference":
        "02c8fc2a43cfcef7fc9f0f5220633d0f7a870ea32a66cf19fd0190b3c2d1dd3f",
    "llama_debug.chunk.pallas":
        "ab5662331b580970e5068e1d63d346ccfd26de0722376ea1b273ff88e29827e8",
}


def lowered_digest(preset, program, lane):
    from ray_tpu.serve._private.continuous import _program

    cfg = preset(max_seq_len=128)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    slots, T, P, C = 4, 8, 16, 16
    caches = jax.eval_shape(lambda: init_paged_caches(cfg, slots * P + 1, T,
                                                      P))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    u32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.uint32)
    kw = {"attn": lane}
    if cfg.mlp == "moe":
        kw["moe_info"] = True
    if program == "step":
        fn = _program(paged_decode_step, "paged_decode_step", cfg, **kw)
        args = (params, i32(slots), i32(slots), i32(slots), i32(slots, P),
                i32(slots, P), caches, f32(slots), u32(slots))
    else:
        fn = _program(paged_prefill_into_slot, "paged_prefill_chunk", cfg,
                      **kw)
        step = StepRows(i32(slots), i32(slots), i32(slots, P), i32(slots, P),
                        f32(slots), u32(slots))
        args = (params, i32(1, C), i32(), i32(), i32(P), i32(P), caches,
                i32(slots), i32(), f32(), u32(), step, i32())
    text = jax.jit(fn, donate_argnums=(6,)).lower(*args).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("lane", ["reference", "pallas"])
@pytest.mark.parametrize("program", ["step", "chunk"])
@pytest.mark.parametrize("preset", [moe_debug, llama_debug],
                         ids=lambda p: p.__name__)
def test_without_a_window_the_paged_programs_lower_to_the_parents_text(
        preset, program, lane):
    if jax.__version__ != PARENT_JAX:
        pytest.skip(f"the parent's digests were made under jax {PARENT_JAX}")
    assert lowered_digest(preset, program, lane) == PARENT_DIGESTS[
        f"{preset.__name__}.{program}.{lane}"]
