"""The 'indexed_latent_attention' kind where it meets pages (ISSUE 61): the
op ``ops.picked_latent_attention`` against plain latent attention and
against a sort, the paged chunk, step and fused turn by the ``jax.numpy``
path and by the kernel interpreted, two faults of the pool, the scheduler's
counters, a spliced prefix and what the scheduler refuses — float32 on the
CPU at a toy size against ``perfbench/reference/deepseek_v32.py``, selection
and routes EQUAL. The model's own forwards, the named faults of the
reference and the router are ``tests/test_deepseek_v32.py``'s; two files so
that ``--dist loadfile`` spreads them.
"""

from __future__ import annotations

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench.reference import deepseek_v32 as ref  # noqa: E402
from ray_tpu.models.decode import (init_paged_caches,  # noqa: E402
                                   init_slot_caches)
from ray_tpu.models.presets import deepseek_v32_debug  # noqa: E402
from ray_tpu.models.transformer import (INDEXED_LATENT,  # noqa: E402
                                        init_params)
from ray_tpu.ops.indexed_attention import IndexerSizes, index_row  # noqa: E402
from ray_tpu.ops.latent_attention import join, latent_attention  # noqa: E402
from ray_tpu.ops.picked_latent_attention import (  # noqa: E402
    _attend_chunk, _attend_reference, _segments, picked_latent_attention,
    picked_rows)
from tests import model_harness as harness  # noqa: E402
from tests.model_harness import rel  # noqa: E402
from tests.test_deepseek_v32 import (TOL, hp_of,  # noqa: E402
                                     near_the_references_best, stirred)


# ---------------------------------------------------------------- the op


def _op_case(B, S, lengths, topk, H=4, rank=32, rope=8, Hi=4, Di=16, T=4,
             P=20, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    N = B * P + 1
    q_c = jax.random.normal(ks[0], (B, S, H, rank))
    q_r = jax.random.normal(ks[1], (B, S, H, rope))
    qi = jax.random.normal(ks[2], (B, S, Hi, Di))
    w = jax.random.normal(ks[3], (B, S, Hi))
    pool = join(jax.random.normal(ks[4], (N, T, rank)),
                jax.random.normal(ks[5], (N, T, rope)))
    ik = index_row(jax.random.normal(ks[6], (N, T, Di)))
    tables = jnp.asarray(
        1 + np.random.default_rng(seed).permutation(B * P).reshape(B, P),
        jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    positions = lengths[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    sizes = IndexerSizes(indexer_num_heads=Hi, indexer_head_dim=Di,
                         topk=topk)
    return (q_c, q_r, qi, w, pool, ik, tables, positions, lengths, sizes)


@pytest.mark.parametrize("impl,B,S,lengths", [
    ("pallas", 3, 1, [7, 30, -1]), ("pallas", 1, 24, [9]),
    ("pallas", 3, 20, [3, -20, 40]), ("reference", 2, 70, [0, 5])])
def test_a_context_within_topk_is_plain_latent_attention(impl, B, S,
                                                         lengths):
    """Rows whose context is no longer than ``topk`` attend all of it: the
    op equals ``ops.latent_attention``, whatever the index scores."""
    *args, sizes = _op_case(B, S, lengths, topk=96)
    q_c, q_r, qi, w, pool, ik, tables, positions, lens = args
    with jax.default_matmul_precision("highest"):
        got, take = picked_latent_attention(
            *args, sizes, sm_scale=0.17, impl=impl, return_selected=True)
        want = latent_attention(q_c, q_r, pool, tables, lens, sm_scale=0.17,
                                impl="reference")
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 2e-6
    seen = (np.arange(take.shape[-1])[None, None]
            <= np.asarray(positions)[..., None])
    live = (np.asarray(lens) + S > 0)[:, None, None]
    assert (np.asarray(take) == (seen & live)).all()


@pytest.mark.parametrize("impl", ["pallas"])
def test_the_op_attends_what_a_sort_would_pick(impl):
    """Past ``topk``: against dense scores, a stable sort and a masked
    softmax over the same pool, ties and all (index scores quantised)."""
    args = _op_case(2, 40, [30, 3], topk=16, seed=3)
    q_c, q_r, qi, w, pool, ik, tables, positions, lens, sizes = args
    qi, w = jnp.round(qi * 2) / 2, jnp.round(w * 2) / 2
    ik = jnp.round(ik * 2) / 2
    args = (q_c, q_r, qi, w, pool, ik, tables, positions, lens, sizes)
    with jax.default_matmul_precision("highest"):
        got, take = picked_latent_attention(
            *args, sm_scale=0.17, impl=impl, return_selected=True)
        ctx = take.shape[-1]
        rows = jnp.pad(pool[tables].reshape(2, -1, pool.shape[-1]),
                       ((0, 0), (0, ctx - 80), (0, 0)))
        keys = jnp.pad(ik[tables].reshape(2, -1, ik.shape[-1]),
                       ((0, 0), (0, ctx - 80), (0, 0)))[..., :16]
        scores = jnp.einsum("bsh,bshn->bsn", w, jnp.maximum(
            jnp.einsum("bshd,bnd->bshn", qi, keys), 0.0))
        want_take = np.stack([np.asarray(ref.select_block(
            scores[b:b + 1], int(lens[b]), 16))[0] for b in range(2)])
        assert (np.asarray(take) == want_take).all()
        q = jnp.concatenate([q_c, q_r], -1)
        s = jnp.einsum("bshw,bnw->bshn", q, rows[..., :40]) * 0.17
        p = jax.nn.softmax(jnp.where(want_take[:, :, None], s, -jnp.inf), -1)
        want = jnp.einsum("bshn,bnr->bshr", p, rows[..., :32])
    assert np.abs(np.asarray(got) - np.asarray(want)).max() <= 5e-6
    assert (want_take.sum(-1) == np.minimum(
        np.asarray(positions) + 1, 16)).all()


def test_picked_rows_is_the_choice_in_order():
    rng = np.random.default_rng(1)
    take = rng.random((9, 640)) < 0.15
    take[3] = False
    take[4] = False
    take[4, :5] = True
    take[5] = False
    take[5, 639] = True
    at, count = picked_rows(jnp.asarray(take), 128)
    for r in range(9):
        want = np.nonzero(take[r])[0][:128]
        assert int(count[r]) == take[r].sum()
        assert (np.asarray(at[r])[:len(want)] == want).all()
        assert not np.asarray(at[r])[len(want):].any()


@pytest.mark.parametrize("case", ["one_segment", "two_segments", "bf16"])
def test_the_chunk_kernel_is_the_reference_over_the_gathered_run(case):
    """``_attend_chunk`` (the slot's pages copied into the kernel's scratch,
    the chosen rows moved there) against ``_attend_reference`` over XLA's
    gather of the same rows: three slots' chunks across pages of 4 through
    permuted tables — one past ``topk`` from its first query, one that
    starts at 0 (a choice shorter than the run), one that attends nothing
    and returns zeros — every page behind a slot's reach filled with NaN,
    the dead slot's whole table too. ``two_segments``: a budget that holds
    half the context, so each query's choice is split where the segments
    meet and the softmax merged behind the two calls."""
    dtype = jnp.bfloat16 if case == "bf16" else jnp.float32
    B, S, H, rank, rope, T, P, topk = 3, 24, 4, 32, 8, 4, 24, 40
    lengths = np.asarray([70, 0, -S])
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    rng = np.random.default_rng(5)
    tables = 1 + rng.permutation(B * P).reshape(B, P)
    pool = join(jax.random.normal(ks[0], (B * P + 1, T, rank)),
                jax.random.normal(ks[1], (B * P + 1, T, rope))).astype(dtype)
    W, width, context = pool.shape[-1], 128, 128   # the table's 96 in lanes
    last = np.where(lengths + S > 0, lengths + S - 1, -1)
    reached = np.concatenate([[0]] + [tables[b, :last[b] // T + 1]
                                      for b in range(B) if last[b] >= 0])
    pool = pool.at[np.setdiff1d(np.arange(B * P + 1), reached)].set(jnp.nan)
    take = np.zeros((B, S, context), bool)
    for b in range(B):
        for i in range(S):
            t = lengths[b] + i
            if t >= 0:
                take[b, i, rng.permutation(t + 1)[:topk]] = True
    at, count = picked_rows(jnp.asarray(take.reshape(B * S, context)), width)
    q = jax.random.normal(ks[2], (B, S, H, W)).astype(dtype)
    budget = 1 << 30
    if case == "two_segments":
        whole = _segments(context, T, width, H, W * 4, budget)[2]
        budget = whole - context * W * 4 // 2
        assert _segments(context, T, width, H, W * 4, budget)[:2] == (2, 64)
    with jax.default_matmul_precision("highest"):
        got = _attend_chunk(q, at.reshape(B, S, width), count.reshape(B, S),
                            pool, jnp.asarray(tables, jnp.int32),
                            jnp.asarray(last, jnp.int32), context, rank,
                            True, "picked_latent_chunk_attention",
                            budget=budget)
        rows = np.where(np.arange(width)[None] < np.asarray(count)[:, None],
                        tables[np.repeat(np.arange(B), S)[:, None],
                               np.asarray(at) // T] * T + np.asarray(at) % T,
                        0)
        want = _attend_reference(q.reshape(B * S, H, W),
                                 pool.reshape(-1, W)[rows], count, rank)
    got = np.asarray(got, np.float32).reshape(B * S, H, rank)
    assert np.isfinite(got).all() and not got[2 * S:].any()
    assert (np.asarray(count)[:2 * S] == np.minimum(
        (lengths[:2, None] + np.arange(S) + 1).reshape(-1), topk)).all()
    tol = 2e-2 if case == "bf16" else 5e-6
    assert np.abs(got - np.asarray(want, np.float32)).max() <= tol


def test_the_segments_follow_the_budget_and_the_rows_bytes():
    """From the shapes alone: the cell's context (66,048 rows of 1,280 B
    under 128 heads) is ONE segment under the kernel's limit, the model's
    own (163,840) is two of whole pages and tiles, and either way the
    kernel asks for less than the limit."""
    from ray_tpu.ops.latent_attention import _VMEM_LIMIT

    budget = _VMEM_LIMIT - (8 << 20)
    for context, segments in ((66_048, 1), (163_840, 2)):
        n, rows, vmem = _segments(context, 16, 2048, 128, 1280, budget)
        assert n == segments and n * rows >= context > (n - 1) * rows
        assert rows % 16 == 0 and vmem <= budget


def test_the_kernels_counters_follow_the_chunks_positions():
    """``picked_rows_in_kernel`` and ``picked_context_tokens_copied`` by
    position, beside ``picked_chosen_pairs``: a chunk's chosen rows, and
    the whole pages up to its last position (its padding's too) a layer;
    nothing for a step, and nothing where the kernel does not run."""
    from ray_tpu.serve._private.work import Work

    cfg = deepseek_v32_debug(num_layers=2)
    L, topk = cfg.num_layers, cfg.indexer.topk
    for lane in ("pallas", "reference"):
        work = Work(cfg, slots=3, page_tokens=4, pages_per_slot=24,
                    lane=lane, itemsize=2)
        work.record(16, [30], real=10)     # positions 30 .. 39 of 30 .. 45
        work.record(1, [50, 7], idle_rows=1)
        work.record(16, [80], real=16)     # the last page is the table's
        stats = work.stats()
        chunks = L * (10 * topk + 16 * topk)
        assert stats["picked_chosen_pairs"] == chunks + L * (topk + 8)
        if lane == "reference":
            chunks = 0
        assert stats["picked_rows_in_kernel"] == chunks
        assert stats["picked_context_tokens_copied"] == (
            L * 4 * (12 + 24) if chunks else 0)


# --------------------------------------------------------- the paged programs


def _paged(request):
    """Two prompts through the paged programs (``harness.paged_drive``).
    Slot 1 takes a 53-token prompt in chunks of 16 (over three chunk
    boundaries, ending inside a chunk, past ``topk`` 24); slot 2 then a
    33-token prompt (a page's first token last) whose chunks take slot 1's
    decode row along (the fused turn); then plain steps of both. Slots 0
    and 3 hold no sequence, and every page no table names is FILLED WITH
    NaN in both arrays of every layer's pool, as a released page would be:
    whatever read one would show."""
    cfg = deepseek_v32_debug()
    slots, T, P = 4, 4, 24
    return dict(
        cfg=cfg, params=stirred(cfg), impl=request.param,
        tokens=jax.random.randint(jax.random.PRNGKey(9), (2, 80), 0,
                                  cfg.vocab_size),
        caches=init_paged_caches(cfg, slots * P + 1 + 8, T, P),
        tables=harness.slot_tables(slots, P, (1, 2)),
        lengths={1: 53, 2: 33}, chunk=16, steps=6, moe_info=True,
        selected=True)


paged_run = harness.paged_fixture(_paged, impls=["reference", "pallas"])


@pytest.mark.parametrize("slot", [1, 2])
def test_paged_chunks_steps_and_fused_turns_match_the_reference(paged_run,
                                                                slot):
    run = paged_run
    cfg, n, end = run["cfg"], run["n"][slot], run["cursor"][slot]
    seq = run["tokens"][run["row"][slot]][None, :end]
    routes = np.concatenate(run["routes"][slot], 1)[:, None]
    picked = np.concatenate(run["picked"][slot], 1)[:, None]
    assert routes.shape[2] == end == picked.shape[2]
    assert all(r["routes"].shape[0] == cfg.expert_layers
               for r in run["info"])
    got = harness.slot_logits(run, slot)
    # the reference on ITS OWN selection and routes: both equal the
    # program's, ties and all
    want, took, taken = ref.forward_and_choices(run["params"], seq,
                                                hp_of(cfg))
    assert (took == routes).all()
    assert (picked[..., :end] == taken).all() and not picked[
        ..., end:].any()
    assert rel(got, want[0][n - 1:]) <= TOL


def test_the_paged_programs_left_the_poisoned_pages_alone(paged_run):
    assert all(set(harness.pools(c)) == {"ckr", "ik"}
               for c in paged_run["caches"])
    harness.poisoned_pages_left_alone(paged_run)


@pytest.mark.parametrize("fault", ["an_index_key_never_written",
                                   "a_released_page_read"])
def test_a_fault_of_the_pool_is_refused(paged_run, fault):
    """One more step of slot 1 on the pools the run left, once as they are
    and once with the fault planted: index keys of a page the table names
    zeroed (as if never written: the indexer picks other tokens), or that
    page's latents poisoned as a released page is."""
    run = paged_run
    if run["impl"] != "reference":
        pytest.skip("the pools' faults are read once, by the plain path")
    cfg, slot = run["cfg"], 1
    end = run["cursor"][slot]
    seq = run["tokens"][run["row"][slot]][None, :end + 1]
    want = ref.forward(run["params"], seq, hp_of(cfg))[0][end]
    page = int(run["tables"][slot, 2])
    caches = run["caches"]
    if fault == "an_index_key_never_written":
        # every page of the slot's context: the scores are then all alike
        # and the choice is the first 24 tokens
        pages = np.asarray(run["tables"][slot, :end // 4])
        bad = [dataclasses.replace(c, ik=c.ik.at[pages].set(0.0))
               for c in caches]
    else:
        bad = [dataclasses.replace(c, ckr=c.ckr.at[page].set(jnp.nan))
               for c in caches]
    active = np.zeros(4, np.int32)
    cursors = np.zeros(4, np.int32)
    active[slot], cursors[slot] = 1, end
    ids = np.zeros(4, np.int32)
    ids[slot] = run["tokens"][run["row"][slot], end]
    errs = {}
    with jax.default_matmul_precision("highest"):
        for name, pools in (("sound", caches), (fault, bad)):
            out = run["step"](
                run["params"], jnp.asarray(ids), active, cursors,
                run["tables"], run["tables"], list(pools),
                np.zeros(4, np.float32), np.zeros(4, np.uint32))
            logits = np.asarray(out[3])[slot]
            errs[name] = (np.inf if not np.isfinite(logits).all()
                          else rel(logits, want))
    assert errs["sound"] <= TOL < errs[fault], errs


# ------------------------------------------------------------ the scheduler


def test_the_scheduler_serves_the_kind_and_counts_its_work():
    from ray_tpu.serve._private.work import token_bytes

    cfg = deepseek_v32_debug(moe_held_count=8, num_layers=2)
    params = stirred(cfg)
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
    L, topk = cfg.num_layers, 24
    rows = [c for p in prompts for c in range(len(p) + new - 1)]
    steps = [len(p) + i for p in prompts for i in range(new - 1)]
    assert stats["picked_index_pairs"] == L * sum(c + 1 for c in rows)
    assert stats["picked_step_index_pairs"] == L * sum(c + 1 for c in steps)
    assert stats["picked_chosen_pairs"] == L * sum(
        min(c + 1, topk) for c in rows)
    assert stats["picked_step_chosen_pairs"] == L * sum(
        min(c + 1, topk) for c in steps)
    # a chosen row is 32 + 8 float32 values; an index key 16
    assert stats["picked_latent_bytes"] == 160 * stats["picked_chosen_pairs"]
    chunk_ends = [min(c0 + 16, len(p)) for p in prompts
                  for c0 in range(0, len(p), 16)]
    assert stats["picked_index_key_bytes"] == L * 64 * (
        sum(c + 1 for c in steps) + sum(chunk_ends))
    # the 'reference' lane gathers: the chunk's kernel moved nothing
    assert not stats["picked_rows_in_kernel"]
    assert not stats["picked_context_tokens_copied"]
    assert token_bytes(cfg, INDEXED_LATENT, 4) == 512
    assert stats["fused_turns"] > 0 and stats["pages_in_use"] == 0
    for other in ("latent_tokens_context", "indexed_tokens_context"):
        assert other not in stats  # another kind's
    assert stats["attn_tokens_attended"] == stats["attn_tokens_fetched"] > 0
    # the experts: every live row of every EXPERT layer-call chose top-k of
    # 16, of which the 8 held took theirs; the shared expert took every row
    chosen = cfg.expert_layers * cfg.moe_top_k * len(rows)
    assert stats["moe_routes_chosen"] == chosen
    assert 0 < stats["moe_rows_routed"] < chosen
    assert stats["moe_shared_rows"] == cfg.expert_layers * len(rows)


def test_a_spliced_prefix_continues_to_the_same_logits():
    """The prefix cache serves the kind: the second request splices the
    first's pages — latents AND index keys under one table, a context past
    ``topk`` — and answers what the reference, which knows no cache, ranks
    best at every position."""
    cfg = deepseek_v32_debug(num_layers=2)
    params = stirred(cfg)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (96,), 0,
                                           cfg.vocab_size)).tolist()
    first, second = tokens[:64], tokens[:48] + tokens[70:90]
    answers, stats = harness.served(
        cfg, params, (first, second), 6, together=False, prefix_cache=True,
        slots=2, prefill_chunk=16, arena_len=96, page_tokens=4)
    assert stats["prefix_hits"] == 1
    assert stats["prefix_hit_tokens"] >= 44 > 24
    for prompt, out in zip((first, second), answers):
        assert near_the_references_best(cfg, params, prompt, out)


def test_the_scheduler_refuses_what_the_kind_cannot_have():
    from ray_tpu.serve._private.continuous import ContinuousScheduler

    cfg = deepseek_v32_debug()
    params = init_params(cfg, jax.random.PRNGKey(0))
    kw = dict(slots=2, prefill_chunk=16, arena_len=64, page_tokens=4,
              attn="reference")
    with pytest.raises(ValueError, match="speculative decoding cannot serve "
                                         "a model with "
                                         "'indexed_latent_attention'"):
        ContinuousScheduler(cfg, params, drafter=object(), **kw)
    with pytest.raises(ValueError, match="holds keys and values alone"):
        init_slot_caches(cfg, 2, 64)
    sched = ContinuousScheduler(cfg, params, prefix_cache=True, **kw)
    try:
        with pytest.raises(ValueError, match="rotated keys and index keys"):
            sched.export_prefix([1, 2, 3, 4])
    finally:
        sched.shutdown()
