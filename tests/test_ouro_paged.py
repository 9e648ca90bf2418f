"""Ouro's looped stack through the PAGED programs on the CPU (ISSUE 65): one
layer's body under a loop over the pass and the layer, a page holding its
tokens' keys and values once a (pass, layer) under the one table
(``decode.LoopPagedKVCache``), the kernel told which pool it reads. Prefill
in chunks, fused turns and steps against ``perfbench/reference/ouro.py`` on
logits and exits; pass t attends what pass t wrote; the kernel's stacked
form against a pool alone; the scheduler's normal path (prefix cache served,
drafter refused, ``loop_*`` counted). Drives are ``tests/model_harness.py``'s.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import ouro as reference
from ray_tpu.models import presets
from ray_tpu.models.decode import (_paged_forward_loop, _Rows,
                                   init_paged_caches, init_slot_caches)
from ray_tpu.ops.paged_attention import paged_attention
from ray_tpu.serve._private import paging
from ray_tpu.serve._private.work import Work, token_bytes
from tests import model_harness as mh
from tests.test_ouro import hp_of, spread

SLOTS, PAGES, T = 4, 6, 8
PASSES, LAYERS = 4, 2


@pytest.fixture(scope="module")
def cfg():
    return presets.ouro_debug(loop_passes=PASSES, num_layers=LAYERS,
                              exit_threshold=0.5)


def setup(request):
    cfg = request.getfixturevalue("cfg")
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0,
                                           256))
    return dict(
        cfg=cfg, params=spread(cfg), tokens=tokens,
        caches=init_paged_caches(cfg, 1 + SLOTS * PAGES, T, PAGES),
        tables=mh.slot_tables(SLOTS, PAGES, [1, 3]),
        lengths={1: 30, 3: 21}, chunk=16, steps=3, impl="reference",
        loop_info=True)


paged_run = mh.paged_fixture(setup)


def test_paged_chunks_fused_turns_and_steps_against_the_reference(paged_run):
    """Two slots' prompts in chunks of 16 (30 and 21 tokens: the last
    chunks are padded), the second's taking the first's decode row along,
    then three steps: every sampled row's logits within 2e-5 of the
    reference's, every live row's returned exit pass the reference's, and
    no program wrote a page no table names (NaN in all 8 pools of it)."""
    run, cfg = paged_run, paged_run["cfg"]
    want, exits, _ = jax.jit(lambda p, t: reference.forward_and_exits(
        p, t, hp_of(cfg)))(run["params"], jnp.asarray(run["tokens"]))
    exits = np.asarray(exits)
    for slot, row in run["row"].items():
        got = mh.slot_logits(run, slot)
        first = run["n"][slot] - 1
        assert mh.rel(got, want[row, first:first + len(got)]) < 2e-5
    mh.poisoned_pages_left_alone(run)
    (pool,) = run["caches"]
    assert pool.k.shape == (1 + SLOTS * PAGES, PASSES * LAYERS, T, 64)
    # the last three programs are the steps: rows 1 and 3 live, at the
    # positions behind their prompts and the rows the chunks took along
    told = [np.asarray(i["exit_pass"]) for i in run["info"]]
    assert all(t.shape == (1 + SLOTS,) for t in told[:-3])
    at = {1: 30 + 2, 3: 21}  # slot 1 rode in slot 3's two chunks
    for n, t in enumerate(told[-3:]):
        assert t.shape == (SLOTS,) and not t[[0, 2]].any()
        for slot, row in run["row"].items():
            assert t[slot] == exits[row, at[slot] + n]
    # the drive's chunks sample for nobody (``slot`` -1): exit 0; slot 3's
    # two took slot 1's row along, live at positions 30 and 31
    assert all(t[0] == 0 for t in told[:-3])
    for n, t in enumerate(told[2:4]):
        assert t[1 + 1] == exits[run["row"][1], 30 + n] and t[1:].sum() == \
            t[1 + 1]


def test_pass_t_attends_what_pass_t_wrote(cfg):
    """A step over a context whose pools of ONE pass were moved: the passes
    before it come out bitwise as they were (they never read that pass's
    pools), that pass does not."""
    params = spread(cfg)
    tables = jnp.asarray(mh.slot_tables(SLOTS, PAGES, [1]))
    caches = [jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(3), a.shape, a.dtype),
        init_paged_caches(cfg, 1 + SLOTS * PAGES, T, PAGES)[0])]
    active = jnp.asarray([0, 1, 0, 0], jnp.int32)
    cursors = jnp.asarray([0, 19, 0, 0], jnp.int32)

    @jax.jit
    def states(caches):
        rows = _Rows(jnp.full((SLOTS, 1), 7, jnp.int32), cursors[:, None],
                     jnp.where(active > 0, cursors, -1), tables, tables,
                     active[:, None] > 0, active=active)
        return _paged_forward_loop(cfg, params, [rows], caches, "reference",
                                   lambda x: x)[0][:, 1, 0]

    base = np.asarray(states(caches))
    assert base.shape == (PASSES, cfg.embed_dim)
    for t in (1, PASSES - 1):
        pools = slice(t * LAYERS, (t + 1) * LAYERS)
        moved = [jax.tree.map(lambda a: a.at[:, pools].add(1.0), caches[0])]
        got = np.asarray(states(moved))
        assert np.array_equal(got[:t], base[:t])
        assert not np.allclose(got[t], base[t], atol=1e-3)


def test_the_kernel_reads_the_pool_it_is_told_where_it_lies():
    """``paged_attention(pool_index=)`` over a stack ``[N, pools, T, Hkv *
    D]``, the kernel (interpreted) and the reference: what the same call
    over that pool ALONE gives — a chunk's rows past a block's end, a row at
    position 0, an idle row (zeros)."""
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    N, pools, W, which = 9, 3, 4 * 16, 2
    k, v = (jax.random.normal(kk, (N, pools, T, W), jnp.float32)
            for kk in key[:2])
    tables = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8], [0, 0, 0, 0]])
    q = jax.random.normal(key[2], (3, 8, 4, 16), jnp.float32)
    lengths = jnp.asarray([9, 0, -8], jnp.int32)
    alone = paged_attention(q, k[:, which], v[:, which], tables, lengths,
                            impl="reference")
    assert not np.asarray(alone[2]).any()
    for impl in ("reference", "pallas"):
        got = jax.jit(lambda w, impl=impl: paged_attention(
            q, k, v, tables, lengths, impl=impl, pool_index=w))(
                jnp.int32(which))
        np.testing.assert_allclose(got, alone, atol=2e-6)
    with pytest.raises(ValueError, match="head mismatch"):
        paged_attention(q, k, v, tables, lengths)  # a stack, no index


def test_served_through_the_scheduler_prefix_cache_and_counters(cfg):
    """The normal path: ``ContinuousScheduler`` with the radix prefix cache
    over the stacked pools. Three requests, the third the first's prompt
    again: every stream is the sequential cache's greedy text, the repeat is
    SPLICED (its pages carry every pass's keys and values) and answered as
    the first was, and the stats show the loop's counters — passes x program
    runs, layer applications, the sampled rows by exit pass, every one of
    them a token that was emitted or discarded — for this model and for no
    other; the drafter and its slot arena are refused."""
    params = spread(cfg)
    rng = np.random.default_rng(5)
    first = rng.integers(1, 256, 37).tolist()
    prompts = [first, rng.integers(1, 256, 20).tolist(), first]
    kw = dict(slots=2, prefill_chunk=16, arena_len=64, page_tokens=T,
              prefix_cache=True)
    out, stats = mh.served(cfg, params, prompts, 6, together=False, **kw)
    assert out[2] == out[0] == mh.oracle(cfg, params, first, 6, length=64)
    assert stats["prefix_hit_tokens"] == 32  # the repeat's four whole pages
    runs = (stats["decode_steps"] + stats["prefill_chunks"]
            - stats["fused_turns"])
    assert stats["loop_passes"] == PASSES * runs
    assert stats["loop_layer_calls"] == PASSES * LAYERS * runs
    exits = [stats[f"loop_exit_pass_{t}"] for t in range(1, PASSES + 1)]
    assert sum(exits) == stats["tokens_generated"] + stats["discarded_rows"]
    assert sum(e > 0 for e in exits) >= 2
    assert token_bytes(cfg, "attention", 4) == 2 * 64 * 4 * PASSES
    sizes = dict(slots=2, page_tokens=T, pages_per_slot=8, lane="reference",
                 itemsize=4)
    plain = Work(presets.llama_debug(), **sizes)
    assert not [k for k in plain.stats() if k.startswith("loop_")]
    assert plain.program_keywords == {}
    assert Work(cfg, **sizes).program_keywords == {"loop_info": True}
    refused = paging.cannot_continue(cfg, ())
    assert set(refused) == {"drafter"} and refused is paging._REFUSALS["loop"]
    from ray_tpu.serve._private.continuous import ContinuousScheduler
    with pytest.raises(ValueError, match="one \\(K, V\\) a layer and token"):
        ContinuousScheduler(cfg, params, drafter=object(), **kw)
    with pytest.raises(ValueError, match="loop_passes=4"):
        init_slot_caches(cfg, 2, 64)
    # norms behind the sublayers are the looped body's alone
    with pytest.raises(ValueError, match="output_norms without loop_passes"):
        init_paged_caches(presets.ouro_debug(loop_passes=1), 9, T, 4)
