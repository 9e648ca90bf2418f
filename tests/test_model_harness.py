"""The harness under the models' tests (``tests/model_harness.py``, ISSUE 63)
held by cases of its own, on ``llama_debug``: its comparison refuses a program
that read a page no table names, its oracle is not the scheduler's echo, and
its memos hand back one compiled program a key."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama_debug
from ray_tpu.models.decode import init_paged_caches
from ray_tpu.serve._private.continuous import ContinuousScheduler
from tests import model_harness as harness

SLOTS, T, P = 4, 4, 16


@pytest.fixture(scope="module")
def toy():
    cfg = llama_debug()
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 40), 0,
                                cfg.vocab_size)
    return cfg, harness.seeded(cfg), tokens


def _drive(toy, tables, caches, **kw):
    cfg, params, tokens = toy
    return harness.paged_drive(
        cfg, params, tokens, caches, tables, lengths={1: 21, 2: 9}, chunk=8,
        steps=3, impl="reference", **kw)


def test_a_read_of_a_page_no_table_names_is_refused(toy):
    """Sound, the drive is the contiguous cache's logits and leaves its
    poison where it put it. With ONE entry of slot 1's read table pointed
    at a poisoned page the programs' logits are not finite, and the
    drive's comparison refuses them before any tolerance is asked."""
    cfg, params, tokens = toy
    honest = harness.slot_tables(SLOTS, P, (1, 2))
    fresh = init_paged_caches(cfg, SLOTS * P + 1 + 8, T, P, jnp.float32)
    sound = _drive(toy, honest, fresh)
    assert len(sound["poisoned"]) == 2 * P + 8
    for slot, row in sound["row"].items():
        end = sound["cursor"][slot]
        want = harness.cached_logits(cfg, params, tokens[row:row + 1, :end],
                                     sound["n"][slot], dtype=jnp.float32)[0]
        assert harness.rel(harness.slot_logits(sound, slot), want) <= 1e-4
    harness.poisoned_pages_left_alone(sound)

    astray = honest.copy()
    astray[1, 2] = sound["poisoned"][3]  # positions 8..11 of slot 1
    read, write = jnp.asarray(astray), jnp.asarray(honest)
    tables = lambda slot=None: ((read, write) if slot is None
                                else (read[slot], write[slot]))
    bad = _drive(toy, tables, harness.poison(fresh, sound["poisoned"]),
                 poisoned=False)
    with pytest.raises(AssertionError, match="not finite"):
        harness.slot_logits(bad, 1)
    harness.slot_logits(bad, 2)  # the other slot read its own pages


def test_an_oracle_handed_a_wrong_token_disagrees_with_the_stream(toy):
    cfg, params, tokens = toy
    prompt = np.asarray(tokens[0, :13]).tolist()
    sched = ContinuousScheduler(cfg, params, slots=2, prefill_chunk=8,
                                arena_len=64, page_tokens=T,
                                attn="reference")
    try:
        served, = harness.serve(sched, [prompt], 12)
    finally:
        sched.shutdown()
    assert served == harness.oracle(cfg, params, prompt, 12)
    wrong = prompt[:-1] + [(prompt[-1] + 1) % cfg.vocab_size]
    assert served != harness.oracle(cfg, params, wrong, 12)
    # remembered: a shorter ask is a prefix of what was computed
    assert harness.oracle(cfg, params, prompt, 5) == served[:5]


def test_the_memos_hand_back_one_program_a_key(toy):
    cfg = toy[0]
    one = harness.paged_programs(cfg, attn="reference", logits=True)
    assert harness.paged_programs(cfg, logits=True, attn="reference") is one
    other = harness.paged_programs(cfg, attn="pallas", logits=True)
    assert other is not one and other[0] is not one[0]
    assert harness.paged_programs(cfg, attn="reference") is not one
    assert harness.cached_programs(cfg) is harness.cached_programs(cfg)
    narrow = llama_debug(num_layers=1)
    assert harness.cached_programs(narrow) is not harness.cached_programs(cfg)
    # the weights are seeded once for their arguments, the tree is the
    # caller's own
    a, b = harness.seeded(cfg), harness.seeded(cfg)
    assert a is not b and all(x is y for x, y in zip(jax.tree.leaves(a),
                                                     jax.tree.leaves(b)))
