"""A turn reads the weights once (ISSUE 40 and 44, ROADMAP S3): the prefill
chunk's program (``paged_prefill_into_slot``, jitted as
``paged_prefill_chunk``) takes the live decode rows along, and a loop turn
that holds a chunk dispatches ONE program — whatever the kinds of the model's
layers: pages through ``ops.paged_attention``, chosen blocks ('minicpm4'), a
state a slot ('lightning-attn', 'power-retention'), or all four in one model.

Program level: the chunk's program with live step rows against the chunk's
program alone and then ``paged_decode_step``, on the same inputs. Scheduler
level: under churn every stream is the sequential cache's, token for token,
one program a turn, two compiled, and the counters keep their meaning. CPU,
float32, toy models: tokens, pools, states and counts, never a time."""

import asyncio
import re
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (brumby_debug, llama_debug, minicpm_sala_debug,
                            moe_debug)
from ray_tpu.models.decode import (StepRows, init_paged_caches,
                                   paged_decode_step,
                                   paged_prefill_into_slot)
from ray_tpu.models.transformer import (ATTENTION, LINEAR, RETENTION, SPARSE,
                                        STATE_KINDS, init_params)
from ray_tpu.serve._private.continuous import ContinuousScheduler
from tests import model_harness as harness

PRESETS = {
    "llama_debug": llama_debug, "moe_debug": moe_debug,
    # block-selected and linear attention, 1:3; the linear kind alone; power
    # retention alone (no page anywhere)
    "minicpm_sala_debug": minicpm_sala_debug,
    "lightning_debug": partial(minicpm_sala_debug, num_layers=2,
                               layer_kinds=(LINEAR,) * 2),
    "brumby_debug": brumby_debug,
    # every kind of layer in one model
    "four_kinds_debug": partial(
        minicpm_sala_debug, num_layers=4,
        layer_kinds=(SPARSE, LINEAR, ATTENTION, RETENTION)),
}
SLOTS, T, P, C = 4, 4, 32, 8  # slots, page tokens, pages a slot, chunk


@pytest.fixture(scope="module", params=sorted(PRESETS))
def model(request):
    cfg = PRESETS[request.param]()
    return cfg, init_params(cfg, jax.random.PRNGKey(3))


# ----------------------------------------------------------- program level


def _programs(cfg):
    """The two programs, compiled once a model; a model with 'minicpm4'
    layers also hands back the blocks they chose (last of what a program
    returns)."""
    kw = {"attn": "reference"}
    if cfg.mlp == "moe":
        kw["moe_info"] = True
    if SPARSE in cfg.kinds:
        kw["selected"] = True
    return harness.paged_programs(cfg, **kw)


def _rows(cfg, tables, slot=None):
    """The (read, write) tables of all slots or of one; None, None for a
    model none of whose layers holds a page."""
    if not cfg.holds_pages:
        return None, None
    rows = tables if slot is None else tables[slot]
    return rows, rows


def _prompt(n, start):
    return [(start + 7 * i) % 250 + 1 for i in range(n)]


_STATES = {}


@pytest.fixture(scope="module", autouse=True)
def _states_go_with_the_file():
    yield
    _STATES.clear()


def _state(cfg, params, lengths):
    """A pool in which the slots of ``lengths`` hold a prompt each (filled
    by the chunk's program alone), their tables (a slot's pages in order,
    never page 0), cursors and the ids vector with each one's first token.
    Filled once a (model, lengths): the arrays are immutable, the cursors a
    copy."""
    key = (cfg, tuple(sorted(lengths.items())))
    if key not in _STATES:
        _STATES[key] = (params, _filled(cfg, params, lengths))
    held, (caches, tables, ids, cursors) = _STATES[key]
    assert held is params
    return caches, tables, ids, cursors.copy()


def _filled(cfg, params, lengths):
    chunk, _ = _programs(cfg)
    caches = init_paged_caches(cfg, SLOTS * P + 1, T, P, jnp.float32,
                               slots=SLOTS)
    tables = (1 + np.arange(SLOTS * P, dtype=np.int32)).reshape(SLOTS, P)
    ids = jnp.zeros(SLOTS, jnp.int32)
    cursors = np.zeros(SLOTS, np.int32)
    for slot, n in lengths.items():
        prompt = _prompt(n, 11 * slot + 1)
        for at in range(0, n, C):
            part = prompt[at:at + C]
            out = chunk(params, _padded(part), np.int32(len(part)),
                        np.int32(at), *_rows(cfg, tables, slot), caches, ids,
                        np.int32(slot if at + C >= n else -1), np.float32(0),
                        np.uint32(0), None, np.int32(slot))
            ids, caches = out[0], out[1]
        cursors[slot] = n
    return caches, tables, ids, cursors


def _padded(part):
    return np.asarray([list(part) + [0] * (C - len(part))], np.int32)


def _real_positions(tables, cursors):
    """(page, offset) of every position a slot holds, as index arrays."""
    at = [(tables[s, p // T], p % T) for s in range(SLOTS)
          for p in range(int(cursors[s]))]
    return tuple(np.asarray(x) for x in zip(*at))


def _same_caches(cfg, got, want, tables, cursors, untouched=()):
    """What two runs left in the pool, a layer at a time and by its kind:
    keys and values on every position a slot holds (and a 'minicpm4'
    layer's pooled row of every page they fill: the row of a page that is
    not full yet is recomputed by the write that fills it, before any query
    sees it), every slot's states; the
    states of the slots in ``untouched`` ([(slot, the caches they must
    still equal)]) bitwise."""
    where = _real_positions(tables, cursors)
    close = partial(np.testing.assert_allclose, rtol=1e-5, atol=1e-5)
    for kind, a, b in zip(cfg.kinds, got, want):
        if kind in STATE_KINDS:
            for name, x in a.arrays().items():
                close(np.asarray(x), np.asarray(b.arrays()[name]))
            continue
        close(np.asarray(a.k)[where], np.asarray(b.k)[where])
        close(np.asarray(a.v)[where], np.asarray(b.v)[where])
        if kind == SPARSE:
            full = np.concatenate([tables[s, :int(cursors[s]) // T]
                                   for s in range(SLOTS)])
            close(np.asarray(a.means)[full], np.asarray(b.means)[full])
    for slot, before in untouched:
        for a, b in zip(got, before):
            if hasattr(a, "arrays"):
                for name, x in a.arrays().items():
                    assert np.array_equal(np.asarray(x)[slot],
                                          np.asarray(b.arrays()[name])[slot])


# slots 0 and 2 decode (slot 2 so far past the 48 tokens from which a
# 'minicpm4' layer chooses that it leaves blocks out), slot 1 is mid-prompt,
# slot 3 holds a sequence that takes no token this turn
HELD = {0: 5, 1: C, 2: 101, 3: 6}


@pytest.mark.parametrize("last", [False, True], ids=["mid_prompt", "last"])
def test_the_chunk_with_live_rows_is_the_chunk_and_then_the_step(model, last):
    """Slots 0 and 2 decode, slot 1 is mid-prompt with its cursor on a
    page's first position, slot 3 is not active: ONE program against two."""
    cfg, params = model
    chunk, step = _programs(cfg)
    caches, tables, ids, cursors = _state(cfg, params, HELD)
    assert cursors[1] % T == 0
    # slot 1's next chunk; as a decode row it is NOT active, its tables hold
    # its real pages and its cursor is where the chunk writes first
    part = _prompt(C if not last else 5, 50)
    real = len(part)
    active = np.asarray([1, 0, 1, 0], np.int32)
    greedy = (np.zeros(SLOTS, np.float32), np.zeros(SLOTS, np.uint32))
    args = (params, _padded(part), np.int32(real), np.int32(cursors[1]),
            *_rows(cfg, tables, 1))
    tail = (np.int32(1 if last else -1), np.float32(0), np.uint32(0))

    # two programs, as the scheduler ran them: the slot's cursor moves past
    # the chunk before the step, whose idle row 1 writes at its cursor
    two = chunk(*args, caches, ids, *tail, None, np.int32(1))
    moved = cursors.copy()
    moved[1] += real
    two_step = step(params, two[0], active, moved, *_rows(cfg, tables),
                    two[1], *greedy)
    one = chunk(*args, caches, ids, *tail,
                StepRows(active, cursors, *_rows(cfg, tables), *greedy),
                np.int32(1))

    want, got = np.asarray(two_step[0]), np.asarray(one[0])
    # the live rows' next tokens, the free slot's entry as it came
    assert np.array_equal(got[[0, 2, 3]], want[[0, 2, 3]])
    assert got[0] != np.asarray(ids)[0] or got[2] != np.asarray(ids)[2]
    if last:
        # the first token, where the NEXT step reads it: the step that ran
        # behind the chunk alone left it there untouched (row 1 not active)
        assert got[1] == np.asarray(two[0])[1] == want[1]
    else:
        assert got[1] == np.asarray(ids)[1]
    # pools, pooled rows and states; slot 3 took no token: its states are
    # bitwise what they were before either
    _same_caches(cfg, one[1], two_step[1], tables, moved + active,
                 untouched=[(3, caches)])
    if SPARSE in cfg.kinds:
        # the blocks chosen, a group of rows: the chunk's, the live rows'
        of_chunk, of_step = (np.asarray(x) for x in one[-1])
        assert np.array_equal(of_chunk, np.asarray(two[-1]))
        assert np.array_equal(of_step[:, [0, 2]],
                              np.asarray(two_step[-1])[:, [0, 2]])
        assert not of_step[:, 2].all()  # a choice: not every block
    if cfg.mlp == "moe":
        # the experts ran once a layer over both groups' rows; the counts
        # still say which group sent which, as the two programs did
        counts = np.asarray(one[2]["counts"])
        assert counts.shape == (cfg.num_layers, 2, cfg.moe_num_experts)
        assert (counts.sum((1, 2)) == (real + 2) * cfg.moe_top_k).all()
        assert np.array_equal(counts[:, 0], np.asarray(two[2]["counts"]))
        assert np.array_equal(counts[:, 1], np.asarray(two_step[2]["counts"]))
        assert np.asarray(one[2]["routes"]).shape == (
            cfg.num_layers, 1, C + SLOTS, cfg.moe_top_k)


def test_a_row_that_is_not_active_writes_nothing_a_sequence_reads(model):
    """The hazard of one scatter: the prefilling slot is a step row whose
    cursor IS the chunk's first position, and a step row writes. With tables
    that would let it (its own real pages, not the scheduler's zeroed rows)
    the chunk's positions still hold the chunk's keys, whatever token the
    row carries, and so does every other slot's last position."""
    cfg, params = model
    chunk, _ = _programs(cfg)
    caches, tables, ids, cursors = _state(cfg, params, HELD)
    part = _prompt(C, 50)
    args = (params, _padded(part), np.int32(C), np.int32(cursors[1]),
            *_rows(cfg, tables, 1))
    tail = (np.int32(-1), np.float32(0), np.uint32(0))
    greedy = (np.zeros(SLOTS, np.float32), np.zeros(SLOTS, np.uint32))
    alone = chunk(*args, caches, ids, *tail, None, np.int32(1))
    # no row active; rows 0 and 2 point at their newest REAL position
    stale = cursors.copy()
    stale[[0, 2]] -= 1
    for token in (7, 201):
        idle = chunk(*args, caches, ids.at[:].set(token), *tail,
                     StepRows(np.zeros(SLOTS, np.int32), stale,
                              *_rows(cfg, tables), *greedy), np.int32(1))
        assert np.array_equal(np.asarray(idle[0]), np.full(SLOTS, token))
        after = cursors.copy()
        after[1] += C
        where = _real_positions(tables, after)
        for kind, a, b in zip(cfg.kinds, idle[1], alone[1]):
            if kind in STATE_KINDS:
                continue
            assert np.array_equal(np.asarray(a.k)[where],
                                  np.asarray(b.k)[where])
            assert np.array_equal(np.asarray(a.v)[where],
                                  np.asarray(b.v)[where])
        # a state has no second chance: every other slot's is bitwise what
        # it was, the chunk's own slot's what the chunk alone left
        _same_caches(cfg, idle[1], alone[1], tables, after,
                     untouched=[(s, caches) for s in (0, 2, 3)])
        if cfg.mlp == "moe":
            counts = np.asarray(idle[2]["counts"])
            assert np.array_equal(counts[:, 0],
                                  np.asarray(alone[2]["counts"]))
            assert not counts[:, 1].any()


def test_a_model_of_all_four_kinds_of_layer_takes_the_rows_along():
    """One rule, read off nothing but each layer's kind: in a model that
    mixes plain attention, chosen blocks, linear attention and power
    retention the chunk's program calls every layer's kernels a group of
    rows — the chunk's and the step's of each kind, once a layer — and the
    plain step holds none of the chunk's."""
    cfg = PRESETS["four_kinds_debug"]()
    assert set(cfg.kinds) == {ATTENTION, SPARSE, LINEAR, RETENTION}
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    caches = jax.eval_shape(lambda: init_paged_caches(
        cfg, SLOTS * P + 1, T, P, jnp.float32, slots=SLOTS))
    rows = jnp.zeros(SLOTS, jnp.int32)
    tables = jnp.zeros((SLOTS, P), jnp.int32)
    step = (rows, rows, tables, tables, jnp.zeros(SLOTS), rows)

    def kernels(program, *args):
        text = str(jax.make_jaxpr(partial(program, cfg, attn="reference"))(
            *args))
        names = re.findall(r"name=(\w+)", text)
        return {k: names.count(k) for k in (
            "linear_attention_chunk", "linear_attention_step",
            "power_retention_chunk", "power_retention_step",
            "sparse_select", "sparse_paged_attention") if k in names}

    # off a TPU the step's chosen blocks go through the paged reference
    assert kernels(
        paged_prefill_into_slot, params, jnp.zeros((1, C), jnp.int32), 3,
        jnp.int32(0), tables[0], tables[0], caches, rows, 0, jnp.float32(0),
        jnp.uint32(0), StepRows(*step), 0) == {
            "linear_attention_chunk": 1, "linear_attention_step": 1,
            "power_retention_chunk": 1, "power_retention_step": 1,
            "sparse_select": 2, "sparse_paged_attention": 1}
    assert kernels(paged_decode_step, params, rows, *step[:4], caches,
                   *step[4:]) == {
        "linear_attention_step": 1, "power_retention_step": 1,
        "sparse_select": 1}


# --------------------------------------------------------- scheduler level

NEW_MAX = 40


class Watched(ContinuousScheduler):
    """The scheduler, with a note of what each loop turn dispatched:
    (programs, chunks, programs that advanced decode rows, the decode rows a
    chunk's program took along)."""

    def __init__(self, *args, **kw):
        self.per_turn = []
        self._rode = 0
        super().__init__(*args, **kw)

    def _dispatch_chunk(self, seq, tokens, real, rows):
        self._rode = len(rows.live) if rows is not None else 0
        super()._dispatch_chunk(seq, tokens, real, rows)

    def _turn(self, behind):
        before = (self._serial, self._n_prefill_chunks, self._n_steps)
        self._rode = 0
        did = super()._turn(behind)
        after = (self._serial, self._n_prefill_chunks, self._n_steps)
        self.per_turn.append(tuple(b - a for a, b in zip(before, after))
                             + (self._rode,))
        return did


def _oracle(cfg, params, prompt, new=NEW_MAX):
    """The sequential cache, which knows no page, slot or turn: greedy
    tokens after ``prompt`` (one program and one cache length a model)."""
    return harness.oracle(cfg, params, prompt, new, P * T, jnp.float32)


def _upto_eos(stream, eos):
    return stream[:stream.index(eos) + 1] if eos in stream else stream


_stream = harness.stream


# prompt lengths of one chunk and of several, budgets short and long: with
# four slots the later ones are admitted while the earlier ones decode
# (the last one long enough that a 'minicpm4' layer leaves blocks out)
CHURN = [(5, 24), (21, 10), (8, 30), (13, 6), (30, 12), (3, 40), (17, 8),
         (9, 16), (26, 5), (12, 20), (70, 36)]
CANCELLED, CANCEL_AFTER = 2, 3  # (8, 30) is cancelled behind its third token
REPEATED = 4                    # (30, 12) comes again once it has ended


@pytest.fixture(scope="module")
def churn(model):
    """Eleven requests through four slots with the prefix cache on (where
    the model has no layer that keeps a state), an EOS id
    that cuts some streams short, one cancellation in mid-decode and one
    prompt sent again after it ended (the prefix hit)."""
    cfg, params = model
    prompts = [_prompt(n, 17 * i + 1) for i, (n, _) in enumerate(CHURN)]
    free = [_oracle(cfg, params, p) for p in prompts]
    # an id that ends at least one stream in mid-decode and not every stream
    # at once: the most frequent token that is nobody's first
    later = [t for s in free for t in s[2:]]
    eos = max(set(later) - {s[0] for s in free}, key=later.count)
    want = [_upto_eos(s[:new], eos) for s, (_, new) in zip(free, CHURN)]
    # a state cannot be cut at a page boundary: no prefix cache for a model
    # with a layer that keeps one
    sched = Watched(cfg, params, slots=SLOTS, prefill_chunk=C,
                    arena_len=P * T, page_tokens=T, eos_id=eos,
                    prefix_cache=not cfg.recurrent, attn="reference")

    async def drive():
        ended = asyncio.Event()

        async def first_then_flag(i):
            got = await _stream(sched, prompts[i], CHURN[i][1])
            ended.set()
            return got

        jobs = [first_then_flag(i) if i == REPEATED else _stream(
            sched, prompts[i], CHURN[i][1],
            cancel_after=CANCEL_AFTER if i == CANCELLED else None)
            for i in range(len(CHURN))]
        jobs.append(_stream(sched, prompts[REPEATED], CHURN[REPEATED][1],
                            gate=ended))
        return await asyncio.gather(*jobs)

    try:
        got = asyncio.run(drive())
        # the last stream's end is emitted from inside the read of its
        # program: let the loop finish that turn
        patience = time.monotonic() + 10
        while (sched._inflight or sched._steps_unread) and (
                time.monotonic() < patience):
            time.sleep(0.01)
        stats = sched.stats()
        left = (len(sched._inflight), sched._steps_unread)
    finally:
        sched.shutdown()
    return {"cfg": cfg, "want": want + [want[REPEATED]], "got": got,
            "eos": eos, "stats": stats, "per_turn": sched.per_turn,
            "left": left}


def test_under_churn_every_stream_is_the_oracles_token_for_token(churn):
    assert any(churn["eos"] in s and len(s) < new for s, (_, new)
               in zip(churn["want"], CHURN)), "the EOS id cut no stream short"
    for i, ((tokens, end), want) in enumerate(zip(churn["got"],
                                                  churn["want"])):
        if i == CANCELLED and len(want) > CANCEL_AFTER:
            # what arrived before the cancellation took effect, no more
            assert end == ("end", "cancelled")
            assert CANCEL_AFTER <= len(tokens) <= CANCEL_AFTER + 2
            assert tokens == want[:len(tokens)]
            continue
        assert tokens == want, i
        assert end == ("end", "eos" if tokens[-1] == churn["eos"]
                       else "length")
    stats = churn["stats"]
    assert stats["admitted_mid_flight"] > 0
    assert stats["prefix_hit_tokens"] > 0 or churn["cfg"].recurrent
    # EOS and the cancellation reached the loop a program late: their rows
    # rode once more and were dropped, never emitted
    assert stats["discarded_rows"] > 0
    assert stats["retired"] == stats["admitted"] == len(churn["got"])
    assert churn["left"] == (0, 0)


def test_a_turn_dispatches_one_program_and_two_are_compiled(churn):
    per_turn, stats = churn["per_turn"], churn["stats"]
    assert all(programs <= 1 for programs, *_ in per_turn)
    # with a chunk the program is the chunk's, whatever decodes beside it
    assert all(programs == 1 for programs, chunks, *_ in per_turn if chunks)
    assert [t for t in per_turn if t[:3] == (1, 1, 1) and t[3] > 0]
    assert [t for t in per_turn if t[:3] == (1, 0, 1)]   # the plain step
    assert stats["compiled_programs"] == 2


def test_the_counters_keep_their_meaning(churn):
    per_turn, stats = churn["per_turn"], churn["stats"]
    emitted = sum(len(tokens) for tokens, _ in churn["got"])
    assert stats["tokens_generated"] == emitted
    assert stats["first_tokens"] == len(churn["got"])
    assert (stats["gap_plain_tokens"] + stats["gap_prefill_tokens"]
            + stats["first_tokens"]) == stats["tokens_generated"]
    assert stats["gap_prefill_tokens"] > 0 and stats["gap_plain_tokens"] > 0
    # a program that advanced decode rows is a decode step, one that carried
    # a chunk a prefill chunk, one that did both is both
    assert stats["prefill_chunks"] == sum(t[1] for t in per_turn)
    assert stats["decode_steps"] == sum(t[2] for t in per_turn)
    fused = [t[3] for t in per_turn if t[1] and t[2]]
    assert all(fused)
    assert stats["fused_turns"] == len(fused) > 0
    assert stats["fused_step_rows"] == sum(fused) >= len(fused)
    assert stats["fused_turns"] <= stats["prefill_chunks"]
    assert sum(t[0] for t in per_turn) == (
        stats["prefill_chunks"] + stats["decode_steps"]
        - stats["fused_turns"])
    # every token but a sequence's first was sampled for a decode row: no
    # step emitted more than a row a slot (sched.occupancy stays under 100%)
    assert (emitted - stats["first_tokens"] + stats["discarded_rows"]
            <= stats["decode_steps"] * SLOTS)
    assert stats["runahead_steps"] <= stats["decode_steps"]
    assert stats["turns"] >= len([t for t in per_turn if t[0]])
    # prompt tokens dispatched: the prompts less what the prefix cache held
    prompts = sum(n for n, _ in CHURN) + CHURN[REPEATED][0]
    assert stats["prefill_tokens"] == prompts - stats["prefix_hit_tokens"]
    cfg = churn["cfg"]
    if cfg.mlp == "moe":
        assert stats["moe_rows_routed"] == (
            stats["moe_live_rows"] * cfg.moe_top_k * cfg.num_layers)
        # a layer-call is an expert layer over one group of rows: a chunk's
        # program that took rows along is two a layer, as when it was two
        assert stats["moe_layer_calls"] == cfg.num_layers * (
            stats["prefill_chunks"] + stats["decode_steps"])


def test_an_exhausted_pool_fails_one_stream_and_the_rest_are_the_oracles(
        model):
    """Ten pages for two sequences that want six each: one fails cleanly in
    mid-decode (its row rides once more, in a plain step or a chunk's
    program, and is dropped), a third prompt then takes its slot and fits
    beside the survivor, and what every stream holds is the oracle's."""
    cfg, params = model
    if not cfg.holds_pages:
        pytest.skip("no layer of the model holds a page")
    asks = [(10, 14), (9, 14), (7, 4)]
    prompts = [_prompt(n, 31 * i + 5) for i, (n, _) in enumerate(asks)]
    want = [_oracle(cfg, params, p, new)
            for p, (_, new) in zip(prompts, asks)]
    sched = Watched(cfg, params, slots=2, prefill_chunk=C, arena_len=P * T,
                    page_tokens=T, kv_pages=11, prefix_cache=False,
                    attn="reference")

    async def drive():
        return await asyncio.gather(*(
            _stream(sched, p, new) for p, (_, new) in zip(prompts, asks)))

    try:
        got = asyncio.run(drive())
        stats = sched.stats()
    finally:
        sched.shutdown()
    failed = [i for i, (_, (kind, _)) in enumerate(got) if kind == "err"]
    assert len(failed) == 1 and "out of pages" in got[failed[0]][1][1]
    for i, ((tokens, _), full) in enumerate(zip(got, want)):
        assert tokens == (full[:len(tokens)] if i in failed else full), i
    assert 0 < len(got[failed[0]][0]) < asks[failed[0]][1]
    assert all(programs <= 1 for programs, *_ in sched.per_turn)
    assert stats["fused_turns"] > 0  # the third prompt beside the survivor
    assert (stats["gap_plain_tokens"] + stats["gap_prefill_tokens"]
            + stats["first_tokens"]) == stats["tokens_generated"]
    assert stats["compiled_programs"] == 2


def test_the_speculative_loop_calls_the_chunks_program_with_no_row_active():
    """Its decode rows go through the verify program, so the chunk's
    program (the same one, with the step's arrays) carries none: no fused
    turn is counted, the plain step is never compiled, and the streams are
    the oracle's."""
    from ray_tpu.serve.llm import LLMServerImpl

    srv = LLMServerImpl(preset="llama_debug", max_new_tokens=6, slots=2,
                        prefill_chunk=C, prefix_cache=False,
                        share_weights=False, drafter="self", spec_k=2)
    try:
        sched = srv._sched
        prompts = [_prompt(5, 3), _prompt(2 * C + 3, 40)]
        want = [_oracle(srv.cfg, srv.params, p, 12) for p in prompts]

        async def drive():
            return await asyncio.gather(*(_stream(sched, p, 12)
                                          for p in prompts))

        got = asyncio.run(drive())
        stats = sched.stats()
    finally:
        srv.shutdown()
    assert [tokens for tokens, _ in got] == want
    assert stats["spec_rounds"] > 0 and stats["prefill_chunks"] == 4
    assert stats["fused_turns"] == stats["fused_step_rows"] == 0
    assert sched._step._cache_size() == 0 and stats["compiled_programs"] == 2
