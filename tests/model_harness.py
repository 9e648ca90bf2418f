"""How a model's programs are driven against their reference, said ONCE
(ISSUE 63): seeded weights, the paged drive, the contiguous cache and the
sequential oracle, a request's stream through the scheduler. Plain functions;
a model's test file keeps its reference's keys (``hp_of``), its presets, its
named faults and every assertion that is about its kind, and calls these.

Every program here is jitted once a configuration and its keywords
(``paged_programs``, ``cached_programs``, ``forward_program``): a test pays
a compile the first time a program is asked for, never an op-by-op dispatch
with the Pallas kernels interpreted a call at a time. The memos live as long
as one file's tests (``tests/conftest.py`` calls ``forget`` behind every
module); they hold jitted functions, which survive ``jax.clear_caches()``
(they trace again). Nothing here is to be called under a ``monkeypatch`` of
the program: a trace made under one would outlive it.
"""

from __future__ import annotations

import asyncio
import dataclasses
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.decode import (StepRows, decode_step, init_caches,
                                   paged_decode_step,
                                   paged_prefill_into_slot, prefill)
from ray_tpu.models.transformer import forward, init_params


def rel(got, want):
    """The largest difference over the reference's largest value."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / np.abs(want).max())


def seeded(cfg, seed=0, *, stir=("norm", "ln"), by=0.2, times=None, keys=64):
    """Seeded weights with every leaf whose path holds one of ``stir`` moved
    off its trivial value by ``by`` of a normal draw (a norm that is left
    out, or one scale taken for another, then shows), and every leaf whose
    path holds a name of ``times`` multiplied by its factor (an indexer that
    speaks up). ``keys``: how many keys the draws are split from. Made in
    ONE compiled program and once for its arguments: op by op a toy's
    weights cost 8 s."""
    made = _seeded(cfg, seed, tuple(stir), by,
                   tuple(sorted((times or {}).items())), keys)
    return jax.tree.map(lambda leaf: leaf, made)  # the caller's own tree


@lru_cache(maxsize=None)
def _seeded(cfg, seed, stir, by, times, keys):
    def make():
        params = init_params(cfg, jax.random.PRNGKey(seed))
        draws = iter(jax.random.split(jax.random.PRNGKey(seed + 1), keys))

        def one(path, leaf):
            name = jax.tree_util.keystr(path)
            if any(n in name for n in stir):
                return leaf + by * jax.random.normal(next(draws), leaf.shape,
                                                     leaf.dtype)
            for n, factor in times:
                if n in name:
                    return leaf * factor
            return leaf

        return jax.tree_util.tree_map_with_path(one, params)

    return jax.jit(make)()


# ------------------------------------------------------------ the programs

_MEMO = {}


def _once(key, build):
    if key not in _MEMO:
        _MEMO[key] = build()
    return _MEMO[key]


def forget():
    """Drop every memo: the programs, weights and oracle streams of a
    file's tests go with the file."""
    _MEMO.clear()
    _seeded.cache_clear()


def forward_program(cfg, **kw):
    """The uncached ``forward`` with its keywords, jitted once a (cfg,
    keywords)."""
    return _once(("forward", cfg, *sorted(kw.items())),
                 lambda: jax.jit(partial(forward, cfg, **kw)))


def paged_programs(cfg, **kw):
    """(the chunk's program, the step's), jitted once a (cfg, keywords):
    ``paged_prefill_into_slot`` and ``paged_decode_step`` as the scheduler
    jits them, ``real_len`` and the cursor traced."""
    return _once(("paged", cfg, *sorted(kw.items())), lambda: (
        jax.jit(partial(paged_prefill_into_slot, cfg, **kw)),
        jax.jit(partial(paged_decode_step, cfg, **kw))))


def cached_programs(cfg):
    """(``prefill``, ``decode_step``) on the contiguous cache, jitted once
    a cfg."""
    return _once(("cached", cfg), lambda: (jax.jit(partial(prefill, cfg)),
                                           jax.jit(partial(decode_step, cfg))))


def cached_logits(cfg, params, tokens, n, length=None, dtype=None):
    """``tokens`` [B, S] through the contiguous cache: a prompt of ``n``,
    then a step a token, teacher-forced. Logits [B, S - n + 1, vocab], from
    the prompt's last position on."""
    fill, step = cached_programs(cfg)
    caches = init_caches(cfg, tokens.shape[0], length or tokens.shape[1],
                         dtype)
    with jax.default_matmul_precision("highest"):
        logits, caches = fill(params, tokens[:, :n], caches)
        got = [logits]
        for t in range(n, tokens.shape[1]):
            logits, caches = step(params, tokens[:, t:t + 1], caches)
            got.append(logits)
    return jnp.stack(got, 1)


# ------------------------------------------------------ the paged drive


def slot_tables(slots, pages, holders):
    """[slots, pages] int32: the slots of ``holders`` name a run of pages
    each (never page 0, the garbage page), every other row names page 0."""
    tables = np.zeros((slots, pages), np.int32)
    for s in holders:
        tables[s] = 1 + s * pages + np.arange(pages)
    return tables


def pools(cache):
    """{name: array} of the page pools one layer's serving cache holds, by
    the cache's own fields; nothing for a layer that keeps a state a slot
    (``arrays``) or no cache."""
    if cache is None or hasattr(cache, "arrays"):
        return {}
    return {f.name: getattr(cache, f.name) for f in dataclasses.fields(cache)
            if f.name != "length" and getattr(cache, f.name) is not None}


def poison(caches, pages):
    """Every pool of every layer with NaN in ``pages``, as a released page
    would be: whatever read one would show."""
    return [c if not pools(c) else dataclasses.replace(c, **{
        name: pool.at[pages].set(jnp.nan) for name, pool in pools(c).items()})
        for c in caches]


def unnamed_pages(caches, tables):
    """The pages of the pool that no row of ``tables`` names."""
    pages = next(p for c in caches for p in pools(c).values()).shape[0]
    return np.setdiff1d(np.arange(pages), np.unique(tables))


def paged_drive(cfg, params, tokens, caches, tables, *, lengths, chunk,
                steps, impl, along=True, poisoned=True, ensure=None,
                after=None, **kw):
    """THE schedule of the models' paged tests. The two slots of ``lengths``
    ({slot: prompt tokens}, row i of ``tokens`` the i-th slot's sequence)
    take their prompts in chunks of ``chunk``, one slot after the other;
    the second slot's chunks take the first's decode row along (the fused
    turn; ``along=False``: the step's rows ride, none of them live;
    ``along=None``: the chunk goes alone); then ``steps`` plain steps of
    both, teacher-forced. The other slots hold no sequence.

    ``tables``: [slots, P] int32 for both the read and the write table, or
    ``tables(slot=None) -> (read, write)`` where the caller keeps them
    (``ensure(caches, slot, lo, hi) -> caches`` is then called for every
    slot about to write positions [lo, hi), ``after()`` behind every
    program). ``poisoned``: every page no table names is filled with NaN in
    every pool first. ``kw``: the programs' keywords (``moe_info`` or
    ``loop_info``, ``selected``); logits are always asked for.

    Returns what the programs said, a slot at a time: ``got`` (logits, from
    the prompt's last position on), ``routes`` and ``picked`` (where asked
    for), ``cursor``; ``info`` (what every program told beside ids and
    caches: its ``moe_info`` or ``loop_info``), the final
    ``caches``, the ``poisoned`` pages, the jitted ``step``."""
    first, second = sorted(lengths)
    row = {first: 0, second: 1}
    if not callable(tables):
        both = jnp.asarray(tables)
        tables = lambda slot=None: ((both, both) if slot is None
                                    else (both[slot], both[slot]))
    slots = len(jax.tree.leaves(tables()[0])[0])
    bad = np.zeros(0, np.int32)
    if poisoned:
        bad = unnamed_pages(caches, np.asarray(jax.tree.leaves(tables()[0])))
        caches = poison(caches, bad)
    run, step = paged_programs(cfg, attn=impl, logits=True, **kw)
    moe, taps = kw.get("moe_info", False), kw.get("selected", False)
    told = moe or kw.get("loop_info", False)
    got, routes, picked = ({s: [] for s in lengths} for _ in range(3))
    cursor, info = dict.fromkeys(lengths, 0), []

    def rows_of(live):
        active = np.zeros(slots, np.int32)
        cursors = np.zeros(slots, np.int32)
        for s in live:
            active[s], cursors[s] = 1, cursor[s]
        return StepRows(active, cursors, *tables(),
                        np.zeros(slots, np.float32),
                        np.zeros(slots, np.uint32))

    def ids_of(live):
        ids = np.zeros(slots, np.int32)
        for s in live:
            ids[s] = tokens[row[s], cursor[s]]
        return ids

    def said(out):
        """(ids, caches, moe_info, logits, taps) of a program's outputs."""
        out = list(out)
        return (out[0], out[1], out.pop(2) if told else None, out[2],
                out[3] if taps else None)

    def wrote(caches, spans):
        for s, (lo, hi) in spans.items() if ensure else ():
            caches = ensure(caches, s, lo, hi)
        return caches

    with jax.default_matmul_precision("highest"):
        for s, live in ((first, []), (second, [first] if along else [])):
            prompt = np.asarray(tokens[row[s], :lengths[s]])
            for c0 in range(0, lengths[s], chunk):
                real = min(chunk, lengths[s] - c0)
                padded = np.zeros((1, chunk), np.int32)
                padded[0, :real] = prompt[c0:c0 + real]
                caches = wrote(caches, {s: (c0, c0 + real), **{
                    o: (cursor[o], cursor[o] + 1) for o in live}})
                _, caches, said_moe, logits, chose = said(run(
                    params, padded, np.int32(real), np.int32(c0), *tables(s),
                    caches, ids_of(live), np.int32(-1), np.float32(0),
                    np.uint32(0), None if along is None else rows_of(live),
                    np.int32(s)))
                info.append(said_moe)
                cursor[s] = c0 + real
                if moe:
                    r = np.asarray(said_moe["routes"])[:, 0]
                    routes[s].append(r[:, :real])
                if taps:
                    picked[s].append(np.asarray(chose[0])[:, 0, :real])
                for o in live:
                    got[o].append(logits[1 + o])
                    if moe:
                        routes[o].append(r[:, chunk + o][:, None])
                    if taps:
                        picked[o].append(np.asarray(chose[1])[:, o])
                    cursor[o] += 1
                if after is not None:
                    after()
            # the chunk's last real row; behind it the step's rows'
            got[s].append(logits if along is None else logits[0])
        live = [first, second]
        for _ in range(steps):
            caches = wrote(caches, {s: (cursor[s], cursor[s] + 1)
                                    for s in live})
            rows, fed = rows_of(live), ids_of(live)
            ids, caches, said_moe, logits, chose = said(step(
                params, fed, rows.active, rows.cursors, rows.read_tables,
                rows.write_tables, caches, rows.temperature, rows.seeds))
            # an active row's id is its argmax, an idle row's its token
            assert np.array_equal(np.asarray(ids), np.where(
                rows.active > 0, np.asarray(logits).argmax(-1), fed))
            info.append(said_moe)
            for s in live:
                got[s].append(logits[s])
                if moe:
                    routes[s].append(np.asarray(said_moe["routes"])[:, s])
                if taps:
                    picked[s].append(np.asarray(chose)[:, s])
                cursor[s] += 1
            if after is not None:
                after()
    return {"cfg": cfg, "params": params, "tokens": tokens, "got": got,
            "routes": routes, "picked": picked, "n": lengths, "row": row,
            "cursor": cursor, "caches": caches, "poisoned": bad,
            "info": info, "impl": impl, "step": step,
            "tables": tables()[0]}


def paged_fixture(setup, impls=None):
    """The module-scoped fixture ``paged_run`` of a model's file, a case an
    implementation of ``impls`` (none: one case without an id):
    ``setup(request) -> paged_drive's arguments``, with what the file's
    tests want kept beside the drive's results under ``keep``."""
    def paged_run(request):
        args = setup(request)
        keep = args.pop("keep", {})
        return {**paged_drive(**args), **keep}

    if impls is None:
        return pytest.fixture(scope="module")(paged_run)
    return pytest.fixture(scope="module", params=list(impls))(paged_run)


def slot_logits(run, slot):
    """A slot's logits of a drive, stacked and FINITE: a program that read a
    page no table names would have brought its NaN here, and this refuses
    it before any comparison can."""
    got = np.asarray(jnp.stack(run["got"][slot]))
    assert np.isfinite(got).all(), "a logit is not finite: a poisoned page?"
    return got


def poisoned_pages_left_alone(run):
    """Every pool of every layer still holds NaN in the pages the drive
    poisoned: no program wrote one. (The first of them apart, as the
    models' files always had it.)"""
    assert len(run["poisoned"]) > 1
    for cache in run["caches"]:
        for pool in pools(cache).values():
            assert np.isnan(np.asarray(pool[run["poisoned"][1:]])).all()


# -------------------------------------------------- the sequential oracle


def oracle(cfg, params, prompt, new, length=None, dtype=None):
    """The sequential cache, which knows no page, slot or turn: the greedy
    tokens after ``prompt``. The prompt goes through ONE jitted
    ``decode_step`` a token at a time, over one cache length a cfg
    (``length``, else the model's limit), so a cfg compiles one program
    whatever its prompts' lengths; remembered by (cfg, weights, prompt)."""
    _, step = cached_programs(cfg)
    held = _once(("oracle", cfg, id(params), length, dtype),
                 lambda: (params, {}))[1]  # the weights stay alive
    have = held.get(tuple(prompt), [])
    if len(have) >= new:
        return have[:new]
    caches = init_caches(cfg, 1, length or cfg.max_seq_len, dtype)
    for token in prompt:
        logits, caches = step(params, np.asarray([[token]], np.int32),
                              caches)
    out = []
    for _ in range(new):
        out.append(int(np.asarray(logits)[0].argmax()))
        logits, caches = step(params, np.asarray([[out[-1]]], np.int32),
                              caches)
    held[tuple(prompt)] = out
    return out


def sequential_text(srv, prompt, new, length=None):
    """An ``LLMServerImpl``'s prompt through the oracle: the greedy text of
    ``new`` tokens."""
    return srv._detokenize(oracle(srv.cfg, srv.params, srv._tokenize(prompt),
                                  new, length))


# --------------------------------------------------------- the scheduler


async def stream(sched, prompt, new, cancel_after=None, gate=None,
                 submitted=None, **ask):
    """One request's items until its end: (tokens, how it ended). It waits
    for ``gate`` first, hands what ``submit`` returned to ``submitted``,
    and cancels itself behind its ``cancel_after``-th token."""
    if gate is not None:
        await gate.wait()
    queue = asyncio.Queue()
    seq = sched.submit(prompt, max_new_tokens=new,
                       loop=asyncio.get_running_loop(), queue=queue, **ask)
    if submitted is not None:
        submitted(seq)
    out = []
    while True:
        kind, value, _ = await queue.get()
        if kind != "tok":
            return out, (kind, value)
        out.append(value)
        if cancel_after is not None and len(out) == cancel_after:
            sched.cancel(seq)


def serve(sched, prompts, new):
    """``prompts`` submitted together, greedy: every stream's tokens, in
    the prompts' order. A stream that ends otherwise than by its end
    raises."""
    async def one(prompt):
        out, (kind, value) = await stream(sched, prompt, new,
                                          temperature=0.0)
        if kind != "end":
            raise RuntimeError(f"{kind}: {value}")
        return out

    async def drive():
        return await asyncio.gather(*(one(p) for p in prompts))

    with jax.default_matmul_precision("highest"):
        return asyncio.run(drive())


def served(cfg, params, prompts, new, together=True, **kw):
    """``prompts`` through ONE ``ContinuousScheduler`` built with ``kw`` (on
    the reference lane unless it says otherwise), together or one after the
    other: (every stream's tokens, the scheduler's stats). Two programs were
    compiled, whatever was served."""
    from ray_tpu.serve._private.continuous import ContinuousScheduler

    sched = ContinuousScheduler(cfg, params, **{"attn": "reference", **kw})
    try:
        out = (serve(sched, prompts, new) if together
               else [serve(sched, [p], new)[0] for p in prompts])
        stats = sched.stats()
        assert sched.compiled_programs() == 2
    finally:
        sched.shutdown()
    return out, stats


def near_the_references_best(reference, prompt, out, tol=1e-3):
    """Every served token is the reference's choice or within ``tol`` of
    it (of the largest logit): ``reference(seq [1, S] int32) -> logits [1,
    S, vocab]``, on the prompt and what was served behind it."""
    seq = jnp.asarray([prompt + out[:-1]], jnp.int32)
    want = reference(seq)[0][len(prompt) - 1:]
    return all(logits.max() - logits[tok] <= tol * np.abs(want).max()
               for logits, tok in zip(want, out))
