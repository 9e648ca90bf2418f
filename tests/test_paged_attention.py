"""Paged attention: decode/verify/prefill that read KV pages in place.

Covers the op-level contracts of ``ops.paged_attention`` (the pure-JAX
reference against a full-softmax gathered-view oracle; the Pallas kernel —
interpret mode on CPU — bitwise against the reference; garbage-page
redirects, shared prefix pages, length-0 and page-boundary edges), the
paged programs in ``models.decode`` (each program on each implementation
against the SEQUENTIAL cache, ``decode.prefill`` + ``decode_step`` over a
``LayerKVCache``: the same tokens at temperature 0, logits within a float32
tolerance; ``attn="reference"`` against ``"pallas"`` bitwise), the
implementation resolver (unknown/falsy spellings rejected loudly at every
layer, satellite: the ``ops.attention`` impl typo guard), and the scheduler
end to end (token streams equal to the sequential cache's on both
implementations under mixed lengths, slot reuse and prefix hits;
spec-decode acceptance unchanged; the two-compiles contract; the fetch
counters).
"""

import asyncio

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tests import model_harness as harness

SLOTS = 4
CHUNK = 8
NEW = 6
PAGE = 4

PROMPTS = ["hi", "hello 123", "a much longer prompt than the others!"]


# --------------------------------------------------------------- op level


def _mk_pools(rng, S, K, H, Hkv, D, T, P, lengths, garbage_fill=0.0):
    """Random pools + per-slot tables covering ``lengths[s] + K`` tokens;
    table entries past a slot's need point at the garbage page 0, whose
    content is ``garbage_fill`` (non-zero proves redirects can't leak)."""
    need = [min(P, -(-(int(L) + K) // T)) for L in lengths]
    N = sum(need) + 1
    kp = rng.standard_normal((N, T, Hkv, D)).astype(np.float32)
    vp = rng.standard_normal((N, T, Hkv, D)).astype(np.float32)
    kp[0] = garbage_fill
    vp[0] = garbage_fill
    tables = np.zeros((S, P), np.int32)
    pid = 1
    for s in range(S):
        for j in range(need[s]):
            tables[s, j] = pid
            pid += 1
    q = rng.standard_normal((S, K, H, D)).astype(np.float32)
    # the arena's pool layout: one token's kv heads joined on the minor axis
    return (jnp.asarray(q), jnp.asarray(kp.reshape(N, T, Hkv * D)),
            jnp.asarray(vp.reshape(N, T, Hkv * D)),
            jnp.asarray(tables), jnp.asarray(np.asarray(lengths, np.int32)))


def _full_softmax_oracle(q, kp, vp, tables, lengths):
    """The gathered-view answer: materialize each slot's contiguous
    logical view and run a plain masked softmax — the semantics the
    in-place lanes must reproduce without ever building the view."""
    q, kp, vp = np.asarray(q), np.asarray(kp), np.asarray(vp)
    tables, lengths = np.asarray(tables), np.asarray(lengths)
    S, K, H, D = q.shape
    N, T, Hkv = kp.shape[0], kp.shape[1], kp.shape[2] // D
    P = tables.shape[1]
    G = H // Hkv
    sm = 1.0 / np.sqrt(D)
    out = np.zeros_like(q)
    for s in range(S):
        kv = kp[tables[s]].reshape(P * T, Hkv, D)
        vv = vp[tables[s]].reshape(P * T, Hkv, D)
        for i in range(K):
            qpos = lengths[s] + i
            for h in range(H):
                scores = kv[:, h // G] @ q[s, i, h] * sm
                scores[np.arange(P * T) > qpos] = -np.inf
                w = np.exp(scores - scores.max())
                w /= w.sum()
                out[s, i, h] = w @ vv[:, h // G]
    return out


class TestPagedAttentionOp:
    @pytest.mark.parametrize("impl", ["reference", "pallas"])
    @pytest.mark.parametrize("K,T,P,lengths", [
        (1, 4, 6, [0, 5, 8, 13]),       # 8 = exactly two full pages (T=4)
        (3, 4, 6, [0, 5, 8, 13]),
        # around a block (B * T = 512 tokens): B*T - 1, B*T, B*T + 1 tokens
        # attended by the K = 1 row, and several blocks plus a part
        (1, 16, 96, [510, 511, 512, 1300]),
        # a length-0 slot beside a long one
        (1, 16, 96, [0, 1500]),
        (4, 16, 96, [0, 509, 1100]),
    ], ids=["k1_pages", "k3_pages", "k1_block_edges", "k1_empty_beside_long",
            "k4_blocks"])
    def test_lanes_match_full_softmax_oracle(self, impl, K, T, P, lengths):
        """Mixed lengths — 0, exact page and block multiples and one to
        either side — for the decode (K=1) and verify (K>1) windows, with
        the garbage page stuffed with huge values behind every partly
        filled last block: the online-softmax block-streaming lanes must
        equal the materialized-view softmax."""
        from ray_tpu.ops.paged_attention import paged_attention, tile_sizes

        if T == 16:  # the cases are written for blocks of 512 tokens
            assert tile_sizes(K, 2, T, P, 2 * 8 * 4) == (32, K)
        rng = np.random.default_rng(0)
        args = _mk_pools(rng, S=len(lengths), K=K, H=4, Hkv=2, D=8, T=T,
                         P=P, lengths=lengths, garbage_fill=1e4)
        got = np.asarray(paged_attention(*args, impl=impl))
        want = _full_softmax_oracle(*args)
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("impl", ["reference", "pallas"])
    def test_chunk_sized_window_with_group_of_four(self, impl):
        """A prefill-chunk-sized window (K = 160, G = 4: 640 query rows,
        two query tiles of two matmuls' rows each) over contexts that end
        inside, at and past a block edge: tiles re-stream the blocks up to
        their own last position."""
        from ray_tpu.ops.paged_attention import paged_attention, tile_sizes

        pages, q_tile = tile_sizes(160, 4, 16, 64, 2 * 8 * 4)
        assert (pages, q_tile) == (32, 128)
        rng = np.random.default_rng(6)
        args = _mk_pools(rng, S=3, K=160, H=8, Hkv=2, D=8, T=16, P=64,
                         lengths=[0, 352, 700], garbage_fill=1e4)
        got = np.asarray(paged_attention(*args, impl=impl))
        want = _full_softmax_oracle(*args)
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)

    def test_bf16_pool_lanes_agree_and_track_the_oracle(self):
        """bf16 pools (the deployed dtype): operands go to the matmuls in
        bf16 with f32 accumulation, p rounded to bf16 before p.v as the
        flash kernel does. The lanes agree to 1e-6 (same math; measured 0)
        and sit within 2e-2 of the f32 oracle on the rounded pool
        (measured 4e-3: the rounding of p)."""
        from ray_tpu.ops.paged_attention import paged_attention

        rng = np.random.default_rng(7)
        q, kp, vp, tables, lengths = _mk_pools(
            rng, S=3, K=2, H=4, Hkv=2, D=8, T=16, P=96,
            lengths=[0, 511, 1200], garbage_fill=1e4)
        q, kp, vp = (x.astype(jnp.bfloat16) for x in (q, kp, vp))
        outs = {impl: np.asarray(paged_attention(
            q, kp, vp, tables, lengths, impl=impl), np.float32)
            for impl in ("reference", "pallas")}
        np.testing.assert_allclose(outs["pallas"], outs["reference"],
                                   atol=1e-6, rtol=0)
        want = _full_softmax_oracle(*(np.asarray(x, np.float32)
                                      for x in (q, kp, vp)), tables, lengths)
        np.testing.assert_allclose(outs["pallas"], want, atol=2e-2, rtol=0)

    @pytest.mark.parametrize("qk,group,T,P,row_bytes,want", [
        (1, 4, 16, 256, 2048, (32, 1)),      # Mistral decode: 2 MB a block
        (512, 4, 16, 1024, 2048, (32, 128)),  # its chunk: 4 tiles of 512 rows
        (1, 1, 16, 64, 1536, (32, 1)),       # GPT-2 small
        (512, 1, 16, 64, 1536, (32, 512)),
        (1, 2, 4, 6, 64, (4, 1)),            # never wider than the table
        (7, 4, 1024, 8, 64, (1, 7)),         # a page past the token cap
    ])
    def test_tile_sizes_follow_static_shapes(self, qk, group, T, P,
                                             row_bytes, want):
        from ray_tpu.ops.paged_attention import tile_sizes

        assert tile_sizes(qk, group, T, P, row_bytes) == want

    @pytest.mark.parametrize("K,G,T,P,lengths", [
        (1, 2, 4, 8, [0, 7, 16]),
        (4, 2, 4, 8, [0, 7, 16]),
        # a table no block divides (P = 6, blocks of 4 pages): the last
        # block's entries past the table read the garbage page
        (1, 2, 4, 6, [0, 9, 20]),
        (3, 2, 4, 6, [1, 13, 21]),
        # blocks of 512 tokens: one short, exact, one over, several + a part
        (1, 4, 16, 96, [510, 511, 512, 1300]),
        (4, 4, 16, 96, [507, 508, 509, 1300]),
    ], ids=["k1", "k4", "k1_ragged_table", "k3_ragged_table", "k1_blocks",
            "k4_blocks"])
    def test_pallas_interpret_bitwise_equals_reference(self, K, G, T, P,
                                                       lengths):
        """The kernel (interpret mode on CPU) and the pure-JAX reference
        share block order, operand dtypes, mask constant and online-softmax
        update — their outputs must match BITWISE, not just to tolerance."""
        from ray_tpu.ops.paged_attention import paged_attention

        rng = np.random.default_rng(1)
        args = _mk_pools(rng, S=len(lengths), K=K, H=2 * G, Hkv=2, D=8, T=T,
                         P=P, lengths=lengths, garbage_fill=123.0)
        ref = np.asarray(paged_attention(*args, impl="reference"))
        pal = np.asarray(paged_attention(*args, impl="pallas"))
        assert np.array_equal(ref, pal), \
            f"pallas diverged from reference (max |d| = " \
            f"{np.abs(ref - pal).max()})"

    @pytest.mark.parametrize("T,P,cursor", [(4, 4, 9), (16, 96, 700)],
                             ids=["pages", "blocks"])
    def test_shared_prefix_pages_between_slots(self, T, P, cursor):
        """Two slots whose tables point at the SAME physical pages (a
        radix prefix hit) with equal cursors must produce identical rows —
        paging relocates bytes, never values."""
        from ray_tpu.ops.paged_attention import paged_attention

        rng = np.random.default_rng(2)
        q, kp, vp, tables, lengths = _mk_pools(
            rng, S=2, K=1, H=4, Hkv=2, D=8, T=T, P=P, lengths=[cursor] * 2)
        q = jnp.concatenate([q[:1], q[:1]])          # same query both slots
        tables = jnp.concatenate([tables[:1], tables[:1]])  # shared pages
        for impl in ("reference", "pallas"):
            out = np.asarray(paged_attention(q, kp, vp, tables, lengths,
                                             impl=impl))
            assert np.array_equal(out[0], out[1])

    @pytest.mark.parametrize("T,P,lengths", [
        (4, 8, [2, 6, 11]), (16, 96, [3, 515, 1030])],
        ids=["pages", "partly_filled_last_block"])
    def test_garbage_page_content_never_leaks(self, T, P, lengths):
        """Masked pages must contribute bit-exact zeros to the online
        accumulator: stuffing the garbage page with huge values cannot
        change a single output bit."""
        from ray_tpu.ops.paged_attention import paged_attention

        for impl in ("reference", "pallas"):
            outs = []
            for fill in (0.0, 1e4):
                rng = np.random.default_rng(3)  # same content both times
                args = _mk_pools(rng, S=3, K=2, H=4, Hkv=2, D=8, T=T, P=P,
                                 lengths=lengths, garbage_fill=fill)
                outs.append(np.asarray(paged_attention(*args, impl=impl)))
            assert np.array_equal(outs[0], outs[1]), impl

    def test_length_zero_attends_only_the_new_token(self):
        """Cursor 0, K=1: the only legal position is the just-written
        token itself, so the output IS its value row, exactly (a
        single-position softmax has weight 1.0)."""
        from ray_tpu.ops.paged_attention import paged_attention

        rng = np.random.default_rng(4)
        q, kp, vp, tables, lengths = _mk_pools(
            rng, S=1, K=1, H=4, Hkv=2, D=8, T=4, P=4, lengths=[0])
        for impl in ("reference", "pallas"):
            out = np.asarray(paged_attention(q, kp, vp, tables, lengths,
                                             impl=impl))
            want = np.asarray(vp)[np.asarray(tables)[0, 0], 0].reshape(2, 8)
            for h in range(4):
                assert np.array_equal(out[0, 0, h], want[h // 2])

    @pytest.mark.parametrize("K", [1, 3])
    @pytest.mark.parametrize("idle", [[0], [1, 2], [3], [0, 1, 2, 3]],
                             ids=["first", "middle", "last", "all"])
    def test_slots_without_a_sequence_attend_nothing(self, K, idle):
        """A row a caller marks with length -K (no live sequence) returns
        zeros in both lanes, whatever its table holds and wherever it sits
        among the live rows (the kernel serves those first and hands its
        buffers from one to the next), and the live rows are what they are
        without it: the lanes stay bitwise equal to each other."""
        from ray_tpu.ops.paged_attention import paged_attention

        rng = np.random.default_rng(8)
        lengths = np.asarray([600, 30, 1100, 511], np.int32)
        q, kp, vp, tables, _ = _mk_pools(
            rng, S=4, K=K, H=4, Hkv=2, D=8, T=16, P=96, lengths=lengths,
            garbage_fill=1e4)
        want = _full_softmax_oracle(q, kp, vp, tables, lengths)
        want[idle] = 0.0
        lengths[idle] = -K   # the tables still name the stale pages
        outs = {impl: np.asarray(paged_attention(
            q, kp, vp, tables, jnp.asarray(lengths), impl=impl))
            for impl in ("reference", "pallas")}
        assert np.array_equal(outs["reference"], outs["pallas"])
        assert not outs["pallas"][idle].any()
        np.testing.assert_allclose(outs["pallas"], want, atol=1e-5,
                                   rtol=1e-5)

    def test_unknown_impl_and_shape_mismatches_rejected(self):
        from ray_tpu.ops.paged_attention import paged_attention

        rng = np.random.default_rng(5)
        q, kp, vp, tables, lengths = _mk_pools(
            rng, S=2, K=1, H=4, Hkv=2, D=8, T=4, P=4, lengths=[3, 3])
        # the gathered-view lane is gone: its name is refused like any
        # other unknown impl, never silently the reference
        with pytest.raises(ValueError, match="gather"):
            paged_attention(q, kp, vp, tables, lengths, impl="gather")
        with pytest.raises(ValueError, match="slot axis"):
            paged_attention(q[:1], kp, vp, tables, lengths)
        with pytest.raises(ValueError, match="head"):
            paged_attention(q[:, :, :3], kp, vp, tables, lengths)


# -------------------------------------------------------------- model lanes


def _tiny_cfg():
    from ray_tpu.models.transformer import TransformerConfig

    return TransformerConfig(vocab_size=64, num_layers=2, embed_dim=32,
                             num_heads=4, num_kv_heads=2, mlp_dim=64,
                             max_seq_len=32, dtype=jnp.float32,
                             param_dtype=jnp.float32, scan_layers=False,
                             remat=False)


@pytest.fixture(scope="module")
def tiny_model():
    from ray_tpu.models.transformer import init_params

    cfg = _tiny_cfg()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _arena(cfg, S, T, P):
    from ray_tpu.models.decode import init_paged_caches

    caches = init_paged_caches(cfg, S * P + 1, T, P, jnp.float32)
    tables = np.zeros((S, P), np.int32)
    pid = 1
    for s in range(S):
        for j in range(P):
            tables[s, j] = pid
            pid += 1
    return caches, jnp.asarray(tables)


def _paged_prefill(cfg, params, attn, prompts, T=4, P=8):
    """Prefill mixed-length prompts into one slot each, ``CHUNK`` tokens a
    call. Returns (each slot's last logits, caches, tables, cursors): the
    cursors are the caller's, the pool keeps none."""
    from functools import partial

    from ray_tpu.models.decode import StepRows, paged_prefill_into_slot

    S = len(prompts)
    caches, tables = _arena(cfg, S, T, P)
    prefill = jax.jit(partial(paged_prefill_into_slot, cfg, attn=attn,
                              logits=True))
    lasts = []
    first = jnp.zeros(S, jnp.int32)  # the programs' own ids
    # the step's rows the chunk's program takes along: none decodes yet,
    # and a row that is not active leaves the pool and the ids alone
    cursors = np.zeros(S, np.int32)
    idle = (np.zeros(S, np.float32), np.zeros(S, np.uint32))
    for s, ids in enumerate(prompts):
        for at in range(0, len(ids), CHUNK):
            chunk = list(ids[at:at + CHUNK])
            padded = chunk + [0] * (CHUNK - len(chunk))
            ends = at + CHUNK >= len(ids)
            first, caches, last = prefill(
                params, jnp.asarray([padded], jnp.int32),
                np.int32(len(chunk)), np.int32(at), tables[s], tables[s],
                caches, first, np.int32(s if ends else -1), np.float32(0),
                np.uint32(0), StepRows(np.zeros(S, np.int32), cursors,
                                       tables, tables, *idle))
            cursors[s] = at + len(chunk)
        lasts.append(np.asarray(last)[0])  # then the step's rows', unused
    # temperature 0: the id a prompt's last chunk left in its row is the
    # argmax of the logits it returned, and no other chunk touched the row
    assert np.array_equal(np.asarray(first), np.stack(lasts).argmax(-1))
    assert cursors.tolist() == [len(ids) for ids in prompts]
    return np.stack(lasts), caches, tables, cursors


def _drive_lane(cfg, params, attn, prompts, new_tokens, T=4, P=8):
    """Prefill mixed-length prompts into slots, then greedy-decode
    ``new_tokens`` steps. Returns (tokens per slot, stacked logits, caches,
    tables, cursors)."""
    from functools import partial

    from ray_tpu.models.decode import paged_decode_step

    S = len(prompts)
    lasts, caches, tables, cursors = _paged_prefill(cfg, params, attn,
                                                    prompts, T, P)
    step = jax.jit(partial(paged_decode_step, cfg, attn=attn, logits=True))
    toks, active = lasts.argmax(-1).astype(np.int32), np.ones(S, np.int32)
    greedy = (np.zeros(S, np.float32), np.zeros(S, np.uint32))
    out = [[int(t)] for t in toks]
    traces = []
    for _ in range(new_tokens):
        ids, caches, logits = step(params, jnp.asarray(toks),
                                   jnp.asarray(active), cursors, tables,
                                   tables, caches, *greedy)
        cursors = cursors + 1
        la = np.asarray(logits)
        traces.append(la)
        toks = la.argmax(-1).astype(np.int32)
        assert np.array_equal(np.asarray(ids), toks)  # the step's own ids
        for s in range(S):
            out[s].append(int(toks[s]))
    return out, np.stack(traces), caches, tables, cursors


def _sequential_logits(cfg, params, ids, feed):
    """The oracle: ``decode.prefill`` of ``ids`` then one ``decode_step``
    a token of ``feed``, over a ``LayerKVCache`` of one sequence. Returns
    the logits after the prompt and after each fed token, [1 + len(feed),
    vocab]."""
    tokens = jnp.asarray([list(ids) + list(feed)], jnp.int32)
    return np.asarray(harness.cached_logits(
        cfg, params, tokens, len(ids), cfg.max_seq_len, jnp.float32)[0])


PAGED_PROGRAMS = ("paged_prefill_into_slot", "paged_decode_step",
                  "paged_verify_step")


class TestInPlaceLanes:
    # mixed lengths: one exactly two pages (8 = 2 x T), one of two chunks
    PROMPT_IDS = [[1, 2, 3], [4, 5, 6, 7], [8] * 8,
                  [9, 10, 11, 12, 13, 14, 15, 16, 17, 18, 19, 20, 21]]

    @staticmethod
    def _close(got, want):
        """The same token at temperature 0 and logits within a float32
        tolerance (the paged op reduces in blocks, the oracle at once)."""
        assert np.array_equal(got.argmax(-1), want.argmax(-1))
        np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)

    @pytest.mark.parametrize("attn", ["reference", "pallas"])
    @pytest.mark.parametrize("program", PAGED_PROGRAMS)
    def test_program_matches_sequential_cache(self, tiny_model, program,
                                              attn):
        """Each paged program on each implementation against the
        sequential cache, which knows no page, table or block."""
        from functools import partial

        from ray_tpu.models.decode import paged_verify_step

        cfg, params = tiny_model
        if program == "paged_prefill_into_slot":
            lasts, *_ = _paged_prefill(cfg, params, attn, self.PROMPT_IDS)
            for ids, last in zip(self.PROMPT_IDS, lasts):
                self._close(last, _sequential_logits(cfg, params, ids,
                                                     [])[0])
        elif program == "paged_decode_step":
            toks, traces, *_ = _drive_lane(cfg, params, attn,
                                           self.PROMPT_IDS, NEW)
            for s, ids in enumerate(self.PROMPT_IDS):
                want = _sequential_logits(cfg, params, ids, toks[s][:-1])
                assert toks[s] == [int(t) for t in want.argmax(-1)]
                self._close(traces[:, s], want[1:])
        else:
            toks, _, caches, tables, cursors = _drive_lane(
                cfg, params, attn, self.PROMPT_IDS, 1)
            vt = np.asarray([[t[-1], 1, 2] for t in toks], np.int32)
            verify = jax.jit(partial(paged_verify_step, cfg, attn=attn))
            logits, _ = verify(params, jnp.asarray(vt),
                               jnp.full(len(toks), 3, jnp.int32), cursors,
                               tables, tables, caches)
            for s, ids in enumerate(self.PROMPT_IDS):
                want = _sequential_logits(cfg, params, ids,
                                          toks[s][:1] + list(vt[s]))
                self._close(np.asarray(logits)[s], want[2:])

    def test_decode_token_parity_and_pallas_bitwise(self, tiny_model):
        """Temperature-0 token streams must be identical on both
        implementations under mixed prompt lengths (one exactly
        page-aligned), and the kernel's logits must equal the reference's
        BITWISE at every step."""
        cfg, params = tiny_model
        ref, ref_tr, *_ = _drive_lane(cfg, params, "reference",
                                      self.PROMPT_IDS, NEW)
        pal, pal_tr, *_ = _drive_lane(cfg, params, "pallas",
                                      self.PROMPT_IDS, NEW)
        assert pal == ref
        assert np.array_equal(ref_tr, pal_tr), \
            "pallas logits diverged from reference bitwise"

    def test_verify_window_parity(self, tiny_model):
        """A K=3 verify window after mixed-length prefill: the kernel
        bitwise equal to the reference (so acceptance decisions are
        unchanged), and a row marked inactive changes nothing for the
        live rows."""
        from functools import partial

        from ray_tpu.models.decode import paged_verify_step

        cfg, params = tiny_model
        outs = {}
        for attn in ("reference", "pallas"):
            toks, _, caches, tables, cursors = _drive_lane(
                cfg, params, attn, self.PROMPT_IDS, 1)
            vt = np.asarray([[t[-1], 1, 2] for t in toks], np.int32)
            verify = jax.jit(partial(paged_verify_step, cfg, attn=attn))
            active = np.ones(len(toks), np.int32)
            logits, _ = verify(params, jnp.asarray(vt), jnp.asarray(active),
                               cursors, tables, tables, caches)
            outs[attn] = np.asarray(logits)
            # a row marked inactive (a retired slot's pages still in the
            # tables, whatever cursor it passes) changes nothing for the
            # live rows
            active[1] = 0
            logits, _ = verify(params, jnp.asarray(vt), jnp.asarray(active),
                               cursors, tables, tables, caches)
            assert np.array_equal(np.asarray(logits)[[0, 2]],
                                  outs[attn][[0, 2]]), attn
        assert np.array_equal(outs["reference"], outs["pallas"])

    @pytest.mark.parametrize("program", PAGED_PROGRAMS)
    def test_unknown_lane_rejected_before_any_math(self, tiny_model,
                                                   program):
        """``attn`` goes to ``paged_attention(impl=)``, which refuses what
        it does not know — the deleted gather lane among it — while the
        program is traced: nothing runs."""
        import ray_tpu.models.decode as decode

        cfg, params = tiny_model
        caches, tables = _arena(cfg, 2, 4, 8)
        if program == "paged_prefill_into_slot":
            args = (jnp.zeros((1, CHUNK), jnp.int32), np.int32(3),
                    np.int32(0), tables[0], tables[0], caches,
                    jnp.zeros(2, jnp.int32), np.int32(0), np.float32(0),
                    np.uint32(0), None)
        else:
            k = (2, 3) if program == "paged_verify_step" else (2,)
            args = (jnp.zeros(k, jnp.int32), jnp.ones(2, jnp.int32),
                    jnp.zeros(2, jnp.int32), tables, tables, caches)
            if program == "paged_decode_step":
                args += (jnp.zeros(2, jnp.float32), jnp.zeros(2, jnp.uint32))
        for bad in ("gather", "turbo", "auto", ""):
            with pytest.raises(ValueError, match="unknown paged attention"):
                jax.eval_shape(lambda *a, bad=bad: getattr(decode, program)(
                    cfg, params, *a, attn=bad), *args)

    @pytest.mark.parametrize("program", PAGED_PROGRAMS)
    def test_attn_is_a_required_keyword(self, program):
        """No default: a caller that names no implementation lands on none
        (at the parent it ran the gathered-view lane, silently)."""
        import ray_tpu.models.decode as decode

        positional = {"paged_prefill_into_slot": 12, "paged_decode_step": 9,
                      "paged_verify_step": 7}[program]
        with pytest.raises(TypeError, match="attn"):
            getattr(decode, program)(None, *([None] * positional))


# ------------------------------------------------------------- dispatchers


class TestLaneResolution:
    def test_attention_impl_typo_rejected(self):
        """Satellite: a typo'd ``attention(..., impl=)`` must raise with
        the valid choices, never silently fall through to the reference
        path."""
        from ray_tpu.ops.attention import attention

        q = jnp.zeros((1, 2, 2, 4))
        with pytest.raises(ValueError, match="flash"):
            attention(q, q, q, impl="flsah")
        # and a valid impl still runs
        out = attention(q, q, q, impl="reference")
        assert out.shape == q.shape

    def test_resolver_choices_and_falsy_rejection(self):
        from ray_tpu.ops.paged_attention import resolve_impl

        cfg = _tiny_cfg()
        # conftest pins the backend to CPU: the platform's choice is the
        # pure-JAX reference
        assert resolve_impl(cfg) == "reference"
        assert resolve_impl(cfg, "reference") == "reference"
        assert resolve_impl(cfg, "pallas") == "pallas"
        for bad in ("0", "", "off", "turbo", "auto", "gather"):
            with pytest.raises(ValueError, match="unknown paged attention"):
                resolve_impl(cfg, bad)


# ---------------------------------------------------------- the counters


class TestAttnCounters:
    """``Work.record`` adds up what ``streamed_tokens`` says each
    implementation fetches, in whole blocks of ``tile_sizes`` pages,
    against what is attended; and every other kind of layer counts its own
    (``work._KINDS``), under keys a model shows only if it has the kind."""

    @staticmethod
    def _work(lane, cfg=None, **sizes):
        from ray_tpu.models import llama_debug
        from ray_tpu.serve._private.work import Work

        # Mistral-7B's heads over two layers, bf16 pools
        cfg = cfg or llama_debug(embed_dim=4096, num_heads=32, num_kv_heads=8)
        sizes = {"slots": 5, "page_tokens": 16, "pages_per_slot": 256,
                 "itemsize": 2, **sizes}
        return Work(cfg, lane=lane, **sizes)

    @pytest.mark.parametrize("lane,attended,fetched", [
        # blocks of 32 pages = 512 tokens: 1 + 2 + 2 of the live rows' own
        # and none for an idle row
        ("pallas", 11 + 601 + 1024, (1 + 2 + 2) * 512),
        # every row, idle ones too, over the longest row's two blocks
        ("reference", 11 + 601 + 1024, 5 * 2 * 512),
    ])
    def test_decode_step(self, lane, attended, fetched):
        work = self._work(lane)
        work.record(1, [10, 600, 1023], idle_rows=2)
        # K and V, both layers, 2048 bytes a token row; + the 5 new rows
        assert work.stats() == {
            "attn_tokens_attended": attended, "attn_tokens_fetched": fetched,
            "attn_bytes_moved": 2 * 2 * 2048 * (fetched + 5)}

    def test_prefill_chunk_streams_once_per_query_tile(self):
        """A 512-token chunk at G = 4 is four query tiles of 128 tokens;
        each streams the blocks up to its own last position."""
        got = {}
        for lane in ("pallas", "reference"):
            work = self._work(lane)
            work.record(512, [700])
            got[lane] = work.stats()
        assert got["pallas"]["attn_tokens_attended"] == 828 + 956 + 1084 + 1212
        assert got["pallas"]["attn_tokens_fetched"] == (2 + 2 + 3 + 3) * 512
        assert (got["reference"]["attn_tokens_attended"],
                got["reference"]["attn_tokens_fetched"]) == (1212, 3 * 512)

    @pytest.mark.parametrize("kind", [
        "lightning-attn", "power-retention", "minicpm4", "sliding_attention",
        "experts"])
    def test_a_kind_counts_its_own_calls(self, kind):
        """A step over three live rows (cursors 10, 40, 99) beside one idle
        one, then a chunk of 16 tokens, 13 of them real, at position 32: the
        identities PERF.md 3 states in words, a kind a case."""
        from ray_tpu.models import (brumby_debug, mellum_debug,
                                    minicpm_sala_debug)

        preset = {"power-retention": brumby_debug,
                  "sliding_attention": mellum_debug,
                  "experts": mellum_debug}.get(kind, minicpm_sala_debug)
        cfg = preset()
        layers = cfg.kinds.count(kind)
        work = self._work("reference", cfg, slots=4, page_tokens=4,
                          pages_per_slot=64, itemsize=4)
        cursors = [10, 40, 99]
        work.record(1, cursors, idle_rows=1)
        work.record(16, [32], real=13)
        got = work.stats()
        chunk = np.arange(32, 32 + 13)
        if kind == "lightning-attn":
            assert got["linear_step_rows"] == layers * 3 == 18
            assert got["linear_chunk_calls"] == layers
            assert got["state_slots"] == 4 and got["state_bytes"] > 0
        elif kind == "power-retention":
            assert got["retention_step_rows"] == layers * 3
            assert got["retention_chunk_calls"] == layers
            assert got["retention_chunk_tokens"] == layers * 13
            # no layer holds a page: the counts of pages read a true 0
            assert (got["attn_tokens_fetched"], got["attn_bytes_moved"]) == (
                0, 0)
        elif kind == "minicpm4":
            at = np.concatenate([cursors, chunk])
            assert got["sparse_rows"] == layers * (3 + 13)
            assert got["sparse_rows_dense"] == layers * int(
                (at + 1 <= cfg.sparse.dense_len).sum())
            assert got["sparse_tokens_context"] == layers * int(
                (at + 1).sum())
            assert got["sparse_step_tokens_context"] == layers * (11 + 41 + 100)
            assert 0 < got["sparse_tokens_attended"] < got[
                "sparse_tokens_context"]
        elif kind == "sliding_attention":
            w, full = cfg.sliding_window, len(cfg.kinds) - layers
            assert got["window_attn_step_keys"] == layers * sum(
                min(c + 1, w) for c in cursors)
            assert got["full_attn_step_keys"] == full * sum(
                c + 1 for c in cursors)
            assert got["window_attn_chunk_pairs"] == layers * int(
                np.minimum(chunk + 1, w).sum())
            assert got["full_attn_chunk_pairs"] == full * int(
                (chunk + 1).sum())
        else:
            # a chunk's program that took a step along: two groups a layer
            counts = np.zeros((8, 2, cfg.moe_num_experts), np.int32)
            counts[:, 0, :3], counts[:, 1, 5] = 13, 9
            assert work.counts_experts
            # the program's 16 + 4 static rows chose top-k experts each
            routes = np.zeros((8, 1, 20, cfg.moe_top_k), np.int32)
            work.routed(({"counts": counts, "routes": routes},), 16)
            got = work.stats()
            # the two groups' rows are ONE kernel call a layer
            assert got["moe_kernel_calls"] == 8
            assert 8 * 4 <= got["moe_visits"] <= got["moe_grid_visits"]
            assert got["moe_rows_routed"] == counts.sum() == 8 * (39 + 9)
            assert got["moe_layer_calls"] == 16
            assert got["moe_experts_hit"] == 8 * 4
            assert got["moe_max_expert_rows"] == 8 * (13 + 9)
            assert got["moe_live_rows"] == 16
        # a kind's keys are there if and only if the model has the kind
        prefixes = {"lightning-attn": ("linear_", "state_", "sparse_"),
                    "minicpm4": ("linear_", "state_", "sparse_"),
                    "power-retention": ("retention_", "state_"),
                    "sliding_attention": ("window_", "full_attn_", "moe_"),
                    "experts": ("window_", "full_attn_", "moe_")}[kind]
        assert all(key.startswith(("attn_",) + prefixes) for key in got), got
        assert all(any(key.startswith(p) for key in got) for p in prefixes)


# ------------------------------------------------------------- end to end


def _sequential_reference(srv, prompt, new_tokens=NEW):
    return harness.sequential_text(srv, prompt, new_tokens, length=64)


class TestSchedulerLanes:
    def _drive(self, attn):
        from ray_tpu.serve.llm import LLMServerImpl

        # an arena of several blocks (a block is at most 512 tokens), so
        # that provisioning and live tokens can be told apart
        srv = LLMServerImpl(max_new_tokens=NEW, slots=SLOTS,
                            prefill_chunk=CHUNK, page_tokens=PAGE,
                            share_weights=False, attn=attn,
                            preset_overrides={"max_seq_len": 2048})
        try:
            async def go():
                reqs = [{"prompt": p} for p in PROMPTS * 3]  # > slots
                return await asyncio.gather(*[srv(r) for r in reqs])

            outs = asyncio.run(go())
            if attn == "reference":  # the kernel is held to this lane
                refs = {p: _sequential_reference(srv, p) for p in PROMPTS}
                assert [o["text"] for o in outs] == [
                    refs[p] for p in PROMPTS * 3]
            return [o["text"] for o in outs], srv.scheduler_stats()
        finally:
            srv.shutdown()

    def test_token_streams_identical_across_lanes(self):
        """The acceptance bar: temperature-0 token streams are identical on
        both implementations — and equal to the sequential cache's — under
        mixed lengths, slot reuse (3x slots) and prefix hits, and both
        keep the two-compiles contract."""
        texts = {}
        stats = {}
        for lane in ("reference", "pallas"):
            texts[lane], stats[lane] = self._drive(lane)
            assert stats[lane]["attn_lane"] == lane
            assert stats[lane]["compiled_programs"] == 2, stats[lane]
            assert stats[lane]["prefix_hits"] > 0
            assert stats[lane]["attn_bytes_moved"] > 0
            # the block fill share: what was attended of what was fetched
            assert 0 < stats[lane]["attn_tokens_attended"] \
                <= stats[lane]["attn_tokens_fetched"]
        assert texts["pallas"] == texts["reference"]
        # the kernel fetches each slot's own blocks, the reference every
        # row over the longest row's: never more
        assert stats["pallas"]["attn_tokens_fetched"] \
            <= stats["reference"]["attn_tokens_fetched"]

    def test_spec_decode_acceptance_unchanged_on_inplace_lane(self):
        """Speculative decoding rides the in-place verify lane unchanged:
        self-drafter at temperature 0 still accepts EVERY draft and the
        emitted text still equals the sequential greedy reference."""
        from ray_tpu.serve.llm import LLMServerImpl

        srv = LLMServerImpl(max_new_tokens=NEW, slots=SLOTS,
                            prefill_chunk=CHUNK, page_tokens=PAGE,
                            share_weights=False, attn="reference",
                            drafter="self", spec_k=3)
        try:
            ref = _sequential_reference(srv, "hello 123")
            out = asyncio.run(srv({"prompt": "hello 123"}))
            assert out["text"] == ref
            st = srv.scheduler_stats()
            assert st["attn_lane"] == "reference"
            assert st["spec_accept_rate"] == 1.0
            assert st["compiled_programs"] == 2
        finally:
            srv.shutdown()
