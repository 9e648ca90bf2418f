"""The yardstick's arithmetic: the trace reduction (on hand-made events and
on a trace recorded here), percentiles and spreads, the MFU and roofline
functions, the traffic generator, and the plain references against the
program at a toy size."""

import math
import statistics

import numpy as np
import pytest

from perfbench.lib import hostwatch, peaks, stats, trace, traffic

MS = 1_000_000  # ns


# ------------------------------------------------------------- the trace


def test_union_total_and_subtract():
    merged = trace.union([(5, 9), (0, 3), (2, 4), (9, 9), (8, 12)])
    assert merged == [(0, 4), (5, 12)]
    assert trace.total(merged) == 11
    assert trace.subtract([(0, 10), (20, 30)], [(2, 4), (8, 22), (29, 40)]) \
        == [(0, 2), (4, 8), (22, 29)]


def test_busy_is_a_union_and_never_passes_the_window():
    # an enclosing `while` and its two children overlap: 8 ms busy, not 15
    ops = [("while.1", 0, 8 * MS), ("fusion.1", 0, 3 * MS),
           ("fusion.2", 4 * MS, 4 * MS), ("copy.3", 10 * MS, 2 * MS)]
    s = trace.summarize({"devices": [
        {"name": "d0", "ops": ops, "programs": [("jit_step", 0, 12 * MS)]}],
        "host": []})
    assert s["window_s"] == pytest.approx(0.012)
    assert s["busy_s"] == pytest.approx(0.010)
    assert s["busy_s"] <= s["window_s"]
    # self time: the while keeps only what its children do not cover
    assert s["ops"]["while"]["sum_s"] == pytest.approx(0.001)
    assert s["ops"]["fusion"] == {"count": 2,
                                  "sum_s": pytest.approx(0.007)}
    assert s["breakdown"]["device_ops"][0][0] == "fusion"
    assert len(s["breakdown"]["idle_gaps"]) == 1
    assert s["breakdown"]["idle_gaps"][0][1] == pytest.approx(0.002)


def test_programs_and_kernels_are_found_by_name():
    dev = {"name": "d0", "programs": [
        ("jit_paged_decode_step", 0, 2 * MS),
        ("jit_paged_prefill_into_slot", 3 * MS, 5 * MS),
        ("jit_paged_decode_step", 9 * MS, 4 * MS)],
        "ops": [("paged_attention.1", 0, 1 * MS), ("fusion.9", 1 * MS, MS),
                ("paged_attention.2", 3 * MS, 5 * MS),
                ("paged_attention.1", 9 * MS, 3 * MS)]}
    s = trace.summarize({"devices": [dev], "host": []})
    decode = trace.find(s["programs"], "paged_decode_step")
    assert decode["count"] == 2
    assert decode["median_s"] == pytest.approx(0.003)
    assert decode["sum_s"] == pytest.approx(0.006)
    assert trace.find(s["programs"], "no_such_program") is None
    assert trace.find(s["ops"], "paged_attention")["sum_s"] == \
        pytest.approx(0.009)
    in_decode = s["ops_by_program"]["jit_paged_decode_step"]
    assert in_decode["paged_attention"]["sum_s"] == pytest.approx(0.004)
    assert trace.program_name("jit_step_fn(123456)") == "jit_step_fn"


def test_collective_exposed_time_on_two_devices():
    # device 0: all-reduce 4..8 ms, hidden under a fusion for 4..6 ms
    # device 1: all-gather 2..3 ms with nothing beside it
    d0 = {"name": "d0", "programs": [("jit_step", 0, 10 * MS)], "ops": [
        ("fusion.1", 0, 6 * MS), ("all-reduce.1", 4 * MS, 4 * MS),
        ("fusion.2", 8 * MS, 2 * MS)]}
    d1 = {"name": "d1", "programs": [("jit_step", 0, 10 * MS)], "ops": [
        ("fusion.1", 0, 2 * MS), ("all-gather-start.1", 2 * MS, 1 * MS),
        ("fusion.2", 3 * MS, 7 * MS)]}
    s = trace.summarize({"devices": [d0, d1], "host": []})
    assert s["devices"] == 2
    assert s["collective_exposed_s"] == pytest.approx((0.002 + 0.001) / 2)
    assert s["busy_s"] == pytest.approx(0.010)  # the mean, not the sum
    assert s["busy_s_per_device"] == [pytest.approx(0.010)] * 2
    assert trace.is_collective("%reduce-scatter.3")
    assert not trace.is_collective("fusion.4")


def test_idle_gaps_say_what_the_host_was_doing():
    dev = {"name": "d0", "programs": [("jit_a", 0, 2 * MS),
                                      ("jit_b", 7 * MS, 2 * MS)],
           "ops": [("fusion.1", 0, 2 * MS), ("fusion.2", 7 * MS, 2 * MS)]}
    host = [("sample_tokens", 2 * MS + 1000, 4 * MS),
            ("thread_main", 0, 9 * MS)]  # spans the window: not a label
    s = trace.summarize({"devices": [dev], "host": host})
    (label, seconds), = s["breakdown"]["idle_gaps"]
    assert label == "jit_a -> jit_b | host: sample_tokens"
    assert seconds == pytest.approx(0.005)


def test_nothing_on_the_device_is_no_summary():
    assert trace.summarize({"devices": [], "host": [("x", 0, 5)]}) is None


def test_a_recorded_trace_is_read(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def little_step(x):
        return (x @ x).sum()

    x = jnp.ones((256, 256))
    little_step(x).block_until_ready()
    trace.start(str(tmp_path))
    for _ in range(3):
        little_step(x).block_until_ready()
    path = trace.stop(str(tmp_path))
    s = trace.summarize(trace.load(path))
    program = trace.find(s["programs"], "little_step")
    assert program and program["count"] == 3
    assert 0 < s["busy_s"] <= s["window_s"]
    assert trace.find(s["ops"], "dot") is not None
    assert "PLANE" in trace.describe(path)


# -------------------------------------------------------- the arithmetic


def test_percentile_matches_numpy_and_refuses_nothing():
    xs = [float(x) for x in np.random.default_rng(0).exponential(1, 257)]
    for q in (0, 50, 95, 99, 100):
        assert stats.percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q)))
    with pytest.raises(ValueError):
        stats.percentile([], 95)
    q1, _, q3 = statistics.quantiles([1, 2, 3, 4, 5, 6], n=4)
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx((q3 - q1) / 3.5)


GPT2S = {"vocab_size": 50257, "num_layers": 12, "embed_dim": 768,
         "num_heads": 12, "num_kv_heads": 12, "head_dim": 64,
         "mlp_dim": 3072, "mlp": "gelu"}
MISTRAL_L16 = {"vocab_size": 32768, "num_layers": 16, "embed_dim": 4096,
               "num_heads": 32, "num_kv_heads": 8, "head_dim": 128,
               "mlp_dim": 14336, "mlp": "swiglu"}


def test_mfu_arithmetic():
    # GPT-2 small: 12 x (4 d^2 + 8 d^2) + d V = 84.9M + 38.6M multiplied
    assert peaks.matmul_params(GPT2S) == 12 * 12 * 768 * 768 + 768 * 50257
    per_token = peaks.train_flops_per_token(GPT2S, 1024)
    assert per_token == 6 * peaks.matmul_params(GPT2S) \
        + 12 * 12 * 1024 * 768
    # 82,980 tokens/s on one v5e (PR 21's reading) is about 36% of peak
    assert peaks.mfu_percent(82980, GPT2S, 1024, 1, "TPU v5 lite") == \
        pytest.approx(100 * 82980 * per_token / 197e12)
    assert 30 < peaks.mfu_percent(82980, GPT2S, 1024, 1, "TPU v5 lite") < 40
    # four chips at the same rate: a quarter of the utilization
    assert peaks.mfu_percent(1e4, GPT2S, 1024, 4, "TPU v5 lite") == \
        pytest.approx(peaks.mfu_percent(1e4, GPT2S, 1024, 1,
                                        "TPU v5 lite") / 4)
    # Mistral block: 218.1M parameters, all of them multiplied
    block = (peaks.matmul_params(MISTRAL_L16) - 4096 * 32768) / 16
    assert block == 4096 * (32 + 16) * 128 + 4096 * 4096 + 3 * 4096 * 14336
    with pytest.raises(KeyError):
        peaks.peak("TPU v9000")


def test_attention_roofline_arithmetic():
    assert peaks.kv_bytes_per_token(MISTRAL_L16) == 65536  # 64 KB a token
    # decoding one token over 1000 cached: bound by the bytes
    t = peaks.attention_least_seconds(MISTRAL_L16, 1, 1000, "TPU v5 lite")
    assert t == pytest.approx(1000 * 65536 / 819e9)
    # a 512-token chunk over 1024: bound by the operations
    t = peaks.attention_least_seconds(MISTRAL_L16, 512, 1024, "TPU v5 lite")
    assert t == pytest.approx(4 * 512 * 1024 * 32 * 128 * 16 / 197e12)


# ------------------------------------------------------------ the traffic

CHAT = {"kind": "requests", "block": 32,
        "arrival": {"mode": "poisson", "rate_per_s": 5.0},
        "prompt_tokens": {"min": 128, "max": 2048, "body_max": 1024,
                          "tail_share": 0.1, "tail_alpha": 1.5},
        "output_tokens": {"min": 64, "max": 384, "body_max": 256,
                          "tail_share": 0.1, "tail_alpha": 1.5}}
DOCS = {"kind": "requests", "block": 32,
        "arrival": {"mode": "closed", "clients": 8},
        "documents": {"tokens": {"min": 2048, "max": 8192}, "asks": 4},
        "prompt_tokens": {"min": 32, "max": 128},
        "output_tokens": {"min": 32, "max": 96}}


def _take(mix, seed, n):
    stream = traffic.RequestStream(mix, seed, vocab=32768)
    return [stream.next() for _ in range(n)]


@pytest.mark.parametrize("mix", [CHAT, DOCS], ids=["chat", "docs"])
def test_generator_reproduces_from_its_seed(mix):
    a, b = _take(mix, 2**31 + 11, 70), _take(mix, 2**31 + 11, 70)
    assert [(r.prompt_ids, r.max_new_tokens, r.due_s) for r in a] == \
        [(r.prompt_ids, r.max_new_tokens, r.due_s) for r in b]
    c = _take(mix, 12, 70)
    assert [r.prompt_ids for r in a] != [r.prompt_ids for r in c]


def test_chat_lengths_and_arrivals():
    reqs = _take(CHAT, 5, 320)
    assert all(128 <= len(r.prompt_ids) <= 2048 for r in reqs)
    assert all(64 <= r.max_new_tokens <= 384 for r in reqs)
    assert max(len(r.prompt_ids) for r in reqs) > 1024  # the tail is there
    assert all(r.shared_tokens == 0 for r in reqs)
    assert all(0 < t < 32768 for r in reqs for t in r.prompt_ids[:8])
    dues = [r.due_s for r in reqs]
    assert dues == sorted(dues)
    assert dues[-1] == pytest.approx(320 / 5.0)  # mean gap exactly 1/rate
    # every seed gets the same set of sizes and gaps, in another order
    other = _take(CHAT, 6, 320)
    for block in range(10):
        mine, theirs = (x[32 * block:32 * block + 32] for x in (reqs, other))
        assert sorted(len(r.prompt_ids) for r in mine) == \
            sorted(len(r.prompt_ids) for r in theirs)
        assert sorted(r.max_new_tokens for r in mine) == \
            sorted(r.max_new_tokens for r in theirs)
    assert [len(r.prompt_ids) for r in reqs] != \
        [len(r.prompt_ids) for r in other]


def test_a_short_window_holds_the_same_work_whatever_the_seed():
    mix = dict(CHAT, shuffle=8)
    a, b = _take(mix, 5, 96), _take(mix, 2**31 + 6, 96)
    for i in range(0, 96, 8):
        mine, theirs = a[i:i + 8], b[i:i + 8]
        assert sorted(len(r.prompt_ids) for r in mine) == \
            sorted(len(r.prompt_ids) for r in theirs)
        assert sorted(r.max_new_tokens for r in mine) == \
            sorted(r.max_new_tokens for r in theirs)
        assert mine[-1].due_s == pytest.approx(theirs[-1].due_s)
    assert [len(r.prompt_ids) for r in a] != [len(r.prompt_ids) for r in b]
    # a block still holds all 32 quantiles, the tail among them
    assert sorted(len(r.prompt_ids) for r in a[:32]) == \
        traffic.block_lengths(CHAT["prompt_tokens"], 32)


def test_a_mix_can_fix_its_order_for_every_seed():
    mix = dict(CHAT, shuffle=8, order_seed=23)
    a, b = _take(mix, 5, 64), _take(mix, 2**31 + 6, 64)
    assert [(len(r.prompt_ids), r.max_new_tokens, r.due_s) for r in a] == \
        [(len(r.prompt_ids), r.max_new_tokens, r.due_s) for r in b]
    assert [r.prompt_ids for r in a] != [r.prompt_ids for r in b]
    sizes = [len(r.prompt_ids) for r in a[:32]]
    assert sizes != sorted(sizes)  # mixed, not a ramp


def test_documents_are_asked_four_times_over_four_sub_blocks():
    mix = dict(DOCS, shuffle=8)
    reqs = _take(mix, 5, 2 + 4 + 6 + 8 * 8)
    assert all(r.due_s is None for r in reqs)
    assert all(32 <= r.max_new_tokens <= 96 for r in reqs)
    steady = reqs[12:]  # the first three sub-blocks have fewer live documents
    seen = {}
    for i in range(0, len(steady), 8):
        sub = steady[i:i + 8]
        docs = [tuple(r.prompt_ids[:r.shared_tokens]) for r in sub]
        assert len(set(docs)) == 8  # eight live documents, one ask each
        new = [d for d in docs if d not in seen and not any(
            d == tuple(r.prompt_ids[:r.shared_tokens]) for r in reqs[:12])]
        assert len(new) == 2 or i == 0  # two documents open a sub-block
        for r, d in zip(sub, docs):
            assert 2048 <= r.shared_tokens <= 8192
            assert 32 <= len(r.prompt_ids) - r.shared_tokens <= 128
            seen[d] = seen.get(d, 0) + 1
    # documents opened and closed inside the stretch were asked four times
    counts = sorted(seen.values())
    assert counts[-1] == 4 and counts.count(4) >= 8
    tails = {tuple(r.prompt_ids[r.shared_tokens:]) for r in reqs}
    assert len(tails) == len(reqs)  # every question is fresh
    # the documents' sizes do not depend on the seed
    other = _take(mix, 6, len(reqs))
    assert sorted(r.shared_tokens for r in reqs) == \
        sorted(r.shared_tokens for r in other)


def test_training_batches():
    mix = {"kind": "tokens", "seq_len": 64, "unigram_skew": 3.0}
    a = traffic.token_batches(mix, 3, 4, 50257)
    b = traffic.token_batches(mix, 3, 4, 50257)
    first, second = next(a), next(a)
    assert first.shape == (4, 64) and first.dtype == np.int32
    assert (first == next(b)).all() and not (first == second).all()
    assert 0 <= first.min() and first.max() < 50257
    assert np.median(first) < 50257 / 4  # skewed toward the small ids


def test_length_quantiles_are_monotone_and_clipped():
    spec = CHAT["prompt_tokens"]
    xs = traffic.block_lengths(spec, 64)
    assert xs == sorted(xs) and xs[0] >= 128 and xs[-1] <= 2048
    gaps = traffic.block_gaps(4.0, 32)
    assert math.fsum(gaps) == pytest.approx(32 / 4.0)


# -------------------------------------------------------- the references


@pytest.mark.parametrize("family", ["mistral", "gpt2"])
def test_reference_agrees_with_the_program_and_can_disagree(family):
    import os

    import jax
    import jax.numpy as jnp

    from perfbench.lib import configs
    from perfbench.lib import manifest as manifest_lib
    from ray_tpu.models.transformer import forward, init_params

    tiny = manifest_lib.load(os.path.join(
        os.path.dirname(__file__), "tiny", "BENCHMARK.json"))
    hp = manifest_lib.config(tiny, "tiny_" + family)
    fam = manifest_lib.read_json_from_bench("families", family)
    cfg = configs.build_program_config(*configs.program_overrides(hp, fam))
    ref = manifest_lib.load_module(os.path.join(
        manifest_lib.BENCH_DIR, "reference", fam["reference"] + ".py"),
        "ref_" + family)
    params = init_params(cfg, jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 24), 0, 256)
    want = ref.forward(params, tokens, hp)
    got = forward(cfg, params, tokens)
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) / scale < 1e-5
    # the comparison is tight enough to catch changed mathematics
    wrong = dict(hp)
    if family == "mistral":
        wrong["rope_theta"] = 10000.0
    else:
        wrong["layer_norm_epsilon"] = 1e-2
    off = ref.forward(params, tokens, wrong)
    assert float(jnp.abs(off - want).max()) / scale > 1e-3


def test_tpu_op_events_are_whole_instructions():
    fusion = ("%iota_clamp_fusion.4 = s32[1024]{0:T(1024)S(1)} fusion(), "
              "kind=kLoop, calls=%fused_computation.440")
    kernel = ("%closed_call.9 = (bf16[128,12,1024,64]{3,2,1,0:T(8,128)(2,1)},"
              " f32[128,12,1024,1]{3,2,1,0:T(8,128)}) custom-call(bf16[128,12"
              ",1024,64]{3,2,1,0} %x), custom_call_target=\"tpu_custom_call\"")
    done = "%all-reduce-done.3 = f32[8]{0} all-reduce-done(f32[8]{0} %s.3)"
    assert trace.parse_op(fusion) == ("iota_clamp_fusion.4", "fusion")
    assert trace.op_family(fusion) == "iota_clamp_fusion"
    assert trace.parse_op(kernel) == ("closed_call.9", "custom-call")
    assert trace.op_family(kernel) == "closed_call [custom-call]"
    assert trace.is_collective(done) and not trace.is_collective(fusion)
    assert trace.parse_op("dot_general.1") == ("dot_general.1", "")


# ---------------------------------------------------- the host's own pauses


@pytest.mark.parametrize("pause_at,want", [(None, []), (3, [[0.06, 0.5]])],
                         ids=["no_pause", "one_slice_half_a_second_late"])
def test_the_waiting_client_keeps_the_slices_that_came_back_late(
        monkeypatch, pause_at, want):
    """A clock that moves only when slept on: the wait ends at its
    deadline, and a slice that overslept is kept with its offset."""
    now, slept = [100.0], []

    def sleep(seconds):
        slept.append(seconds)
        now[0] += seconds + (0.5 if len(slept) - 1 == pause_at else 0.0)

    monkeypatch.setattr(hostwatch.time, "perf_counter", lambda: now[0])
    monkeypatch.setattr(hostwatch.time, "sleep", sleep)
    late = []
    hostwatch.sleep_until(101.0, 100.0, late)
    assert now[0] == pytest.approx(101.0) and max(slept) <= hostwatch.SLICE_S
    assert [[a, round(b, 3)] for a, b in late] == want
    before = hostwatch.process_reading()
    assert set(hostwatch.delta(before, hostwatch.process_reading())) == {
        "cpu_s", "gc_full_collections"}
