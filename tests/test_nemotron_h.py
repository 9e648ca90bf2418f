"""What NVIDIA-Nemotron-3-Nano-30B-A3B forces (ISSUE 59) against the plain
reference, ``perfbench/reference/nemotron_h.py``: layers that are ONE
sublayer each (a Mamba-2 mixer, an attention mixer without rotation or an
expert feed-forward alone, read off ``layer_pattern``), the 'mamba2' state
kind with its two states a sequence, two-matrix ``relu^2`` experts beside a
shared expert of its own width, and an expert layer that holds a SHARE of
the experts its router scores. Float32 on the CPU at a toy size.

The system is held to the reference at 1e-4 of the largest logit through
every forward — without a cache, the contiguous cache (prefill then decode)
and the paged chunk, step and fused turn — on a model that holds experts 2-5
of its 8: the program is float32 here, both sides compute the same sums in
another order, and what is left is rounding (read: 3e-7), three hundred
times under the limit, while every named fault reads above 1e-3. Named
faults planted in the reference are refused by the same comparison.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from perfbench.reference import nemotron_h as ref  # noqa: E402
from ray_tpu.models import presets  # noqa: E402
from ray_tpu.models.decode import (init_caches,  # noqa: E402
                                   init_paged_caches)
from ray_tpu.models.transformer import (ATTENTION, MAMBA, NONE,  # noqa: E402
                                        count_params, init_params,
                                        logical_axes, state_shapes)
from ray_tpu.ops import moe, ssm  # noqa: E402
from ray_tpu.ops.ssm import SsmSizes  # noqa: E402
from tests import model_harness as harness  # noqa: E402
from tests.model_harness import rel  # noqa: E402

TOL = 1e-4
# experts 2-5 of the 8, in five layers where 'M*', '*E', 'EM' and 'ME' all
# occur (the preset's own seven, 'MEM*EME', go through the whole stack in
# tests/perfbench/test_perfbench_nemotron.py's rehearsal): every program
# here is compiled layer by layer, and the file's time is its compiles
HELD = dict(moe_held_first=2, moe_held_count=4, num_layers=5,
            layer_pattern="M*EME")


def hp_of(cfg):
    """The reference's configuration object, keyed as the source keys it."""
    return {"hybrid_override_pattern": cfg.layer_pattern,
            "mamba_num_heads": cfg.ssm_num_heads,
            "mamba_head_dim": cfg.ssm_head_dim, "n_groups": cfg.ssm_groups,
            "ssm_state_size": cfg.ssm_state_dim,
            "conv_kernel": cfg.ssm_conv_kernel, "norm_eps": cfg.norm_eps,
            "num_experts_per_tok": cfg.moe_top_k,
            "norm_topk_prob": cfg.moe_renormalize,
            "routed_scaling_factor": cfg.moe_routed_scale,
            "n_shared_experts": cfg.moe_shared_experts,
            "experts_held_first": cfg.moe_held_first}


# weights with every norm's scale and the skip away from 1 (a norm left out,
# or one scale taken for another, then shows)
seeded = functools.partial(
    harness.seeded, stir=("scale", "'norm'", "d_skip"), by=0.3)


PADDED = 80  # every sound reference call is one row of so many tokens


def reference(params, tokens, hp, routes=None):
    """The reference's logits for tokens [B, S], a row at a time and padded
    behind to ONE length (a causal model's earlier positions see no
    padding): its jitted pieces are compiled once for the whole file."""
    tokens, S = np.asarray(tokens), tokens.shape[1]
    pad = ((0, 0), (0, PADDED - S))
    rows = []
    for b in range(tokens.shape[0]):
        given = None if routes is None else np.pad(
            routes[:, b:b + 1], ((0, 0),) + pad + ((0, 0),))
        rows.append(ref.forward(params, jnp.asarray(
            np.pad(tokens[b:b + 1], pad)), hp, given)[0, :S])
    return np.stack(rows)


def plant(m, hp, sound_hp, rebuilt):
    """The reference's compiled pieces (``ref._pieces``) with those named in
    ``rebuilt`` traced anew, so that what a test planted in the module is
    what they run; the others stay as the sound reference compiled them (a
    fault that sat in a piece not named here would go unseen, and its test
    would fail)."""
    key = lambda h: tuple(h.get(k, 0) for k in ref._KEYS)
    pieces = {**ref._pieces(key(sound_hp)),
              **{n: ref._pieces.__wrapped__(key(hp))[n] for n in rebuilt}}
    m.setattr(ref, "_pieces", lambda sizes: pieces)


@pytest.fixture(scope="module")
def toy():
    cfg = presets.nemotron_h_debug(**HELD)
    params = seeded(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 41), 0,
                                cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        logits, routes = harness.forward_program(cfg, return_routes=True)(
            params, tokens)
    routes = np.asarray(routes)
    return {"cfg": cfg, "params": params, "tokens": tokens,
            "logits": np.asarray(logits), "routes": routes,
            "want": reference(params, tokens, hp_of(cfg), routes)}


# ------------------------------------------------- what a layer is made of


def test_a_layer_holds_what_the_pattern_gives_it_and_no_more():
    cfg = presets.nemotron_h_debug()
    assert cfg.layer_pattern == "MEM*EME"
    assert cfg.kinds == (MAMBA, NONE, MAMBA, ATTENTION, NONE, MAMBA, NONE)
    ffs = [cfg.mlp_of(i) for i in range(cfg.num_layers)]
    assert ffs == [NONE, "moe", NONE, NONE, "moe", NONE, "moe"]
    assert (cfg.expert_layers, cfg.period, cfg.lead_layers) == (3, 7, 0)
    assert cfg.recurrent and cfg.holds_pages and cfg.pos == "none"
    for scan in (True, False):
        c = dataclasses.replace(cfg, scan_layers=scan)
        params = jax.eval_shape(lambda: init_params(c, jax.random.PRNGKey(0)))
        axes = logical_axes(c)
        assert (jax.tree.structure(params) == jax.tree.structure(
            axes, is_leaf=lambda a: isinstance(a, tuple)))
        for i, (kind, ff) in enumerate(zip(c.kinds, ffs)):
            layer = params["blocks"][f"p{i}" if scan else str(i)]
            assert set(layer) == ({"attn", "ln1"} if kind != NONE
                                  else {"ln2", "mlp"}), i
        experts = params["blocks"]["p1" if scan else "1"]["mlp"]
        assert set(experts) == {"w_router", "e_bias", "w_up_t", "w_down",
                                "ws_up", "ws_down"}  # no gate anywhere
    with pytest.raises(ValueError, match="layer_pattern"):
        presets.nemotron_h_debug(layer_pattern="MEM?EME")
    with pytest.raises(ValueError, match="layer_pattern"):
        presets.nemotron_h_debug(num_layers=6)
    with pytest.raises(ValueError, match="moe_held"):
        presets.nemotron_h_debug(moe_held_first=6, moe_held_count=4)


def test_the_published_sizes_count_the_published_parameters():
    """23 'M' + 6 '*' + 23 'E' + embedding and head = 31.58B, the published
    31.6B (shapes only); and the benchmark's cut."""
    cfg = presets.nemotron_h()
    assert (cfg.layer_pattern.count("M"), cfg.layer_pattern.count("*"),
            cfg.layer_pattern.count("E")) == (23, 6, 23)
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    mamba, attn, experts = 38_744_896, 23_399_040, 1_297_468_160
    assert count_params(shapes["blocks"]["p0"]) == mamba
    assert count_params(shapes["blocks"]["p5"]) == attn
    assert count_params(shapes["blocks"]["p1"]) == experts
    assert count_params(shapes) == (
        23 * mamba + 6 * attn + 23 * experts + 2 * 131072 * 2688
        + 2688) == 31_577_940_288  # the last: the final norm
    cut = presets.nemotron_h(num_layers=9, layer_pattern="MEMEM*EME",
                             vocab_size=65536, moe_held_count=64)
    held = jax.eval_shape(lambda: init_params(cut, jax.random.PRNGKey(0)))
    assert count_params(held) == 3_166_244_352
    assert state_shapes(cut, MAMBA, 1) == {"conv": (1, 3, 6144),
                                           "ssm": (1, 8, 128, 512)}


# digests at the PARENT of this change (commit 3b22fbc), made by
# ``weights_digest`` below, run there: the blocks,
# the expert layer and the paged forward are shared with every accepted
# configuration, and none of them may see this change at all. A seed's
# weights for three presets that between them have every branch of the
# mixers' parameters but the window's and the indexer's (sparse, linear,
# retention, latent, a leading dense layer, a bias and a shared expert);
# ``tests/test_mellum.py`` holds ``moe_debug``'s and ``llama_debug``'s two
# lowered paged programs. (All seven presets'
# weights, two programs and training loss were compared with the parent's
# once, by hand: PERF.md 6, PR 59.)
PARENT_WEIGHTS = {
    "minicpm_sala_debug": "8a77934d584065dc",
    "brumby_debug": "8cf2eb45fc72f2f9",
    "glm_moe_lite_debug": "acd197e638536554",
}


def weights_digest(name: str) -> str:
    cfg = getattr(presets, name)(max_seq_len=128)
    return hashlib.sha256(b"".join(
        np.asarray(a).tobytes() for a in jax.tree.leaves(
            init_params(cfg, jax.random.PRNGKey(5))))).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(PARENT_WEIGHTS))
def test_an_accepted_models_seeded_weights_are_the_parents(name):
    assert weights_digest(name) == PARENT_WEIGHTS[name]


# ------------------------------------------------------ against the reference


def test_forward_logits_match_the_reference(toy):
    assert rel(toy["logits"], toy["want"]) <= TOL
    assert toy["routes"].shape == (2, 2, 41, 3)  # a row an EXPERT layer
    # the routes the program took are the reference's own
    assert rel(toy["logits"][:1], reference(
        toy["params"], toy["tokens"][:1], hp_of(toy["cfg"]))) <= TOL


def _hp(**changed):
    return lambda m, hp: {**hp, **changed}


def _no_carry_across_chunks(m, hp):
    """A convolution that starts from zeros at every 16th token: what a
    chunked forward that carried no inputs would compute."""
    true = ref.causal_conv
    m.setattr(ref, "causal_conv", lambda x, w, b: jnp.concatenate(
        [true(x[:, c:c + 16], w, b) for c in range(0, x.shape[1], 16)], 1))
    return hp


def _dt_without_softplus(m, hp):
    m.setattr(jax.nn, "softplus", lambda x: x)
    return hp


def _gate_after_the_norm(m, hp):
    true = ref.rms_norm
    m.setattr(ref, "gated_norm", lambda y, z, scale, groups, eps: (
        true(y.reshape(*y.shape[:2], groups, -1), scale.reshape(groups, -1),
             eps).reshape(y.shape) * jax.nn.silu(z)))
    return hp


def _norm_over_the_whole_width(m, hp):
    true = ref.gated_norm
    m.setattr(ref, "gated_norm",
              lambda y, z, scale, groups, eps: true(y, z, scale, 1, eps))
    return hp


def _relu_not_squared(m, hp):
    m.setattr(ref, "relu2", lambda h, up, down: jax.nn.relu(h @ up) @ down)
    return hp


def _normalised_over_the_held_chosen(m, hp):
    """Weights over the sum of the chosen experts THIS chip holds (2-5), not
    over all the chosen."""
    def weights(scores, bias, routes, hp_):
        if routes is None:
            routes = jax.lax.top_k(scores + bias,
                                   hp_["num_experts_per_tok"])[1]
        taken = jax.nn.one_hot(routes, scores.shape[-1]).sum(-2)
        here = (jnp.arange(scores.shape[-1]) >= 2) & (
            jnp.arange(scores.shape[-1]) < 6)
        w = scores * taken * here
        return w / (w.sum(-1, keepdims=True) + 1e-20) * hp_[
            "routed_scaling_factor"]

    m.setattr(ref, "token_weights", weights)
    return hp


def _bias_left_in_the_weights(m, hp):
    true = ref.token_weights
    m.setattr(ref, "token_weights",
              lambda s, b, r, hp_: true(s + b, 0 * b, r, hp_))
    return hp


def _rotated_attention(m, hp):
    from perfbench.reference.mistral import rotate

    true = ref.common.causal_attention
    m.setattr(ref.common, "causal_attention", lambda q, k, v: true(
        rotate(q, 10000.0), rotate(k, 10000.0), v))
    return hp


# name: (what is planted, the reference's pieces that read it)
FAULTS = {
    "no_convolution_carry_across_chunks": (_no_carry_across_chunks, "M"),
    "dt_without_softplus": (_dt_without_softplus, "M"),
    "gate_after_the_norm": (_gate_after_the_norm, "M"),
    "norm_over_4096_instead_of_groups": (_norm_over_the_whole_width, "M"),
    "relu_not_squared": (_relu_not_squared, ("shared", "feed")),
    "weights_normalised_over_the_held_chosen":
        (_normalised_over_the_held_chosen, ("weigh",)),
    "bias_in_the_weights": (_bias_left_in_the_weights, ("weigh",)),
    "shared_expert_left_out": (_hp(n_shared_experts=0), ()),
    "scaling_factor_1": (_hp(routed_scaling_factor=1.0), ("weigh",)),
    "attention_rotated": (_rotated_attention, "*"),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_named_fault_is_refused(toy, monkeypatch, fault):
    """Each fault planted in the reference moves its logits ten times past
    the limit the sound reference stays within
    (``test_forward_logits_match_the_reference``), given the program's
    routes."""
    sound = hp_of(toy["cfg"])
    planted, pieces = FAULTS[fault]
    hp = planted(monkeypatch, sound)
    plant(monkeypatch, hp, sound, pieces)
    err = rel(toy["logits"][:1], reference(
        toy["params"], toy["tokens"][:1], hp, toy["routes"][:, :1]))
    # (a step that is not positive overflows the decay: NaN is refused too)
    assert not err <= 10 * TOL, err


@pytest.mark.parametrize("n", [37])
def test_prefill_and_decode_step_match_the_reference(toy, n):
    """The contiguous cache: a prompt of n tokens in one call of the chunked
    scan (blocks of 16: 37 is two whole blocks and five tokens, so the
    product across blocks and the padding are in what is compared), then
    steps on the states and keys it left."""
    cfg, params, tokens = toy["cfg"], toy["params"], toy["tokens"]
    caches = init_caches(cfg, 2, 48)
    assert [c is None for c in caches] == [k == NONE for k in cfg.kinds]
    got = harness.cached_logits(cfg, params, tokens[:, :41], n, length=48)
    assert rel(got, toy["want"][:, n - 1:]) <= TOL


# ------------------------------------------------------------ the two scans


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _scan_case(sizes: SsmSizes, B: int, S: int, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    H, P, G, N = sizes.heads, sizes.head_dim, sizes.groups, sizes.state
    normal = jax.random.normal
    return dict(
        x=normal(ks[0], (B, S, H, P)), Bm=normal(ks[1], (B, S, G, N)) * 0.3,
        Cm=normal(ks[2], (B, S, G, N)) * 0.3,
        dt=jax.nn.softplus(normal(ks[3], (B, S, H)) - 2.0),
        a=-jnp.exp(jax.random.uniform(ks[4], (H,), minval=0.0, maxval=2.5)),
        d=normal(ks[5], (H,)),
        state=normal(ks[6], ssm.state_shapes(B, sizes)["ssm"]))


def _token_by_token(case, sizes, real, impl):
    state, ys = case["state"], []
    B = state.shape[0]
    step = jax.jit(functools.partial(ssm.ssd_step, sizes=sizes, impl=impl))
    for t in range(real):
        y, state = step(
            case["x"][:, t], case["dt"][:, t], case["a"], case["Bm"][:, t],
            case["Cm"][:, t], case["d"], state, jnp.ones((B,), jnp.int32))
        ys.append(y)
    return jnp.stack(ys, 1), state


@pytest.mark.parametrize("impl,sizes,S,real", [
    ("reference", SsmSizes(4, 64, 2, 128, 4, 128), 200, 150),
    ("pallas", SsmSizes(4, 64, 2, 128, 4, 128), 200, 150)],
    ids=["jnp_published_head", "kernel_published_head"])
def test_the_chunked_scan_is_the_recurrence(impl, sizes, S, real):
    """Blocks with the state passed between them = the recurrence a token
    at a time, over a boundary that is no multiple of the block (200 tokens
    of which 150 are real: one whole block of 128, 22 tokens and padding),
    from a state that is not zero; the padding touches nothing. The kernels
    run interpreted, at the published head and state sizes."""
    case = _scan_case(sizes, 2, S)
    with jax.default_matmul_precision("highest"):
        y, state = jax.jit(functools.partial(
            ssm.ssd_chunk, sizes=sizes, impl=impl))(
            case["x"], case["dt"], case["a"], case["Bm"], case["Cm"],
            case["d"], case["state"], real_len=jnp.int32(real))
        want_y, want_state = _token_by_token(case, sizes, real, "reference")
        if impl == "pallas":  # the step's kernel beside its jnp form
            ky, kstate = _token_by_token(case, sizes, 3, impl)
            assert rel(ky, np.asarray(want_y[:, :3])) <= 1e-5
    assert rel(y[:, :real], np.asarray(want_y)) <= 1e-5
    assert rel(state, np.asarray(want_state)) <= 1e-5


@pytest.mark.parametrize("impl", ["reference", None],
                         ids=["jnp", "kernel"])
def test_a_step_leaves_idle_rows_bitwise_alone(impl):
    sizes = SsmSizes(4, 64, 2, 128, 4, 128)
    case = _scan_case(sizes, 3, 1)
    active = jnp.asarray([1, 0, 1], jnp.int32)
    _, state = jax.jit(functools.partial(ssm.ssd_step, sizes=sizes,
                                         impl=impl))(
        case["x"][:, 0], case["dt"][:, 0], case["a"], case["Bm"][:, 0],
        case["Cm"][:, 0], case["d"], case["state"], active)
    before, after = np.asarray(case["state"]), np.asarray(state)
    assert np.array_equal(after[1], before[1])
    assert not np.array_equal(after[0], before[0])
    # and so does the convolution: the inputs an idle row carries stay
    carried = jax.random.normal(jax.random.PRNGKey(1), (3, 3, 24))
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 1, 24))
    w = jax.random.normal(jax.random.PRNGKey(3), (4, 24))
    out, new = ssm.causal_conv(x, carried, w, jnp.zeros(24), active=active)
    assert np.array_equal(np.asarray(new[1]), np.asarray(carried[1]))
    assert np.array_equal(np.asarray(new[0, :2]), np.asarray(carried[0, 1:]))
    assert np.array_equal(np.asarray(new[0, 2]), np.asarray(x[0, 0]))


def test_a_chunks_convolution_carries_the_last_real_inputs():
    """Chunks of 7 with 2 tokens of padding each = the convolution of the
    whole sequence, and what is carried is the last three REAL inputs."""
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 15, 24))
    w = jax.random.normal(jax.random.PRNGKey(3), (4, 24))
    b = jax.random.normal(jax.random.PRNGKey(4), (24,))
    whole, _ = ssm.causal_conv(x, jnp.zeros((1, 3, 24)), w, b)
    carried, outs = jnp.zeros((1, 3, 24)), []
    for lo in range(0, 15, 5):
        chunk = jnp.pad(x[:, lo:lo + 5], ((0, 0), (0, 2), (0, 0)),
                        constant_values=9.0)
        out, carried = ssm.causal_conv(chunk, carried, w, b,
                                       real_len=jnp.int32(5))
        outs.append(out[:, :5])
    assert rel(jnp.concatenate(outs, 1), np.asarray(whole)) <= 1e-6
    assert np.array_equal(np.asarray(carried), np.asarray(x[:, 12:]))


# ----------------------------------------------------- the chip's share


def test_the_two_shares_add_up_to_the_uncut_layer():
    """Chip 0's routed part (experts 0-3) + chip 1's (4-7) + the shared
    expert ONCE = the uncut layer's output; each share's counts are over the
    experts it holds, and together they are every choice made."""
    cfg = presets.nemotron_h_debug()
    d, f, E, k = cfg.embed_dim, cfg.hidden_dim, cfg.moe_num_experts, 3
    p = moe.init_moe_params(jax.random.PRNGKey(3), d, f, E, choice_bias=True,
                            shared_dim=96, activation="relu2")
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 19, d))
    valid = jnp.ones((2, 19), bool).at[1, 11:].set(False)
    kw = dict(num_experts=E, top_k=k, dtype=jnp.float32, valid=valid,
              scoring="sigmoid", routed_scale=2.5, activation="relu2")
    own = lambda q, first: {**q, **{n: q[n][..., first:first + 4, :, :]
                                    for n in ("w_up_t", "w_down")}}

    @jax.jit
    def layers(p, x):
        """The uncut layer, the two chips' shares, and chip 1's again on
        the kernel's path (a stack of layers and a layer index)."""
        stack = jax.tree.map(lambda a: jnp.stack([a, a]), p)
        return (moe.moe_layer(p, x, **kw),
                [moe.moe_layer(own(p, first), x, held=(first, 4), **kw)
                 for first in (0, 4)],
                moe.moe_layer(own(stack, 4), x, held=(4, 4), layer=1, **kw))

    with jax.default_matmul_precision("highest"):
        (whole, _, counts, routes), shares, stacked = layers(p, x)
        shared = jnp.where(valid[..., None], ref.relu2(
            x, p["ws_up"], p["ws_down"]), 0)
    for _, _, _, r in shares:
        assert np.array_equal(np.asarray(r), np.asarray(routes))
    parts = [y - shared for y, _, _, _ in shares]
    held_counts = [np.asarray(c) for _, _, c, _ in shares]
    assert rel(parts[0] + parts[1] + shared, np.asarray(whole)) <= 1e-6
    assert np.array_equal(np.concatenate(held_counts), np.asarray(counts))
    assert counts.sum() == k * int(valid.sum())
    assert rel(stacked[0] - shared, np.asarray(parts[1])) <= 1e-5
    assert np.array_equal(np.asarray(stacked[2]), held_counts[1])


# ------------------------------------------------------ the paged programs


def _paged(request):
    """Two prompts through the paged programs (``harness.paged_drive``).
    Slot 1 takes a 53-token prompt in chunks of 16 (over three chunk
    boundaries, ending inside a chunk: the states are carried from chunk to
    chunk, the last one's padding touches neither); slot 2 then a 33-token
    prompt whose chunks take slot 1's decode row along (the fused turn);
    then plain steps of both. Slots 0 and 3 hold no sequence; their states
    are filled with a value that must come back bitwise, and every page no
    table names is FILLED WITH NaN in the attention layer's pool."""
    cfg = presets.nemotron_h_debug(**HELD)
    slots, T, P = 4, 4, 24
    caches = init_paged_caches(cfg, slots * P + 1, T, P, slots=slots)
    idle = jnp.asarray([0, 3])
    caches = [c if k != MAMBA else dataclasses.replace(
        c, conv=c.conv.at[idle].set(7.0), ssm=c.ssm.at[idle].set(7.0))
        for c, k in zip(caches, cfg.kinds)]
    # slot 2's states hold another sequence's leavings: a chunk at position
    # 0 must start from zero on the device
    caches = [c if k != MAMBA else dataclasses.replace(
        c, conv=c.conv.at[2].set(3.0), ssm=c.ssm.at[2].set(3.0))
        for c, k in zip(caches, cfg.kinds)]
    return dict(
        cfg=cfg, params=seeded(cfg), impl="reference", caches=caches,
        tokens=jax.random.randint(jax.random.PRNGKey(9), (2, 80), 0,
                                  cfg.vocab_size),
        tables=harness.slot_tables(slots, P, (1, 2)),
        lengths={1: 53, 2: 33}, chunk=16, steps=5, moe_info=True)


paged_run = harness.paged_fixture(_paged)


@pytest.mark.parametrize("slot", [1, 2])
def test_paged_chunks_steps_and_fused_turns_match_the_reference(paged_run,
                                                                slot):
    run = paged_run
    cfg, n, end = run["cfg"], run["n"][slot], run["cursor"][slot]
    seq = run["tokens"][run["row"][slot]][None, :end]
    routes = np.concatenate(run["routes"][slot], 1)[:, None]
    assert routes.shape[2] == end
    for i, info in enumerate(run["info"]):
        assert info["routes"].shape[0] == cfg.expert_layers
        if i < 4 + 3:  # the chunks' programs: held experts, a group of rows
            assert info["counts"].shape == (2, 2, 4)
    got = harness.slot_logits(run, slot)
    want = reference(run["params"], seq, hp_of(cfg), routes)[0]
    assert rel(got, want[n - 1:]) <= TOL


def test_the_paged_programs_left_the_idle_slots_states_bitwise(paged_run):
    cfg = paged_run["cfg"]
    for c, kind in zip(paged_run["caches"], cfg.kinds):
        assert (c is None) == (kind == NONE)
        if kind == MAMBA:
            for state in (c.conv, c.ssm):
                assert (np.asarray(state)[[0, 3]] == 7.0).all()
    harness.poisoned_pages_left_alone(paged_run)
    # every choice a live row made is counted, here or as left out
    rows = sum(paged_run["cursor"].values())
    held, left_out = (sum(int(np.asarray(info[key]).sum())
                          for info in paged_run["info"])
                      for key in ("counts", "left_out"))
    assert held + left_out == cfg.expert_layers * cfg.moe_top_k * rows
    assert 0 < left_out < held + left_out


# ------------------------------------------------------------ the scheduler


def test_the_scheduler_serves_the_kind_and_counts_its_work():
    """``LLMServerImpl``'s scheduler on the toy: states and pages in one
    manager, a share of the experts, no prefix cache (a state forbids it)."""
    from ray_tpu.serve._private.continuous import ContinuousScheduler

    cfg = presets.nemotron_h_debug(**HELD)
    params = seeded(cfg)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(9), (4, 80), 0,
                                           cfg.vocab_size))
    new = 6
    with pytest.raises(ValueError, match="state"):
        ContinuousScheduler(cfg, params, slots=3, prefill_chunk=16,
                            arena_len=96, page_tokens=4, prefix_cache=True,
                            attn="reference")
    prompts = [tokens[i, :n].tolist() for i, n in enumerate((70, 9, 33))]
    served, stats = harness.served(
        cfg, params, prompts, new, slots=3, prefill_chunk=16, arena_len=96,
        page_tokens=4, prefix_cache=False)
    hp = hp_of(cfg)
    for prompt, out in zip(prompts, served):
        assert len(out) == new
        assert harness.near_the_references_best(
            lambda seq: reference(params, seq, hp), prompt, out)
    mamba, experts = cfg.kinds.count(MAMBA), cfg.expert_layers
    rows = sum(len(p) + new - 1 for p in prompts)
    steps = (new - 1) * len(prompts)
    chunks = sum(-(-len(p) // 16) for p in prompts)
    assert stats["ssm_step_rows"] == mamba * steps
    assert stats["ssm_chunk_calls"] == mamba * chunks
    assert stats["ssm_chunk_tokens"] == mamba * sum(map(len, prompts))
    row_bytes = 4 * (3 * 128 + 2 * 16 * 32)  # conv 3 x 128, ssm 2 x 16 x 32
    assert stats["ssm_state_bytes_moved"] == 2 * row_bytes * mamba * (
        steps + chunks)
    assert stats["state_slots"] == 3
    assert stats["state_bytes"] == 3 * mamba * row_bytes
    # the no-drop identity is exact over every choice; the held counts are
    # a share of them
    assert stats["moe_routes_chosen"] == experts * cfg.moe_top_k * rows
    assert 0 < stats["moe_rows_routed"] < stats["moe_routes_chosen"]
    assert stats["moe_shared_rows"] == experts * rows
    assert stats["fused_turns"] > 0 and stats["pages_in_use"] == 0
    assert stats["attn_tokens_attended"] > 0  # the one attention layer
    assert "retention_step_rows" not in stats  # another kind's
