"""Ouro's looped stack on the CPU (ISSUE 65): every token through the SAME
stacked layers ``loop_passes`` times, a norm behind each sublayer, the final
norm behind every pass, an exit gate that picks the pass the head projects.
The uncached ``forward`` and the contiguous cache against
``perfbench/reference/ouro.py`` on logits AND exits at 4 passes and at 2, at
two thresholds; the looped ``loss_fn``'s gradients against ``jax.grad`` of the
reference's loss; what ``loop_passes`` 1 leaves as it was; every accepted
configuration's seeded weights held to the parent's program. The paged
programs are ``tests/test_ouro_paged.py``'s, the described-chip compile
``tests/test_ouro_compile.py``'s. Drives are ``tests/model_harness.py``'s.
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.reference import ouro as reference
from ray_tpu.models import presets
from ray_tpu.models.transformer import (count_params, forward, init_params,
                                        logical_axes, loss_fn)
from tests import model_harness as mh


def hp_of(cfg):
    """The reference's keys (the source's own) of a program config."""
    return {"rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "num_hidden_layers": cfg.num_layers,
            "total_ut_steps": cfg.loop_passes,
            "early_exit_threshold": cfg.exit_threshold}


def spread(cfg, seed=0):
    """Seeded weights whose gate speaks up: exits spread over the passes
    under a threshold below 1 (a gate drawn like any matrix sits at 0.5)."""
    params = mh.seeded(cfg, seed, times={"exit_gate": 25.0})
    params["exit_gate"]["b"] = jnp.asarray(-1.0, jnp.float32)
    return params


CASES = [pytest.param(4, 0.5, id="4-passes-threshold-0.5"),
         pytest.param(2, 1.0, id="2-passes-threshold-1")]


@pytest.mark.parametrize("passes,threshold", CASES)
def test_forward_and_cache_against_the_reference(passes, threshold):
    """Logits within 2e-5 of the reference's largest, uncached and through
    the contiguous cache (a prompt of 20, then a step a token), and every
    position's exit pass the reference's: at threshold 0.5 rows leave at
    different passes though every pass is computed, at 1 at the last."""
    cfg = presets.ouro_debug(loop_passes=passes, exit_threshold=threshold,
                             num_layers=2)
    params = spread(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 256)
    want, exits, shares = jax.jit(
        lambda p, t: reference.forward_and_exits(p, t, hp_of(cfg)))(params,
                                                                    tokens)
    assert shares.shape == (passes, 2, 24)
    np.testing.assert_allclose(np.asarray(shares.sum(0)), 1.0, atol=1e-6)
    with jax.default_matmul_precision("highest"):
        got, left = mh.forward_program(cfg, return_exit_pass=True)(params,
                                                                   tokens)
    assert mh.rel(got, want) < 2e-5
    assert np.array_equal(left, exits)
    seen = np.bincount(np.asarray(exits).ravel(), minlength=passes + 1)[1:]
    if threshold < 1:
        assert (seen > 0).sum() >= 3, seen  # rows leave at different passes
    else:
        assert seen[-1] == exits.size  # no gate saturates: the last pass
    if threshold < 1:  # (one case pays the cache's two programs)
        cached = mh.cached_logits(cfg, params, tokens, 20)
        assert mh.rel(cached, want[:, 19:]) < 2e-5
        return
    # given another's exits the reference projects THOSE passes' states
    moved = jax.jit(lambda p, t, e: reference.forward(
        p, t, hp_of(cfg), exit_pass=e))(params, tokens, jnp.ones_like(exits))
    assert mh.rel(moved, want) > 1e-3


def test_gradients_of_the_looped_loss_against_the_reference():
    """``loss_fn`` over the scan of the scan (shared weights: gradients add
    up over the passes) against ``jax.grad`` of the reference's loss, leaf
    by leaf; the gate's own gradient is zero on both sides (the exit is a
    step)."""
    cfg = presets.ouro_debug(loop_passes=2, num_layers=1, exit_threshold=0.5)
    params = spread(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 12), 0, 256)
    with jax.default_matmul_precision("highest"):
        loss, got = jax.jit(jax.value_and_grad(
            lambda p: loss_fn(cfg, p, {"tokens": tokens})[0]))(params)
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda p: reference.loss(p, tokens, hp_of(cfg))))(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5
    flat = jax.tree_util.tree_leaves_with_path(want)
    assert len(flat) == len(jax.tree.leaves(got))
    for (path, w), g in zip(flat, jax.tree.leaves(got)):
        name = jax.tree_util.keystr(path)
        if "exit_gate" in name:
            assert not np.asarray(g).any() and not np.asarray(w).any()
            continue
        assert np.asarray(w).any(), name
        assert mh.rel(g, w) < 2e-4, name


def test_one_pass_without_output_norms_is_the_model_it_was():
    """``loop_passes`` 1 and no norm behind a sublayer: the config IS the
    Llama-shaped one of the same sizes (so every program it traces to is
    that model's, bit for bit), and its weights hold no gate and no third
    or fourth norm."""
    sizes = dict(num_kv_heads=4, head_dim=16)
    plain = presets.llama_debug(num_layers=3, norm_eps=1e-6,
                                rope_theta=1000000.0, **sizes)
    once = presets.ouro_debug(loop_passes=1, output_norms=False)
    assert once == plain and not once.looped
    params = jax.eval_shape(lambda: init_params(once, jax.random.PRNGKey(0)))
    assert "exit_gate" not in params and set(params["blocks"]) == {
        "attn", "ln1", "ln2", "mlp"}
    with pytest.raises(ValueError, match="return_exit_pass"):
        jax.eval_shape(lambda p: forward(
            once, p, jnp.zeros((1, 4), jnp.int32), return_exit_pass=True),
            params)
    with pytest.raises(ValueError, match="loop_passes"):
        presets.ouro_debug(loop_passes=2, mlp="moe", moe_num_experts=4)


def test_the_published_sizes_and_axes():
    """The preset at the published widths: 51,388,416 parameters a layer,
    2,667,974,657 in all (shapes only), an axis a leaf."""
    cfg = presets.ouro()
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    assert count_params(shapes["blocks"]) == 48 * 51_388_416
    assert count_params(shapes) == 2_667_974_657
    assert count_params(shapes["exit_gate"]) == 2049
    axes = logical_axes(cfg)
    assert jax.tree.structure(shapes) == jax.tree.structure(
        axes, is_leaf=lambda a: isinstance(a, tuple))
    assert set(shapes["blocks"]) == {"attn", "ln1", "ln1_out", "ln2",
                                     "ln2_out", "mlp"}
    # ONE layer's draws under vmap, not 48 copies of them: the program that
    # makes the weights compiles in seconds for the chip (drawn a layer at
    # a time: two minutes, longer than a replica is given to start)
    text = str(jax.make_jaxpr(lambda key: init_params(cfg, key))(
        jax.random.PRNGKey(0)))
    assert text.count("random_bits") < 16


# sha256 (first 16) of ``str(make_jaxpr(init_params))`` at the published
# widths of every configuration the benchmark had before this PR, as the
# PARENT's tree (2897359) traces it: the program that makes a seed's weights
PARENTS_WEIGHTS = {
    "gpt2_small": "eac58dacc189f887",
    "mistral7b_v03_l16": "8a9229806b0dc5a1",
    "mistral7b_v03_l8": "dd190a73285bda67",
    "olmoe_1b_7b_l8": "e0d5c77840de0da1",
    "minicpm_sala_l16": "55cd5de58e630d49",
    "brumby_14b_l8": "c3e00cbe682b98b6",
    "mellum2_12b_l8": "c96e6a93ff1595ab",
    "keye_vl2_30b_a3b_l5": "8b3e072e97d007a0",
    "glm47_flash_l6": "8684c3dd9c2008e3",
    "nemotron3_nano_30b_a3b_l9": "f5ab6c570ee0ed59",
    "deepseek_v32_exp_l5": "a3bc57570f610441",
}


@pytest.mark.parametrize("config", sorted(PARENTS_WEIGHTS))
def test_an_accepted_configurations_seeded_weights_are_the_parents(config):
    """A seed's weights must not move by a bit for any accepted
    configuration: ``init_params`` traces (nothing runs) to the program the
    parent's tree traced to — the exit gate's key is folded in beside the
    others' split, and only a looped model draws it."""
    from tests.tpu_compile_harness import program_config

    _, cfg = program_config(config)
    text = str(jax.make_jaxpr(lambda key: init_params(cfg, key))(
        jax.random.PRNGKey(0)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        PARENTS_WEIGHTS[config]
