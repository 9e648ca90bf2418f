"""Model-family tests: forward shape/dtype, loss decreases under the jitted
sharded train step on an 8-device CPU mesh (fsdp×tp), GPT-2 vs LLaMA configs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import (TransformerConfig, count_params, forward,
                            init_params, logical_axes, loss_fn, llama_debug,
                            gpt2_small)
from ray_tpu.models.training import (OptimizerConfig, init_train_state,
                                     make_optimizer, make_train_step)
from ray_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_tpu.parallel.sharding import ShardingRules, param_specs, shard_params


def _tiny_gpt2():
    return gpt2_small(num_layers=2, embed_dim=32, num_heads=2, vocab_size=128,
                      max_seq_len=64, dtype=jnp.float32)


class TestForward:
    @pytest.mark.parametrize("cfg_fn", [llama_debug, _tiny_gpt2])
    def test_shapes(self, cfg_fn):
        cfg = cfg_fn()
        params = init_params(cfg, jax.random.PRNGKey(0))
        tokens = jnp.zeros((2, 16), jnp.int32)
        logits = forward(cfg, params, tokens)
        assert logits.shape == (2, 16, cfg.vocab_size)

    def test_scan_vs_unrolled(self):
        cfg_s = llama_debug(scan_layers=True, remat=False)
        cfg_u = llama_debug(scan_layers=False, remat=False)
        p_s = init_params(cfg_s, jax.random.PRNGKey(0))
        # convert stacked params -> per-layer dict
        p_u = dict(p_s)
        p_u["blocks"] = {
            str(i): jax.tree.map(lambda a, i=i: a[i], p_s["blocks"])
            for i in range(cfg_s.num_layers)}
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 256)
        np.testing.assert_allclose(
            forward(cfg_s, p_s, tokens), forward(cfg_u, p_u, tokens),
            atol=1e-5, rtol=1e-5)

    def test_causality(self):
        cfg = llama_debug(remat=False)
        params = init_params(cfg, jax.random.PRNGKey(0))
        t1 = jax.random.randint(jax.random.PRNGKey(1), (1, 16), 0, 256)
        t2 = t1.at[:, 10:].set(0)  # change only the future
        l1 = forward(cfg, params, t1)
        l2 = forward(cfg, params, t2)
        np.testing.assert_allclose(l1[:, :10], l2[:, :10], atol=1e-5)

    def test_param_count_gpt2(self):
        cfg = gpt2_small()
        n = count_params(init_params(cfg, jax.random.PRNGKey(0)))
        assert 120e6 < n < 130e6  # 124M


class TestShardedTraining:
    def test_loss_decreases_fsdp_tp(self):
        cfg = llama_debug()
        mesh = build_mesh(MeshSpec.of(fsdp=4, tp=2))
        ocfg = OptimizerConfig(learning_rate=1e-2, warmup_steps=1,
                               decay_steps=100)
        state, tx = init_train_state(cfg, ocfg, jax.random.PRNGKey(0), mesh)
        step = make_train_step(cfg, tx, mesh)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, 256)
        batch = {"tokens": tokens}
        losses = []
        for _ in range(8):
            state, metrics = step(state, batch)
            losses.append(float(metrics["loss"]))
        assert losses[-1] < losses[0] * 0.9, losses
        assert int(state.step) == 8

    def test_flash_kernel_inside_the_sharded_step(self):
        """make_train_step puts its mesh in scope, so attn_impl='flash'
        (what 'auto' resolves to on a TPU) runs per shard inside the
        GSPMD step: same losses as the XLA reference attention."""
        mesh = build_mesh(MeshSpec.of(fsdp=2, tp=2),
                          devices=jax.devices()[:4])
        ocfg = OptimizerConfig(learning_rate=1e-2, warmup_steps=1,
                               decay_steps=100)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, 256)
        losses = {}
        for impl in ("flash", "reference"):
            cfg = llama_debug(attn_impl=impl)
            state, tx = init_train_state(cfg, ocfg, jax.random.PRNGKey(0),
                                         mesh)
            step = make_train_step(cfg, tx, mesh)
            losses[impl] = []
            for _ in range(3):
                state, metrics = step(state, {"tokens": tokens})
                losses[impl].append(float(metrics["loss"]))
        np.testing.assert_allclose(losses["flash"], losses["reference"],
                                   rtol=1e-4)
        assert losses["flash"][-1] < losses["flash"][0]

    def test_param_shardings_applied(self):
        cfg = llama_debug()
        mesh = build_mesh(MeshSpec.of(fsdp=4, tp=2))
        state, _ = init_train_state(
            cfg, OptimizerConfig(), jax.random.PRNGKey(0), mesh)
        # mlp w_gate: (layers, embed, mlp) -> (None, fsdp, tp)
        s = state.params["blocks"]["mlp"]["w_gate"].sharding
        assert s.spec == jax.sharding.PartitionSpec(None, "fsdp", "tp")

    def test_unsharded_cpu_training(self):
        cfg = llama_debug()
        ocfg = OptimizerConfig(learning_rate=1e-2, warmup_steps=1)
        state, tx = init_train_state(cfg, ocfg, jax.random.PRNGKey(0))
        step = make_train_step(cfg, tx)
        batch = {"tokens": jnp.ones((2, 16), jnp.int32)}
        state, m = step(state, batch)
        assert np.isfinite(float(m["loss"]))


class TestLoss:
    def test_mask_respected(self):
        cfg = llama_debug(remat=False)
        params = init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 256)
        full, _ = loss_fn(cfg, params, {"tokens": tokens})
        masked, aux = loss_fn(
            cfg, params,
            {"tokens": tokens, "mask": jnp.ones_like(tokens)})
        np.testing.assert_allclose(full, masked, atol=1e-6)
        assert int(aux["tokens"]) == 2 * 15
