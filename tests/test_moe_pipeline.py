"""MoE expert parallelism and SPMD pipeline parallelism — the pp/ep
axes as first-class capabilities (SURVEY §5; VERDICT r2 missing #10).
Runs on the virtual 8-device CPU mesh from conftest."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ray_tpu.models import (count_params, forward, init_params, loss_fn,
                            moe_debug)
from ray_tpu.ops.moe import init_moe_params, moe_layer
from ray_tpu.parallel.pipeline import (pipeline_apply, stack_stage_params,
                                       stage_param_sharding)


class TestMoELayer:
    def test_shapes_and_aux(self):
        p = init_moe_params(jax.random.PRNGKey(0), 32, 64, 4)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 32))
        y, aux, counts, routes = moe_layer(p, x, num_experts=4,
                                           dtype=jnp.float32)
        assert y.shape == x.shape
        assert jnp.isfinite(y).all()
        # Switch aux loss is ~1 for near-uniform routing, >= 1 in general
        assert 0.5 < float(aux) < 4.0
        # dropless: every row reaches its top-2 experts
        assert int(counts.sum()) == 2 * 8 * 2
        assert routes.shape == (2, 8, 2)

    def test_gradients_flow_to_all_parts(self):
        p = init_moe_params(jax.random.PRNGKey(0), 16, 32, 4)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 4, 16))

        def loss(p):
            y, aux, _, _ = moe_layer(p, x, num_experts=4, dtype=jnp.float32)
            return jnp.sum(y**2) + 0.01 * aux

        g = jax.grad(loss)(p)
        for name, leaf in jax.tree_util.tree_leaves_with_path(g):
            assert float(jnp.abs(leaf).sum()) > 0, name


class TestMoETransformer:
    def test_loss_includes_aux_and_trains(self):
        cfg = moe_debug()
        params = init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                    cfg.vocab_size)
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: loss_fn(cfg, p, {"tokens": tokens}),
            has_aux=True)(params)
        assert jnp.isfinite(loss)
        assert "moe_aux" in metrics
        router_g = grads["blocks"]["mlp"]["w_router"]
        assert float(jnp.abs(router_g).sum()) > 0

    def test_expert_parallel_matches_single_device(self):
        """EP-sharded MoE must be numerically identical to unsharded."""
        cfg = moe_debug()
        params = init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                    cfg.vocab_size)
        ref = forward(cfg, params, tokens)

        devs = np.array(jax.devices()[:4]).reshape(2, 2)
        mesh = Mesh(devs, ("dp", "ep"))
        from ray_tpu.parallel.sharding import shard_params
        from ray_tpu.models import logical_axes

        sharded = shard_params(params, mesh, logical=logical_axes(cfg))
        out = jax.jit(lambda p, t: forward(cfg, p, t))(sharded, tokens)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=2e-4, atol=2e-4)


class TestPipeline:
    def test_linear_stages_compose(self):
        devs = np.array(jax.devices()[:4])
        mesh = Mesh(devs, ("pp",))
        # stage i multiplies by w_i and adds b_i
        per_stage = [{"w": jnp.float32(i + 2), "b": jnp.float32(i)}
                     for i in range(4)]
        stacked = jax.device_put(
            stack_stage_params(per_stage),
            stage_param_sharding(stack_stage_params(per_stage), mesh))

        def stage_fn(p, x):
            return x * p["w"] + p["b"]

        x = jnp.arange(24, dtype=jnp.float32).reshape(6, 4)  # 6 microbatches
        out = pipeline_apply(stage_fn, stacked, x, mesh=mesh)
        expect = x
        for i in range(4):
            expect = expect * (i + 2) + i
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                                   rtol=1e-6)

    def test_pipeline_is_differentiable(self):
        devs = np.array(jax.devices()[:2])
        mesh = Mesh(devs, ("pp",))
        per_stage = [{"w": jnp.float32(1.5)}, {"w": jnp.float32(0.5)}]
        stacked = stack_stage_params(per_stage)

        def stage_fn(p, x):
            return jnp.tanh(x * p["w"])

        x = jnp.ones((4, 3))

        def loss(sp):
            return jnp.sum(pipeline_apply(stage_fn, sp, x, mesh=mesh) ** 2)

        g = jax.grad(loss)(stacked)
        assert g["w"].shape == (2,)
        assert (jnp.abs(g["w"]) > 0).all()

    def test_1f1b_matches_single_device_grads(self):
        """1F1B over 4 stages reproduces plain autodiff's loss AND param
        grads (VERDICT r4 item 7: microbatched 1F1B, gradient-correct)."""
        from ray_tpu.parallel.pipeline import pipeline_1f1b

        devs = np.array(jax.devices()[:4])
        mesh = Mesh(devs, ("pp",))
        rng = np.random.RandomState(0)
        per_stage = [
            {"w": jnp.asarray(rng.randn(8, 8), jnp.float32) * 0.5,
             "b": jnp.asarray(rng.randn(8), jnp.float32) * 0.1}
            for _ in range(4)]
        stacked = stack_stage_params(per_stage)

        def stage_fn(p, x):
            return jnp.tanh(x @ p["w"] + p["b"])

        def loss_fn(act):
            return jnp.mean(act ** 2)

        M = 6
        x = jnp.asarray(rng.randn(M, 4, 8), jnp.float32)

        loss, grads = jax.jit(
            lambda sp, xx: pipeline_1f1b(
                stage_fn, loss_fn, sp, xx, mesh=mesh))(stacked, x)

        # single-device reference: sequential stages, mean loss over
        # microbatches, autodiff end to end
        def ref_loss(sp):
            total = 0.0
            for m in range(M):
                h = x[m]
                for s in range(4):
                    p = jax.tree.map(lambda v: v[s], sp)
                    h = stage_fn(p, h)
                total = total + loss_fn(h)
            return total / M

        ref_l, ref_g = jax.value_and_grad(ref_loss)(stacked)
        np.testing.assert_allclose(float(loss), float(ref_l), rtol=1e-5)
        for k in ("w", "b"):
            np.testing.assert_allclose(
                np.asarray(grads[k]), np.asarray(ref_g[k]),
                rtol=1e-4, atol=1e-6)

    def test_1f1b_bf16_microbatches(self):
        """bf16 — the TPU training dtype — must trace and train: the
        cotangent carry dtype follows the activations (regression: a
        float32-initialized bwd buffer failed scan's carry check)."""
        from ray_tpu.parallel.pipeline import pipeline_1f1b

        mesh = Mesh(np.array(jax.devices()[:4]), ("pp",))
        stages = stack_stage_params(
            [{"w": jnp.eye(8, dtype=jnp.bfloat16) * (0.8 + 0.1 * i)}
             for i in range(4)])
        xs = jnp.ones((6, 4, 8), jnp.bfloat16)
        loss, grads = pipeline_1f1b(
            lambda p, h: jnp.tanh(h @ p["w"]),
            lambda a: jnp.mean(a.astype(jnp.float32) ** 2),
            stages, xs, mesh=mesh)
        assert np.isfinite(float(loss)) and float(loss) > 0
        assert float(jnp.abs(grads["w"].astype(jnp.float32)).sum()) > 0

    def test_1f1b_bounded_activation_store(self):
        """The act store is 2*S slots — independent of microbatch count:
        a 32-microbatch run must still be correct (slots are reused)."""
        from ray_tpu.parallel.pipeline import pipeline_1f1b

        devs = np.array(jax.devices()[:4])
        mesh = Mesh(devs, ("pp",))
        per_stage = [{"w": jnp.float32(0.9 + 0.05 * i)} for i in range(4)]
        stacked = stack_stage_params(per_stage)

        def stage_fn(p, x):
            return x * p["w"]

        def loss_fn(act):
            return jnp.mean(act ** 2)

        M = 32  # >> 2*S = 8 slots
        x = jnp.linspace(0.1, 1.0, M * 4).reshape(M, 4).astype(jnp.float32)
        loss, grads = pipeline_1f1b(
            stage_fn, loss_fn, stacked, x, mesh=mesh)

        def ref_loss(sp):
            scale = sp["w"][0] * sp["w"][1] * sp["w"][2] * sp["w"][3]
            return jnp.mean((x * scale) ** 2, axis=1).mean()

        ref_l, ref_g = jax.value_and_grad(ref_loss)(stacked)
        np.testing.assert_allclose(float(loss), float(ref_l), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(grads["w"]),
                                   np.asarray(ref_g["w"]), rtol=1e-4)

    def test_pipelined_transformer_blocks_match_sequential(self):
        """4 blocks split 2x2 over pp must reproduce the sequential
        forward exactly (same params, same input)."""
        from ray_tpu.models.transformer import _block
        from ray_tpu.models import llama_debug
        from ray_tpu.ops.rotary import rope_frequencies

        cfg = llama_debug(num_layers=4, remat=False)
        params = init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                    cfg.vocab_size)
        ref = forward(cfg, params, tokens)

        devs = np.array(jax.devices()[:2])
        mesh = Mesh(devs, ("pp",))
        layers_per_stage = 2
        per_stage = [
            jax.tree.map(lambda a, i=i: a[i * layers_per_stage:
                                          (i + 1) * layers_per_stage],
                         params["blocks"])
            for i in range(2)
        ]
        stacked = stack_stage_params(per_stage)
        rope = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                cfg.rope_theta)

        def stage_fn(stage_params, h):
            def body(carry, layer_params):
                out, _, _, _ = _block(cfg, layer_params, carry, rope, None,
                                   None)
                return out, None
            h, _ = jax.lax.scan(body, h, stage_params)
            return h

        # embed outside, blocks in the pipeline, head outside
        x = params["embed"]["table"].astype(cfg.dtype)[tokens]
        micro = x.reshape(2, 2, *x.shape[1:])  # 2 microbatches of batch 2
        h = pipeline_apply(stage_fn, stacked, micro, mesh=mesh)
        h = h.reshape(4, *h.shape[2:])
        from ray_tpu.ops.norms import rms_norm

        h = rms_norm(h, params["final_norm"]["scale"], cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", h,
                            params["lm_head"]["kernel"].astype(cfg.dtype))
        np.testing.assert_allclose(np.asarray(ref), np.asarray(logits),
                                   rtol=2e-4, atol=2e-4)
