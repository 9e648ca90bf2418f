"""The attention forward runs once a layer in training (ISSUE 48): the flash
kernel's output and log-sum-exp are named in its forward rule and every remat
policy keeps them, so a block's recompute holds no second
``flash_attention_fwd`` and the backward kernels get the very arrays the
forward made, so the gradients are the un-rematted ones.

Counted in the gradient's jaxpr of a scanned, rematted stack of blocks: a
scan's body stands once in it, so a kernel's calls there are its calls a
layer — one forward, one dQ, one dK/dV (the parent: two forwards). The
kernels are interpreted on the CPU at small shapes; what the chip's compiler
makes of it is ``tests/test_tpu_compile.py``'s to ask. A count, never a time.
"""

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec

from ray_tpu.models.transformer import (TransformerConfig, init_params,
                                        loss_fn, remat_policy)
from ray_tpu.parallel.mesh import MeshSpec, build_mesh, data_sharding

B, S, LAYERS = 4, 128, 2
ONCE_A_LAYER = {"flash_attention_fwd": 1, "flash_attention_bwd_dq": 1,
                "flash_attention_bwd_dkv": 1}


def _cfg(**more) -> TransformerConfig:
    return TransformerConfig(**{**dict(
        vocab_size=256, num_layers=LAYERS, embed_dim=256, num_heads=4,
        num_kv_heads=2, head_dim=128, mlp_dim=512, max_seq_len=S,
        ce_chunk=64, attn_impl="flash", tie_embeddings=False,
        # bf16 would round where the CPU's compiler ends a fusion, and remat
        # moves those ends: float32 compares the programs, not the roundings
        dtype=jnp.float32), **more})


def _kernel_calls(jaxpr) -> dict:
    """``pallas_call``s by kernel name, through every sub-jaxpr (a scan's
    body, a remat's, a custom_vjp's, a shard_map's), each where it stands."""
    calls = collections.Counter()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                calls[eqn.params["name"]] += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return dict(calls)


def _mesh(name):
    return None if name == "no_mesh" else build_mesh(
        MeshSpec.of(fsdp=2, tp=2), devices=jax.devices()[:4])


def _grad(cfg, mesh):
    """Loss and gradients, traced with ``mesh`` in scope as
    ``make_train_step(mesh)`` has it: the dispatcher then shard_maps the
    kernel over fsdp x tp."""
    grad = jax.value_and_grad(lambda p, b: loss_fn(cfg, p, b)[0])
    if mesh is None:
        return grad

    def on_mesh(params, batch):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return grad(params, batch)

    return on_mesh


@functools.cache
def _arguments(mesh_name):
    """Weights and a batch where a step on the mesh finds them: the batch
    over the data axes, the weights (small here) on every device."""
    params = init_params(_cfg(), jax.random.PRNGKey(0))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                          256)}
    mesh = _mesh(mesh_name)
    if mesh is None:
        return params, batch
    return (jax.device_put(params, NamedSharding(mesh, PartitionSpec())),
            jax.device_put(batch, data_sharding(mesh)))


@functools.cache
def _unrematted(mesh_name):
    """Loss and gradients with ``remat=False``: what every policy has to
    give back."""
    return jax.jit(_grad(_cfg(remat=False), _mesh(mesh_name)))(
        *_arguments(mesh_name))


@pytest.mark.parametrize("mesh_name", ["no_mesh", "fsdp2_tp2"])
@pytest.mark.parametrize("policy", ["full", "dots"])
def test_a_layers_gradient_holds_one_forward_kernel(policy, mesh_name):
    grad = _grad(_cfg(remat_policy=policy), _mesh(mesh_name))
    arguments = _arguments(mesh_name)
    assert _kernel_calls(jax.make_jaxpr(grad)(*arguments)) == ONCE_A_LAYER

    # With one forward call left, ``out`` and ``lse`` can only be its own.
    # The numbers beside it: float32's rounding and no more (read: 9e-7 of
    # a leaf's largest entry; not 0, the CPU's compiler orders the sums of
    # two different programs differently), where a wrong residual is off by
    # the size of the gradient itself
    loss, grads = jax.jit(grad)(*arguments)
    loss_0, grads_0 = _unrematted(mesh_name)
    np.testing.assert_allclose(loss, loss_0, rtol=1e-6)
    flat, flat_0 = (jax.tree.leaves_with_path(g) for g in (grads, grads_0))
    assert len(flat) == len(flat_0) > 8
    for (path, g), (_, g0) in zip(flat, flat_0):
        g, g0 = np.asarray(g), np.asarray(g0)
        assert np.abs(g - g0).max() <= 1e-5 * np.abs(g0).max(), (
            jax.tree_util.keystr(path))


@pytest.mark.parametrize("policy", ["nothing_saveable", "checkpoint_dots"])
def test_without_the_names_the_recompute_calls_the_kernel_again(
        policy, monkeypatch):
    """The parent's two policies: the count this file's other cases hold is
    the table's doing, and ``checkpoint_dots`` does not see a Pallas call."""
    from ray_tpu.models import transformer

    monkeypatch.setattr(transformer, "remat_policy",
                        lambda name: getattr(jax.checkpoint_policies, policy))
    assert _kernel_calls(jax.make_jaxpr(_grad(_cfg(), None))(
        *_arguments("no_mesh"))) == {**ONCE_A_LAYER, "flash_attention_fwd": 2}


@pytest.mark.parametrize("policy", ["full", "dots"])
def test_the_pipeline_stages_read_the_one_table(policy):
    """``presets._apply_blocks`` and ``_tp_apply_blocks`` (the (g, f) pair
    is a pipeline actor's own collective: identities here, the count does
    not hang on them), scanned with the tail block split off."""
    from ray_tpu.models import presets

    cfg = _cfg(remat_policy=policy, num_kv_heads=4, num_layers=4)
    n_local = 2  # the first of two chunks
    h = jnp.zeros((2, S, cfg.embed_dim), cfg.dtype)

    blocks = presets._stage_init(cfg, 0, 2, 0)["blocks"]
    plain = jax.make_jaxpr(jax.grad(lambda b: presets._apply_blocks(
        cfg, b, h, n_local).astype(jnp.float32).sum()))(blocks)
    assert _kernel_calls(plain) == ONCE_A_LAYER

    blocks = presets._stage_init_tp(cfg, 0, 2, 0, 2)["blocks"]
    same = lambda x: x

    def tail_split(b):
        u, partial = presets._tp_apply_blocks(cfg, b, h, n_local,
                                              (same, same),
                                              split_tail=True)
        return (u + partial).astype(jnp.float32).sum()

    # the scanned chain and the tail block: each its own rematted body
    assert _kernel_calls(jax.make_jaxpr(jax.grad(tail_split))(blocks)) == {
        name: 2 * n for name, n in ONCE_A_LAYER.items()}


def test_outside_a_checkpoint_a_name_lowers_to_nothing(monkeypatch):
    """The serving programs call the kernel with no gradient and no
    ``jax.checkpoint``: their lowered text is what it is with the names out
    of the forward rule."""
    from ray_tpu.ops import flash_attention as fa

    q = jax.ShapeDtypeStruct((2, S, 4, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((2, S, 2, 128), jnp.bfloat16)

    def lowered():
        return jax.jit(lambda q, k, v: fa.flash_attention(q, k, v)).lower(
            q, kv, kv).as_text()

    named = lowered()
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    assert lowered() == named


def test_an_unknown_policy_is_refused():
    with pytest.raises(ValueError, match="unknown remat_policy"):
        remat_policy("some")
