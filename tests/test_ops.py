"""Numerics tests for the ops layer: Pallas flash kernel (interpret mode on
CPU) and ring attention (8-device CPU mesh) vs the XLA reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention, ring_attention, rms_norm, layer_norm
from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops.flash_attention import (Tiles, flash_attention,
                                         reference_attention, tile_sizes)
from ray_tpu.ops.losses import softmax_cross_entropy
from ray_tpu.ops.rotary import apply_rotary, rope_frequencies


def _qkv(b=2, s=128, h=4, hkv=None, d=32, dtype=jnp.float32, seed=0,
         sq=None):
    hkv = hkv or h
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, sq or s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, s, hkv, d), dtype)
    return q, k, v


def _grads(fn, q, k, v):
    """Gradients of a weighted sum (a plain sum leaves dS = 0 rows)."""
    w = jax.random.normal(jax.random.PRNGKey(7), q.shape, q.dtype)
    return jax.grad(lambda q, k, v: (fn(q, k, v) * w).sum(),
                    argnums=(0, 1, 2))(q, k, v)


# (shape of _qkv, forced Tiles or None, causal): both head shapes of the
# benchmark's cells (D 64 MHA: two heads a 128-lane window; D 128, group 4:
# a head a window), resident and streamed, and the edges of the triangle walk
_D64 = dict(b=2, s=128, h=4, d=64)
_D128 = dict(b=1, s=128, h=4, hkv=1, d=128)
SCHEDULES = {
    "d64_mha-resident": (_D64, Tiles(2, 64, 32, 128), True),
    "d64_mha-streamed": (_D64, Tiles(2, 64, 32, 64), True),
    "d128_gqa4-resident": (_D128, Tiles(4, 64, 32, 128), True),
    "d128_gqa4-streamed": (_D128, Tiles(4, 64, 32, 64), True),
    "seq_is_one_sub_block": (dict(b=1, s=64, h=2, d=64),
                             Tiles(2, 64, 64, 64), True),
    "rows_span_four_sub_blocks": (dict(b=1, s=256, h=2, d=64),
                                  Tiles(2, 128, 32, 256), True),
    "sub_block_wider_than_rows": (_D64, Tiles(4, 32, 64, 128), True),
    "streamed_sub_block_is_the_part": (_D64, Tiles(4, 64, 32, 32), True),
    "seq_q_shorter": (dict(b=1, sq=64, s=128, h=2, d=64),
                      Tiles(2, 32, 32, 128), True),
    "seq_q_longer": (dict(b=1, sq=128, s=64, h=2, d=64),
                     Tiles(2, 64, 32, 32), True),
    "not_causal-resident": (_D64, Tiles(2, 64, 32, 128), False),
    "not_causal-streamed": (_D128, Tiles(4, 32, 32, 64), False),
    "tile_sizes_own_choice": (dict(b=1, s=512, h=4, d=64), None, True),
    "tile_sizes_own_choice-d128_gqa4": (dict(b=1, s=512, h=4, hkv=1, d=128),
                                        None, True),
    "shared_window_gqa_rolls": (dict(b=1, s=128, h=8, hkv=4, d=32),
                                Tiles(8, 64, 32, 64), True),
    "head_is_no_window": (dict(b=1, s=64, h=8, hkv=2, d=32),
                          Tiles(8, 32, 32, 64), True),
}


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, causal):
        q, k, v = _qkv()
        out = flash_attention(q, k, v, None, causal, Tiles(4, 64, 64, 128))
        ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_gqa(self):
        q, k, v = _qkv(h=8, hkv=2)
        out = flash_attention(q, k, v, None, True, Tiles(8, 64, 64, 128))
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def test_grad_matches(self):
        q, k, v = _qkv(s=64)

        def f_flash(q, k, v):
            return flash_attention(q, k, v, None, True,
                                   Tiles(4, 32, 32, 64)).sum()

        def f_ref(q, k, v):
            return reference_attention(q, k, v, causal=True).sum()

        g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)

    def test_grad_matches_gqa(self):
        """dK/dV accumulation over the query-head group (one accumulator
        a kv head in _dkv_kernel) vs the reference."""
        q, k, v = _qkv(s=64, h=4, hkv=2)

        def f_flash(q, k, v):
            return flash_attention(q, k, v, None, True,
                                   Tiles(4, 32, 32, 64)).sum()

        def f_ref(q, k, v):
            return reference_attention(q, k, v, causal=True).sum()

        g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("case", list(SCHEDULES), ids=list(SCHEDULES))
    def test_schedule_matches_reference_forward_and_grads(self, case):
        shape, tiles, causal = SCHEDULES[case]
        q, k, v = _qkv(**shape)

        def flash(q, k, v):
            return flash_attention(q, k, v, None, causal, tiles)

        def ref(q, k, v):
            return reference_attention(q, k, v, causal=causal)

        np.testing.assert_allclose(flash(q, k, v), ref(q, k, v),
                                   atol=2e-5, rtol=2e-5)
        for a, b in zip(_grads(flash, q, k, v), _grads(ref, q, k, v)):
            np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)

    def test_forced_tiles_must_divide_the_sequences(self):
        q, k, v = _qkv(s=96)
        with pytest.raises(ValueError, match="does not divide"):
            flash_attention(q, k, v, None, True, Tiles(4, 64, 32, 96))

    def test_dispatcher_on_cpu(self):
        q, k, v = _qkv(s=64)
        out = attention(q, k, v, causal=True)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(out, ref, atol=1e-6)


# the benchmark's two training cells, as a shard sees them
CELL_SHAPES = {
    "gpt2s_train": dict(seq=1024, head_dim=64, num_heads=12, group=1),
    "mistral7b_train_4chip": dict(seq=4096, head_dim=128, num_heads=16,
                                  group=4),
}


class TestTileSizes:
    @pytest.mark.parametrize("cell", list(CELL_SHAPES))
    def test_blocks_fit_the_stated_budget_and_divide_the_shapes(self, cell):
        c = CELL_SHAPES[cell]
        schedule = tile_sizes(c["seq"], c["seq"], c["head_dim"],
                              c["num_heads"], c["group"], 2)
        for role, tiles in schedule._asdict().items():
            assert fa.block_bytes(tiles, role, c["head_dim"], c["group"],
                                  2) <= fa._VMEM_BUDGET < fa._VMEM_LIMIT
            assert c["seq"] % tiles.rows == 0 and c["seq"] % tiles.major == 0
            assert tiles.major % tiles.cols == 0
            assert c["num_heads"] % tiles.heads == 0
            assert tiles.heads % c["group"] == 0
            # lane blocks are whole 128-lane windows of q and of k/v
            assert tiles.heads * c["head_dim"] % 128 == 0
            assert tiles.heads // c["group"] * c["head_dim"] % 128 == 0
            # both cells' K/V (and Q/dO) fit: resident, no fourth grid step
            assert tiles.major == c["seq"], (role, tiles)

    def test_a_sequence_too_long_for_the_budget_is_streamed(self):
        schedule = tile_sizes(131072, 131072, 128, 16, 4, 2)
        for role, tiles in schedule._asdict().items():
            assert tiles.major < 131072 and 131072 % tiles.major == 0
            assert fa.block_bytes(tiles, role, 128, 4, 2) <= fa._VMEM_BUDGET

    def test_heads_follow_the_lane_cap_and_edges_the_head_size(self):
        """A cell takes whole 128-lane windows of heads up to the cap (every
        head is unrolled in the kernel's body), tiles of 4 x head_dim."""
        gpt2s = tile_sizes(1024, 1024, 64, 12, 1, 2).fwd
        assert (gpt2s.heads, gpt2s.rows, gpt2s.cols) == (4, 256, 256)
        shard = tile_sizes(4096, 4096, 128, 16, 4, 2).dkv
        assert (shard.heads, shard.rows, shard.cols) == (4, 512, 512)
        # no legal choice under the cap: the fewest heads that are legal
        assert tile_sizes(1024, 1024, 128, 8, 8, 2).fwd.heads == 8


class TestFlashUnderMesh:
    """GSPMD cannot partition a Mosaic kernel, so with a mesh in scope the
    dispatcher shard_maps the kernel over the axes the sharding rules
    give batch and heads (ISSUE 21). Interpreted here; the chip's compiler
    is asked in tests/test_tpu_compile.py."""

    @staticmethod
    def _run(mesh, q, k, v, grad=False):
        def fn(q, k, v):
            with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
                o = attention(q, k, v, causal=True, impl="flash")
            return o.sum() if grad else o

        fn = jax.grad(fn, argnums=(0, 1, 2)) if grad else fn
        on_mesh = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())
        return jax.jit(fn)(*jax.device_put((q, k, v), on_mesh))

    @pytest.mark.parametrize("axes,hkv", [
        (dict(fsdp=2, tp=2), 4),   # batch over fsdp, heads over tp
        (dict(dp=2, fsdp=2), 4),   # batch over both data axes
        (dict(fsdp=2, tp=2), 1),   # tp does not divide Hkv: heads whole
        (dict(sp=4), 4),           # nothing shards batch or heads
    ], ids=["fsdp_tp", "dp_fsdp", "gqa_heads_whole", "sp_only"])
    def test_matches_reference_forward_and_grad(self, axes, hkv):
        from ray_tpu.parallel.mesh import MeshSpec, build_mesh

        mesh = build_mesh(MeshSpec.of(**axes), devices=jax.devices()[:4])
        q, k, v = _qkv(b=4, s=64, h=4, hkv=hkv)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(self._run(mesh, q, k, v), ref,
                                   atol=2e-5, rtol=2e-5)
        g_ref = jax.grad(
            lambda q, k, v: reference_attention(q, k, v, causal=True).sum(),
            argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(self._run(mesh, q, k, v, grad=True), g_ref):
            np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)

    def test_shards_batch_and_heads_not_the_whole_arrays(self):
        """The kernel sees its shard: [B/fsdp, S, H/tp, D]."""
        import sys

        from ray_tpu.parallel.mesh import MeshSpec, build_mesh

        # (the package re-exports the function under the module's name)
        attn_mod = sys.modules["ray_tpu.ops.attention"]
        seen = []
        real = attn_mod.flash_attention

        def spy(q, k, v, *a):
            seen.append((q.shape, k.shape))
            return real(q, k, v, *a)

        mesh = build_mesh(MeshSpec.of(fsdp=2, tp=2), devices=jax.devices()[:4])
        q, k, v = _qkv(b=4, s=64, h=4, hkv=2)
        attn_mod.flash_attention = spy
        try:
            self._run(mesh, q, k, v)
        finally:
            attn_mod.flash_attention = real
        assert seen == [((2, 64, 2, 32), (2, 64, 1, 32))]

    def test_no_mesh_in_scope_calls_the_kernel_bare(self):
        q, k, v = _qkv(s=64)
        out = jax.jit(lambda q, k, v: attention(
            q, k, v, causal=True, impl="flash"))(q, k, v)
        np.testing.assert_allclose(
            out, reference_attention(q, k, v, causal=True),
            atol=2e-5, rtol=2e-5)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_full(self, causal):
        from ray_tpu.parallel.mesh import build_mesh, MeshSpec

        mesh = build_mesh(MeshSpec.of(sp=8))
        q, k, v = _qkv(b=2, s=128, h=4, d=16)
        out = ring_attention(q, k, v, mesh, causal=causal)
        ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_gqa_ring(self):
        from ray_tpu.parallel.mesh import build_mesh, MeshSpec

        mesh = build_mesh(MeshSpec.of(sp=4), devices=jax.devices()[:4])
        q, k, v = _qkv(b=1, s=64, h=8, hkv=2, d=16)
        out = ring_attention(q, k, v, mesh, causal=True)
        ref = reference_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)


class TestNormsRotaryLoss:
    def test_rms_norm(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (4, 32))
        w = jnp.ones(32) * 2.0
        out = rms_norm(x, w)
        expected = x / np.sqrt((np.asarray(x) ** 2).mean(-1, keepdims=True) + 1e-6) * 2.0
        np.testing.assert_allclose(out, expected, atol=1e-5)

    def test_layer_norm(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 32))
        out = layer_norm(x, jnp.ones(32), jnp.zeros(32))
        xn = np.asarray(x)
        expected = (xn - xn.mean(-1, keepdims=True)) / np.sqrt(
            xn.var(-1, keepdims=True) + 1e-5)
        np.testing.assert_allclose(out, expected, atol=1e-5)

    def test_rotary_preserves_norm(self):
        x = jax.random.normal(jax.random.PRNGKey(2), (1, 16, 2, 8))
        cos, sin = rope_frequencies(8, 16)
        out = apply_rotary(x, cos, sin)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(out), axis=-1),
            np.linalg.norm(np.asarray(x), axis=-1), atol=1e-5)

    def test_rotary_relative(self):
        # attention scores depend only on relative positions
        d = 8
        cos, sin = rope_frequencies(d, 32)
        q = jax.random.normal(jax.random.PRNGKey(3), (1, 1, 1, d))
        k = jax.random.normal(jax.random.PRNGKey(4), (1, 1, 1, d))
        pos = jnp.array([[5]])
        pos2 = jnp.array([[9]])
        s1 = (apply_rotary(q, cos, sin, pos) * apply_rotary(k, cos, sin, pos)).sum()
        s2 = (apply_rotary(q, cos, sin, pos2) * apply_rotary(k, cos, sin, pos2)).sum()
        np.testing.assert_allclose(s1, s2, atol=1e-5)

    def test_cross_entropy(self):
        logits = jnp.array([[2.0, 0.0, 0.0], [0.0, 3.0, 0.0]])
        labels = jnp.array([0, 1])
        loss, n = softmax_cross_entropy(logits, labels)
        expected = -np.log(np.exp([2.0, 3.0]) /
                           (np.exp([2.0, 3.0]) + 2)).mean()
        np.testing.assert_allclose(loss, expected, atol=1e-6)
        assert n == 2

    def test_cross_entropy_mask(self):
        logits = jax.random.normal(jax.random.PRNGKey(5), (2, 4, 10))
        labels = jnp.zeros((2, 4), jnp.int32)
        mask = jnp.array([[1, 1, 0, 0], [1, 0, 0, 0]])
        loss, n = softmax_cross_entropy(logits, labels, mask)
        assert n == 3
        assert np.isfinite(loss)


class TestFusedCrossEntropy:
    """fused (projection-folded, chunked) CE vs the materialized reference."""

    def _case(self, n=37, d=16, v=53, seed=7):
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        hidden = jax.random.normal(ks[0], (3, n, d))
        table = jax.random.normal(ks[1], (v, d)) * 0.1
        labels = jax.random.randint(ks[2], (3, n), 0, v)
        return hidden, table, labels

    def test_matches_reference(self):
        from ray_tpu.ops.losses import fused_softmax_cross_entropy

        hidden, table, labels = self._case()
        logits = jnp.einsum("bnd,vd->bnv", hidden, table)
        ref, n_ref = softmax_cross_entropy(logits, labels)
        out, n = fused_softmax_cross_entropy(
            hidden, table, labels, chunk=16, compute_dtype=jnp.float32)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)
        assert n == n_ref

    def test_masked_and_transposed(self):
        from ray_tpu.ops.losses import fused_softmax_cross_entropy

        hidden, table, labels = self._case()
        mask = (jax.random.uniform(jax.random.PRNGKey(9), labels.shape)
                > 0.5).astype(jnp.int32)
        logits = jnp.einsum("bnd,vd->bnv", hidden, table)
        ref, n_ref = softmax_cross_entropy(logits, labels, mask)
        out, n = fused_softmax_cross_entropy(
            hidden, table.T, labels, mask, chunk=16,
            compute_dtype=jnp.float32, transpose_table=True)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(n, n_ref)

    def test_grad_matches(self):
        from ray_tpu.ops.losses import fused_softmax_cross_entropy

        hidden, table, labels = self._case(n=21, v=40)

        def ref_loss(h, w):
            return softmax_cross_entropy(
                jnp.einsum("bnd,vd->bnv", h, w), labels)[0]

        def fused_loss(h, w):
            return fused_softmax_cross_entropy(
                h, w, labels, chunk=8, compute_dtype=jnp.float32)[0]

        gh_ref, gw_ref = jax.grad(ref_loss, argnums=(0, 1))(hidden, table)
        gh, gw = jax.grad(fused_loss, argnums=(0, 1))(hidden, table)
        np.testing.assert_allclose(gh, gh_ref, atol=1e-5, rtol=1e-4)
        np.testing.assert_allclose(gw, gw_ref, atol=1e-5, rtol=1e-4)

    def test_model_loss_fused_vs_unfused(self):
        from ray_tpu.models import llama_debug
        from ray_tpu.models.transformer import init_params, loss_fn

        cfg_f = llama_debug(fused_ce=True, ce_chunk=32)
        cfg_u = llama_debug(fused_ce=False)
        params = init_params(cfg_u, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0,
                                    cfg_u.vocab_size)
        lf, _ = loss_fn(cfg_f, params, {"tokens": tokens})
        lu, _ = loss_fn(cfg_u, params, {"tokens": tokens})
        np.testing.assert_allclose(lf, lu, atol=1e-5, rtol=1e-5)
