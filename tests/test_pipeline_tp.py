"""Tensor parallelism as a composed axis: tp x dp x pp (ISSUE 17).

The contracts under test:
  * sharding is EXACT — `partition_pipeline_params(...,
    tensor_parallel=tp)` and `reassemble_pipeline_params` are bit-exact
    inverses, and every stage actor's `_stage_init_tp` shard is
    bit-identical to slicing the fused `init_params` tree;
  * the Megatron conjugate pair is the fused math — a tp=2 stage pair
    emulated with `jax.vmap` + `psum_tp_ops` reproduces the fused
    model's loss AND the reassembled grads to 1e-5 (replicated leaves
    get the exact replicated grad);
  * the host-callback reduce ops (`make_tp_reduce_ops`) run the same
    collective sequence on every rank — proven with a threaded
    barrier reducer against closed-form grads;
  * the static tp schedule is a pure function of (S, V, M, depth,
    stage): per-chunk op counts, ascending microbatch order, identical
    replay — timing-divergent dynamic scheduling would desync the
    tagless collective streams;
  * on a real cluster, tp=2 x S=2 (and, slow, tp=2 x dp=2 x V=2)
    trains to the fused reference losses at 1e-5 with ZERO
    steady-state control-plane RPCs per rank (counter-asserted) and
    the tp groups demonstrably engaged; teardown returns every pin;
  * knob validation the house way — `tensor_parallel=0` (argument and
    RAY_TPU_PIPELINE_TP env) raises naming the knob, infeasible tp
    raises with the actionable count, tie_embeddings/MoE raise naming
    the config field.
"""

import threading
import time

import numpy as np
import pytest

import ray_tpu
from tests.test_train_pipeline import (_batch, _local_losses,
                                       _store_pins)

TP = 2


def _tp_cfg(num_layers=2):
    """llama_debug with head/kv/ffn counts divisible by tp=2."""
    from ray_tpu.models import presets

    return presets.llama_debug(
        num_layers=num_layers, vocab_size=128, max_seq_len=32,
        embed_dim=32, num_heads=4, num_kv_heads=2, mlp_dim=64)


def _assert_trees_equal(want, got, ctx=""):
    import jax

    wl = jax.tree_util.tree_leaves_with_path(want)
    gl = jax.tree_util.tree_leaves_with_path(got)
    assert len(wl) == len(gl), (ctx, len(wl), len(gl))
    for (pw, w), (pg, g) in zip(wl, gl):
        assert pw == pg, (ctx, pw, pg)
        assert np.array_equal(np.asarray(w), np.asarray(g)), (ctx, pw)


class TestTpPartition:
    def test_partition_reassemble_bit_exact(self):
        """partition -> reassemble must be the identity on the fused
        tree, bit-for-bit — the parity oracle every cluster test (and
        fetch_params consumer) leans on."""
        import jax

        from ray_tpu.models import presets, transformer

        cfg = _tp_cfg()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        shards = presets.partition_pipeline_params(
            cfg, params, 2, tensor_parallel=TP)
        for chunk in shards:
            assert isinstance(chunk, list) and len(chunk) == TP
        back = presets.reassemble_pipeline_params(
            cfg, shards, 2, tensor_parallel=TP)
        _assert_trees_equal(params, back)

    def test_tp1_partition_shape_unchanged(self):
        """tensor_parallel=1 must emit the EXACT pre-tp shard shape
        (dicts, not one-element lists) — downstream consumers index it."""
        import jax

        from ray_tpu.models import presets, transformer

        cfg = _tp_cfg()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        shards = presets.partition_pipeline_params(cfg, params, 2)
        assert all(isinstance(s, dict) for s in shards)
        back = presets.reassemble_pipeline_params(cfg, shards, 2)
        _assert_trees_equal(params, back)

    def test_stage_init_tp_matches_partitioned_init(self):
        """Each (chunk, tp_rank) shard built standalone on a stage actor
        must be bit-identical to slicing the fused init — stages never
        materialize the full model, so this is the init parity proof."""
        import jax

        from ray_tpu.models import presets, transformer

        cfg = _tp_cfg()
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        shards = presets.partition_pipeline_params(
            cfg, params, 2, tensor_parallel=TP)
        for c in range(2):
            for t in range(TP):
                got = presets._stage_init_tp(cfg, 0, 2, c, TP, tp_rank=t)
                _assert_trees_equal(shards[c][t], got, ctx=(c, t))

    def test_stage_defs_carry_tp_and_tail(self):
        from ray_tpu.models import presets

        defs = presets.pipeline_stage_defs(_tp_cfg(), 2, seed=0,
                                           tensor_parallel=TP)
        assert all(d["tp"] == TP for d in defs)
        # swiglu tail-splits on every chunk but the loss chunk (the
        # replicated lm_head consumes a completed residual stream)
        assert defs[0]["tp_tail"] is True
        assert defs[-1]["tp_tail"] is False


class TestTpEmulatedParity:
    def test_tp2_stage_math_matches_fused(self):
        """tp=2 single-stage math vs the fused model, emulated with
        vmap over the rank axis + psum tp ops: per-rank losses AND the
        reassembled grads (sharded + replicated leaves) match to 1e-5.

        This isolates the Megatron conjugate pair (g: partial-sum fwd /
        identity bwd at row-parallel outputs; f: identity fwd /
        allreduce bwd at column-parallel inputs) from the runtime."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import presets, transformer
        from ray_tpu.util.collective.tp import psum_tp_ops

        cfg = _tp_cfg()
        tokens = jnp.asarray(_batch(4, 16), jnp.int32)
        params = transformer.init_params(cfg, jax.random.PRNGKey(0))
        fused_loss, _ = transformer.loss_fn(cfg, params,
                                            {"tokens": tokens})
        fused_grads = jax.grad(lambda p: transformer.loss_fn(
            cfg, p, {"tokens": tokens})[0])(params)

        shards = presets.partition_pipeline_params(
            cfg, params, 2, tensor_parallel=TP)
        defs = presets.pipeline_stage_defs(cfg, 2, seed=0,
                                           tensor_parallel=TP)
        ops = psum_tp_ops("tp")

        def rank_loss(s0, s1, toks):
            u, mp = defs[0]["fwd"](s0, toks, tp_ops=ops)
            h = u + ops.g(mp)  # complete the tail reduce in-trace
            return defs[1]["loss"](s1, h, toks, tp_ops=ops)

        spec = transformer.tp_block_shard_spec(cfg)

        def in_axes_for(chunk_shard):
            out = {}
            for grp, leaves in chunk_shard["blocks"].items():
                gspec = spec.get(grp, {})
                out[grp] = {n: (0 if n in gspec else None)
                            for n in leaves}
            tree = {"blocks": out}
            for k in chunk_shard:
                if k != "blocks":
                    tree[k] = jax.tree.map(lambda _: None,
                                           chunk_shard[k])
            return tree

        ax0 = in_axes_for(shards[0][0])
        ax1 = in_axes_for(shards[1][0])
        is_none = lambda x: x is None  # noqa: E731

        def stack_chunk(chunk_shards, axtree):
            # stack only sharded leaves; replicated stay unbatched
            return jax.tree.map(
                lambda ax, *xs: jnp.stack(xs) if ax == 0 else xs[0],
                axtree, *chunk_shards, is_leaf=is_none)

        st0 = stack_chunk([shards[0][t] for t in range(TP)], ax0)
        st1 = stack_chunk([shards[1][t] for t in range(TP)], ax1)

        losses = jax.vmap(rank_loss, in_axes=(ax0, ax1, None),
                          axis_name="tp")(st0, st1, tokens)
        assert np.allclose(np.asarray(losses), float(fused_loss),
                           atol=1e-5), (losses, fused_loss)

        def mean_loss(s0, s1):
            ls = jax.vmap(rank_loss, in_axes=(ax0, ax1, None),
                          axis_name="tp")(s0, s1, tokens)
            return jnp.mean(ls)

        g0, g1 = jax.grad(mean_loss, argnums=(0, 1))(st0, st1)

        def unstack_chunk(gtree, axtree):
            # replicated leaves: vmap(None) summed rank cotangents —
            # exactly the fused grad, once (what f's bwd reduce gives
            # every cluster rank)
            return [jax.tree.map(
                lambda ax, a: a[t] if ax == 0 else a, axtree, gtree,
                is_leaf=is_none) for t in range(TP)]

        gfull = presets.reassemble_pipeline_params(
            cfg, [unstack_chunk(g0, ax0), unstack_chunk(g1, ax1)],
            2, tensor_parallel=TP)
        for (pw, w), (pg, g) in zip(
                jax.tree_util.tree_leaves_with_path(fused_grads),
                jax.tree_util.tree_leaves_with_path(gfull)):
            assert pw == pg, (pw, pg)
            assert np.allclose(np.asarray(w), np.asarray(g),
                               atol=1e-5), pw


class _ThreadReducer:
    """Barrier-based SUM allreduce across tp ranks running as threads —
    the in-process stand-in for the host collective group."""

    def __init__(self, tp):
        self.tp = tp
        self.bar = threading.Barrier(tp, timeout=30)
        self.slots = [None] * tp
        self.out = None

    def make(self, rank):
        def reduce_cb(a):
            self.slots[rank] = np.asarray(a)
            self.bar.wait()
            if rank == 0:
                self.out = sum(self.slots)
            self.bar.wait()
            res = np.array(self.out, copy=True)
            self.bar.wait()
            return res
        return reduce_cb


class TestTpReduceOps:
    def test_threaded_callback_ops_match_closed_form(self):
        """make_tp_reduce_ops under jit on two real threads: g must
        partial-sum forward / pass-through backward, f must pass
        forward / allreduce backward — checked against the closed-form
        grads of a toy loss. A desynced callback sequence would
        deadlock the barrier (timeout=30) instead of passing."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.util.collective.tp import make_tp_reduce_ops

        red = _ThreadReducer(TP)
        results, errs = [None] * TP, [None] * TP

        def run(rank):
            try:
                ops = make_tp_reduce_ops(red.make(rank))

                def fn(w, x):
                    y = ops.g(w * x)
                    return jnp.sum(y * y) + jnp.sum(ops.f(x))

                w = jnp.float32(rank + 1.0)
                x = jnp.arange(4, dtype=jnp.float32)
                loss, grads = jax.jit(
                    jax.value_and_grad(fn, argnums=(0, 1)))(w, x)
                results[rank] = (np.asarray(loss),
                                 [np.asarray(g) for g in grads])
            except Exception as e:  # noqa: BLE001 — re-raised below
                errs[rank] = e

        ts = [threading.Thread(target=run, args=(r,), daemon=True)
              for r in range(TP)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in ts), (
            "threaded tp reduce deadlocked — rank collective sequences "
            "diverged")
        for e in errs:
            if e:
                raise e
        # y = (w0 + w1) x = 3x on both ranks
        x = np.arange(4, dtype=np.float32)
        ref_loss = float(np.sum(9 * x * x) + np.sum(x))
        for r in range(TP):
            loss, (gw, gx) = results[r]
            assert np.allclose(loss, ref_loss), (r, loss, ref_loss)
            # dL/dw_r = 2 (w0+w1) sum(x^2) (g bwd passes through)
            assert np.allclose(gw, 6 * np.sum(x * x)), (r, gw)
            # dL/dx on rank r: the w_r*x path contributes 2 y w_r, the
            # f(x) path allreduces its ones cotangent across ranks
            assert np.allclose(gx, 6 * (r + 1) * x + TP), (r, gx)


class TestTpSchedule:
    @pytest.mark.parametrize("shape", [(2, 1, 4), (2, 2, 8), (3, 2, 4),
                                       (4, 1, 16), (4, 3, 8)])
    def test_counts_and_order(self, shape):
        """Every stage's static order runs each non-loss chunk exactly
        M fwds + M bwds (the loss chunk M fused fwds), microbatches in
        ascending order per (kind, chunk)."""
        from ray_tpu.train._internal.pipeline import _simulate_tp_schedule

        S, V, M = shape
        C = S * V
        for s in range(S):
            order = _simulate_tp_schedule(S, V, M, depth=4, stage=s)
            chunks = list(range(s, C, S))
            by = {}
            for kind, v, m in order:
                by.setdefault((kind, v), []).append(m)
            for i, c in enumerate(chunks):
                assert by[("fwd", i)] == list(range(M)), (s, c)
                if c == C - 1:
                    assert ("bwd", i) not in by  # loss fwd is fused
                else:
                    assert by[("bwd", i)] == list(range(M)), (s, c)

    def test_pure_function_replay(self):
        """Identical (S, V, M, depth, stage) must give the identical op
        list — tp peers derive their collective sequence from it, so
        any nondeterminism would desync the tagless reduces."""
        from ray_tpu.train._internal.pipeline import _simulate_tp_schedule

        a = _simulate_tp_schedule(3, 2, 8, depth=4, stage=1)
        b = _simulate_tp_schedule(3, 2, 8, depth=4, stage=1)
        assert a == b

    def test_depth2_high_m_feasible(self):
        """The simulator must stay deadlock-free at a shallow ring and
        deep microbatch count (the regime where a naive m-major GPipe
        order wedges on ring capacity) — it raises RuntimeError if no
        stage can make progress."""
        from ray_tpu.train._internal.pipeline import _simulate_tp_schedule

        for s in range(4):
            order = _simulate_tp_schedule(4, 2, 16, depth=2, stage=s)
            assert len(order) > 0


class TestTpValidation:
    def test_stage_defs_reject_zero_and_env_zero(self):
        from ray_tpu._private import config as cfgmod
        from ray_tpu.models import presets

        cfg = _tp_cfg()
        with pytest.raises(ValueError, match="tensor_parallel"):
            presets.pipeline_stage_defs(cfg, 2, tensor_parallel=0)
        old = cfgmod._global_config
        zero = cfgmod.Config()
        zero.pipeline_tp = 0
        cfgmod.set_global_config(zero)
        try:
            with pytest.raises(ValueError, match="RAY_TPU_PIPELINE_TP"):
                presets.pipeline_stage_defs(cfg, 2)
        finally:
            cfgmod.set_global_config(old)

    def test_indivisible_rejections_carry_counts(self):
        """Infeasible tp raises naming the config FIELD and the count
        the user must fix — heads, kv heads, and ffn width each."""
        from ray_tpu.models import presets

        cfg = _tp_cfg()  # heads=4, kv=2, mlp=64
        with pytest.raises(ValueError, match=r"cfg\.num_heads=4"):
            presets.pipeline_stage_defs(cfg, 2, tensor_parallel=8)
        with pytest.raises(ValueError, match=r"cfg\.num_kv_heads=2"):
            presets.pipeline_stage_defs(cfg, 2, tensor_parallel=4)
        odd = presets.llama_debug(
            num_layers=2, vocab_size=128, max_seq_len=32, embed_dim=32,
            num_heads=4, num_kv_heads=4, mlp_dim=66)
        with pytest.raises(ValueError, match=r"cfg\.mlp_dim=66"):
            presets.pipeline_stage_defs(odd, 2, tensor_parallel=4)

    def test_tie_embeddings_and_moe_name_the_field(self):
        from ray_tpu.models import presets

        tied = presets.llama_debug(
            num_layers=2, vocab_size=128, max_seq_len=32, embed_dim=32,
            num_heads=4, num_kv_heads=2, mlp_dim=64,
            tie_embeddings=True)
        with pytest.raises(ValueError, match="tie_embeddings"):
            presets.pipeline_stage_defs(tied, 2, tensor_parallel=2)
        moe = presets.moe_debug()
        with pytest.raises(ValueError, match="moe"):
            presets.pipeline_stage_defs(moe, 2, tensor_parallel=2)

    def test_trainer_rejects_zero_env_zero_and_mismatch(self, ray_init):
        from ray_tpu._private import api
        from ray_tpu.models import presets
        from ray_tpu.train import PipelineTrainer

        cfg = _tp_cfg()
        defs_tp1 = presets.pipeline_stage_defs(cfg, 2, seed=0)
        defs_tp2 = presets.pipeline_stage_defs(cfg, 2, seed=0,
                                               tensor_parallel=2)
        with pytest.raises(ValueError, match="tensor_parallel"):
            PipelineTrainer(defs_tp2, num_microbatches=2,
                            tensor_parallel=0)
        core = api._require_core()
        old = core.config.pipeline_tp
        core.config.pipeline_tp = 0
        try:
            with pytest.raises(ValueError, match="RAY_TPU_PIPELINE_TP"):
                PipelineTrainer(defs_tp2, num_microbatches=2)
        finally:
            core.config.pipeline_tp = old
        # stage defs and trainer must agree on the tp width
        with pytest.raises(ValueError, match="pipeline_stage_defs"):
            PipelineTrainer(defs_tp1, num_microbatches=2,
                            tensor_parallel=2)
        # tp>1 needs the channel substrate, and is not elastic yet
        with pytest.raises(ValueError, match="tasks"):
            PipelineTrainer(defs_tp2, num_microbatches=2,
                            tensor_parallel=2, mode="tasks")
        with pytest.raises(ValueError, match="elastic"):
            PipelineTrainer(defs_tp2, num_microbatches=2, dp=2,
                            tensor_parallel=2, elastic=True)


class TestTpClusterParity:
    def test_tp2_pipeline_matches_local_training(self, ray_init):
        """tp=2 x S=2 on a real cluster vs the fused single-process
        model: same init, same microbatch split, same SGD — losses to
        1e-5 every step, ZERO steady-state control-plane RPCs per rank
        (counter-asserted from each rank's flush report), tp groups
        demonstrably reducing, and teardown returns every pin."""
        import gc

        from ray_tpu._private import api
        from ray_tpu.models import presets
        from ray_tpu.train import PipelineTrainer

        core = api._core
        gc.collect()
        time.sleep(0.3)
        pins_before = _store_pins(core)

        cfg = _tp_cfg()
        batch = _batch()
        ref = _local_losses(cfg, batch, num_microbatches=4, steps=3)
        trainer = PipelineTrainer(
            presets.pipeline_stage_defs(cfg, 2, seed=0,
                                        tensor_parallel=TP),
            num_microbatches=4, tensor_parallel=TP,
            optimizer=("sgd", 0.05))
        try:
            assert trainer.is_channel_backed
            assert trainer.channel_depth > 1
            assert trainer.tensor_parallel == TP
            got, outs = [], []
            for _ in range(3):
                out = trainer.step(batch)
                outs.append(out)
                got.append(out["loss"])
            assert np.allclose(got, ref, atol=1e-5), (got, ref)
            assert got[-1] < got[0], "no training progress"
            # flush 0 absorbs the declarative group rendezvous; every
            # later flush must be pure data plane on all S x tp ranks
            for out in outs[1:]:
                assert len(out["reports"]) == 2 * TP
                for rep in out["reports"]:
                    assert rep["tp"] == TP
                    assert rep["tp_reduce_calls"] > 0, (
                        "tp groups never engaged", rep)
                    assert rep["rpc_calls"] == 0, (
                        f"stage {rep['stage']} tp_rank {rep['tp_rank']} "
                        f"issued {rep['rpc_calls']} control-plane RPCs "
                        f"in a steady flush")
        finally:
            trainer.shutdown()
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            if _store_pins(core) == pins_before:
                break
            time.sleep(0.2)
        assert _store_pins(core) == pins_before, (
            "tp pipeline leaked pins")

    def test_tp2_overlap_off_matches_too(self, ray_init):
        """tp_overlap=False serializes every tail reduce in line — the
        losses must be IDENTICAL (overlap is a latency hide, never a
        numeric change)."""
        from ray_tpu.models import presets
        from ray_tpu.train import PipelineTrainer

        cfg = _tp_cfg()
        batch = _batch()
        ref = _local_losses(cfg, batch, num_microbatches=4, steps=2)
        trainer = PipelineTrainer(
            presets.pipeline_stage_defs(cfg, 2, seed=0,
                                        tensor_parallel=TP),
            num_microbatches=4, tensor_parallel=TP, tp_overlap=False,
            optimizer=("sgd", 0.05))
        try:
            got = [trainer.step(batch)["loss"] for _ in range(2)]
        finally:
            trainer.shutdown()
        assert np.allclose(got, ref, atol=1e-5), (got, ref)

    @pytest.mark.slow
    def test_tp2_dp2_v2_matches_local_training(self, ray_init):
        """The full 3D grid (tp=2 x dp=2 x S=2, V=2 interleaved): loss
        parity vs the fused model to 1e-5 with zero steady-state
        control-plane RPCs per rank — the ISSUE 17 acceptance shape."""
        from ray_tpu.models import presets
        from ray_tpu.train import PipelineTrainer

        cfg = _tp_cfg(num_layers=4)
        batch = _batch()
        ref = _local_losses(cfg, batch, num_microbatches=4, steps=3)
        trainer = PipelineTrainer(
            presets.pipeline_stage_defs(cfg, 2, seed=0, virtual_stages=2,
                                        tensor_parallel=TP),
            num_microbatches=4, dp=2, virtual_stages=2,
            tensor_parallel=TP, optimizer=("sgd", 0.05),
            buffer_bytes=1 * 1024 * 1024)
        try:
            assert trainer.tensor_parallel == TP
            got, outs = [], []
            for _ in range(3):
                out = trainer.step(batch)
                outs.append(out)
                got.append(out["loss"])
            assert np.allclose(got, ref, atol=1e-5), (got, ref)
            for out in outs[1:]:
                assert len(out["reports"]) == 2 * 2 * TP
                for rep in out["reports"]:
                    assert rep["tp"] == TP
                    assert rep["tp_reduce_calls"] > 0
                    assert rep["rpc_calls"] == 0, rep
        finally:
            trainer.shutdown()
