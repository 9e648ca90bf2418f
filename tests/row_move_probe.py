"""How fast a kernel moves chosen ROWS out of a context it holds in VMEM
(ISSUE 62, step 0): not a test, a probe for the chip.

    chiprun -- python tests/row_move_probe.py            # times every form
    python tests/row_move_probe.py --compile FORM        # here, no chip:
                                                        # Mosaic's verdict

A context of ``C`` rows of 640 bf16 is copied into a VMEM scratch at the
grid's first step; each of ``Q`` grid steps then moves the 2048 rows its
SMEM block of positions names into a run scratch, as
``ops.picked_latent_attention``'s chunk kernel does. The forms differ in
how a row travels (``form_h`` is the kernel's ``_move_rows``; PERF.md 6,
PR 62 has what each read on the chip). Beside them XLA's gather of the same
rows in blocks of 64 queries, which is what the kernel replaced. Prints
nanoseconds a row; the figure holds the check's reduction of a query's run
(1,280 vector adds) and the grid step's own cost beside the moves.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

W, WIDTH = 640, 2048
HI = 0xFFFF0000


def _row(u, r):
    """Row r of the bf16 context behind ``u`` (uint32 [C/2, W]: a pair of
    rows a sublane) as uint32 [1, W] with the row's bits on top."""
    x = u[pl.ds(r >> 1, 1), :]
    return x << (16 * (1 - (r & 1)).astype(jnp.uint32))


def form_a(u, pos, run32, run16, i):
    """the issue's: a row at a time into a float32 run"""
    for k in range(8):
        j = i * 8 + k
        f = pltpu.bitcast(_row(u, pos[0, j]) & jnp.uint32(HI), jnp.float32)
        run32[pl.ds(j, 1), :] = f


def form_b(u, pos, run32, run16, i):
    """eight rows into one tile by selects, one aligned store"""
    lane = lax.broadcasted_iota(jnp.int32, (8, W), 0)
    tile = jnp.zeros((8, W), jnp.uint32)
    for k in range(8):
        x = jnp.broadcast_to(_row(u, pos[0, i * 8 + k]), (8, W))
        tile = jnp.where(lane == k, x, tile)
    run32[pl.ds(pl.multiple_of(i * 8, 8), 8), :] = pltpu.bitcast(
        tile & jnp.uint32(HI), jnp.float32)


def form_d(u, pos, run32, run16, i):
    """sixteen rows by selects straight into the bf16 run"""
    lane = lax.broadcasted_iota(jnp.int32, (16, W), 0)
    tile = jnp.zeros((16, W), jnp.uint32)
    for k in range(16):
        x = jnp.broadcast_to(_row(u, pos[0, i * 16 + k]), (16, W))
        tile = jnp.where(lane == k, x, tile)
    f = pltpu.bitcast(tile & jnp.uint32(HI), jnp.float32)
    run16[pl.ds(pl.multiple_of(i * 16, 16), 16), :] = f.astype(jnp.bfloat16)


def form_e(u, pos, run32, run16, i):
    """a row at a time, the PAIR of rows unshifted (a uint32 run)"""
    for k in range(8):
        j = i * 8 + k
        x = u[pl.ds(pos[0, j] >> 1, 1), :]
        run32[pl.ds(j, 1), :] = pltpu.bitcast(x, jnp.float32)


def form_h(u, pos, run32, run16, i, odd=None):
    """sixteen rows by selects, the pairs' sublanes ready-made (``pos`` is
    r >> 1) and the sixteen parities ONE word of a second SMEM block: the
    shift is made once a tile, on the vector unit"""
    lane = lax.broadcasted_iota(jnp.int32, (16, W), 0)
    tile = jnp.zeros((16, W), jnp.uint32)
    for k in range(16):
        x = jnp.broadcast_to(u[pl.ds(pos[0, i * 16 + k], 1), :], (16, W))
        tile = jnp.where(lane == k, x, tile)
    high = ((odd[0, i] >> lane) & 1) == 1
    tile = jnp.where(high, tile & jnp.uint32(HI), tile << 16)
    run16[pl.ds(pl.multiple_of(i * 16, 16), 16), :] = pltpu.bitcast(
        tile, jnp.float32).astype(jnp.bfloat16)


FORMS = {"h": (form_h, 16), "a": (form_a, 8), "b": (form_b, 8),
         "d": (form_d, 16), "e": (form_e, 8)}


def _kernel(pos, *refs, form):
    move, rows = FORMS[form]
    if form == "h":
        move = functools.partial(move, odd=refs[0])
        refs = refs[1:]
    ctx_hbm, o_ref, ctx, run32, run16, sem = refs

    @pl.when(pl.program_id(0) == 0)
    def _():
        cp = pltpu.make_async_copy(ctx_hbm, ctx, sem)
        cp.start()
        cp.wait()

    u = ctx.bitcast(jnp.uint32)
    lax.fori_loop(0, WIDTH // rows,
                  lambda i, _: move(u, pos, run32, run16, i), None)
    if form in "dh":
        o_ref[...] = run16[...].astype(jnp.float32).reshape(
            WIDTH // 8, 8, W).sum(axis=0)
    else:
        o_ref[...] = run32[...].reshape(WIDTH // 8, 8, W).sum(axis=0)


@functools.partial(jax.jit, static_argnames=("form", "interpret"))
def moved(ctx, positions, form, interpret=False):
    """ctx [C, W] bf16, positions [Q, WIDTH] int32 -> [Q, 8, W] float32:
    the sum over every eighth row of each query's run (a check that the
    rows arrived; 1,280 vector adds a query beside 2048 row moves)."""
    Q, C = positions.shape[0], ctx.shape[0]
    positions = positions[:, None]
    smem = lambda a: pl.BlockSpec((None,) + a.shape[1:], lambda q: (q, 0, 0),
                                  memory_space=pltpu.SMEM)
    extra = []
    if form == "h":
        odd = ((positions & 1).reshape(Q, 1, WIDTH // 16, 16)
               << jnp.arange(16)).sum(axis=-1)
        positions, extra = positions >> 1, [odd]
    return pl.pallas_call(
        functools.partial(_kernel, form=form),
        grid=(Q,),
        in_specs=[smem(positions)] + [smem(a) for a in extra] + [
            pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((None, 8, W), lambda q: (q, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((Q, 8, W), jnp.float32),
        scratch_shapes=[pltpu.VMEM((C, W), jnp.bfloat16),
                        pltpu.VMEM((WIDTH, W), jnp.float32),
                        pltpu.VMEM((WIDTH, W), jnp.bfloat16),
                        pltpu.SemaphoreType.DMA(())],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=120 << 20),
        name=f"row_move_{form}", interpret=interpret,
    )(positions, *extra, ctx)


@jax.jit
def gathered(ctx, positions):
    """XLA's gather of the same rows, 64 queries at a time, reduced alike."""
    def block(at):
        run = ctx[at].astype(jnp.float32)                 # [64, WIDTH, W]
        return run.reshape(64, WIDTH // 8, 8, W).sum(axis=1)
    Q = positions.shape[0]
    return lax.map(block, positions.reshape(Q // 64, 64, WIDTH)).reshape(
        Q, 8, W)


def _positions(rng, Q, C, order):
    at = np.stack([np.sort(rng.choice(C, WIDTH, replace=False))
                   for _ in range(Q)])
    if order == "random":
        at = rng.permuted(at, axis=1)
    return jnp.asarray(at, jnp.int32)


def _ms(fn, *args, calls=3):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / calls * 1e3


def compile_only(form: str, C: int = 66048, Q: int = 512) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    shape = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=chip)
    moved.lower(shape((C, W), jnp.bfloat16), shape((Q, WIDTH), jnp.int32),
                form=form).compile()
    print(f"form {form}: Mosaic takes it at C={C}")


def main() -> None:
    if len(sys.argv) > 2 and sys.argv[1] == "--compile":
        for form in sys.argv[2:]:
            try:
                compile_only(form)
            except Exception as e:  # the verdict is the output
                print(f"form {form}: REFUSED: {str(e)[:1500]}")
        return
    if jax.default_backend() != "tpu":
        raise SystemExit("the probe times a chip: run it through chiprun")
    forms = sys.argv[1:] or list(FORMS)
    rng = np.random.default_rng(62)
    Q, out = 512, {}
    for C in (40960, 66048):
        ctx = jax.random.normal(jax.random.key(C), (C, W), jnp.bfloat16)
        for order in ("ascending", "random"):
            at = _positions(rng, Q, C, order)
            want = gathered(ctx, at)
            ms = _ms(gathered, ctx, at)
            out[f"xla_gather_blocks64.{C}.{order}"] = {
                "ms": round(ms, 3), "ns_row": round(ms * 1e6 / at.size, 2)}
            for form in forms:
                key = f"form_{form}.{C}.{order}"
                try:
                    got = moved(ctx, at, form)
                    if form == "e":     # the pair's partner rides along
                        ok = None
                    else:
                        ok = bool(jnp.allclose(got, want, rtol=1e-5,
                                               atol=1e-3))
                    ms = _ms(moved, ctx, at, form)
                    out[key] = {"ms": round(ms, 3), "equal": ok,
                                "ns_row": round(ms * 1e6 / at.size, 2)}
                except Exception as e:
                    out[key] = {"refused": str(e)[:300]}
                print(json.dumps({key: out[key]}), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/row_move_probe.json", "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
