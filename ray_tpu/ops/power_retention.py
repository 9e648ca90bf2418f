"""Power retention of degree 2, gated and normalised: a fixed float32 state a
K/V head instead of a K/V cache, whatever the context.

    a_tj = (q_t . k_j / sqrt(D))^2 * exp(c_(j+1) + ... + c_t)       j <= t
    y_t  = sum_j a_tj v_j / (sum_j a_tj + eps)

with ``c_t <= 0`` the token's log-gate, one a K/V head. ``(q . k)^2 = phi(q) .
phi(k)`` over the symmetric half of the outer product (the ``D (D + 1) / 2``
pairs ``a <= b``: 8256 for ``D`` = 128), so the same function is a recurrence,

    S_t = exp(c_t) S_(t-1) + phi(k_t) v_t^T        z_t = exp(c_t) z_(t-1) + phi(k_t)
    y_t = phi(q_t)^T S_t / D / (phi(q_t) . z_t / D + eps)

and ``H / G`` query heads read the one state of their K/V head.

The pairs are laid out by FOLDING the triangle. Every unordered pair ``{a,
b}`` of ``D`` values is ``(a, (a + r) mod D)`` for exactly one ``r`` in ``0 ..
D / 2 - 1`` (``r = 0``: the squares), or twice for ``r = D / 2``: lane ``r D
+ a`` of a head's pairs holds the pair ``(a, (a + r) mod D)``, ``r = 0 .. D /
2``, so row ``r`` of ``phi(u)`` is ``u`` times ``u`` rolled by ``r`` and no
value has to be picked out by a matrix. That is ``D (D / 2 + 1)`` lanes: 8320
for ``D`` = 128, exactly 65 rows of 128 lanes with none of them padding
(never the 16384 of the square). ``phi(q)`` holds ``q_a q_b`` and ``phi(k)``
holds ``k_a k_b`` times the lane's weight (``_weights``): 1 on ``r = 0`` (a
square), 2 on ``r = 1 .. D / 2 - 1`` (the pair stands for ``(b, a)`` too;
``sqrt(2)`` on both sides is the same product, and 2 is exact in every float
type) and 1 on ``r = D / 2``, where each pair stands twice. The state is
kept value-major and in tiles, ``s [G, tiles, D, lanes]`` and ``z [G, tiles,
1, lanes]``: the pairs lie along the lanes, padded with pairs that stay zero
to whole 128-lane rows, a tile is a block of them that a kernel takes by its
leading index (``layout``). Nothing outside this module knows the order of
the lanes (``layout`` and ``state_shapes`` are all a holder reads), and a
state lives only inside the process that wrote it — a replica's slots
(``decode.RetentionState``; the scheduler refuses to export a prefix of such
a model), never a file or a wire — which is what lets the order change with
the build: a state an older build wrote (pairs ``a <= b`` in ``triu`` order)
is NOT readable by this one. Two kernels:

  * ``power_retention_step`` — one token a row: a K/V head's state and
    normaliser are read once and written once, in place, for all its query
    heads together, on the vector unit; a row that is not ``active`` gets
    its state back bitwise. ``phi`` of the row's q and k is made outside
    (``_phi``: two selections over the fold and a product: 5% of the bytes
    the state is). The kernel reads pairs and state lane by lane and is
    indifferent to their order.
  * ``power_retention_chunk`` — ``S`` tokens in blocks: inside a block the
    attention form (squared scores, the gates' running sum as decay, causal),
    across blocks ``phi(Q) S_in`` and ``S_out = decay S_in + phi(K)^T V``,
    with ``phi`` made in the kernel a tile at a time; the state rides in
    VMEM from block to block, so a chunk reads and writes it once whatever
    its length. ``real_len`` tokens are real and the rest trailing padding,
    which neither decays the state nor adds to it. Which way a tile's pairs
    are made hangs on the head's width alone. A head of whole lane rows
    (``D % 128 == 0`` with a tile whole rows of the fold: 128, the
    published width) ROTATES: the block's rows times themselves under
    ``pltpu.roll`` along the lanes, on the rotate and vector units, and no
    matrix work. A narrower head (16, 32, 64: the debug preset and the
    tests; a lane rotation cannot turn 16 values inside a 128-lane row)
    SELECTS over the same fold: two 0/1 matrices (``_selectors``) pick each
    pair's values, as every width did before the fold.

Both are Pallas kernels under those names (what a profiler trace shows as
the op), interpreted off a TPU (``ops/_pallas.py``). Matmul operands take the
type of ``q`` (bfloat16 when serving); state, normaliser, gates and every
sum are float32. What goes INTO the state is exact: a key's pairs enter it
as two bfloat16 values each, high and low (``_chunk_kernel``). What a chunk
READS across blocks is not: the queries' pairs and the state are rounded to
``q``'s type for that one product (the step kernel reads in float32).
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops._pallas import should_interpret

EPS = 1e-6          # added to the sum of a token's weights
_BLOCK = 128        # tokens of one block of the chunked scan
_LANES = 128
_TILE_LANES = 5     # 128-lane rows of one tile of pairs, at most
_AUG = 16           # rows under V^T that carry the normaliser's update
_VMEM = 64 << 20
F32 = jnp.float32


def layout(head_dim: int) -> Tuple[int, int]:
    """(tiles, lanes a tile) of a head's pairs: the ``D (D / 2 + 1)`` lanes
    of the folded triangle, padded with pairs that stay zero to whole
    128-lane rows."""
    if head_dim % 2:
        raise ValueError(f"power retention folds a head of an even size, "
                         f"not {head_dim}")
    rows = -(-(head_dim * (head_dim // 2 + 1)) // _LANES)
    per = max(n for n in range(1, _TILE_LANES + 1) if rows % n == 0)
    return rows // per, per * _LANES


def state_shapes(rows: int, kv_heads: int, head_dim: int) -> Dict[str, tuple]:
    """The shapes of ``rows`` sequences' states: ``s`` and the normaliser
    ``z``, float32 both."""
    tiles, lanes = layout(head_dim)
    return {"s": (rows, kv_heads, tiles, head_dim, lanes),
            "z": (rows, kv_heads, tiles, 1, lanes)}


def _fold_index(head_dim: int):
    """``(a, b)`` of every lane of the fold, in order: lane ``r D + a``
    holds the pair ``(a, (a + r) mod D)``, ``r = 0 .. D / 2``."""
    r, a = np.divmod(np.arange(head_dim * (head_dim // 2 + 1)), head_dim)
    return a, (a + r) % head_dim


@functools.lru_cache(maxsize=None)
def _weights(head_dim: int):
    """``w [tiles, 1, lanes]``: a lane's weight on the key side. 1 on ``r =
    0`` (a value's square) and on ``r = D / 2`` (where each pair stands
    twice), 2 between (the pair stands for ``(b, a)`` too), 0 on padding."""
    tiles, lanes = layout(head_dim)
    r = np.arange(head_dim * (head_dim // 2 + 1)) // head_dim
    w = np.zeros((tiles * lanes,), np.float32)
    w[:r.size] = np.where((r == 0) | (r == head_dim // 2), 1.0, 2.0)
    return w.reshape(tiles, 1, lanes)


@functools.lru_cache(maxsize=None)
def _selectors(head_dim: int):
    """``(A, B [tiles, D, lanes])``: ``u A`` holds ``u_a`` at every lane of
    the fold, ``u B`` holds ``u_b``; both are 0 on the padding. What a row's
    pairs are made with where nothing can rotate it: the step's q and k
    (``_phi``) and the chunk kernel's heads narrower than a lane row."""
    tiles, lanes = layout(head_dim)
    picks = []
    for index in _fold_index(head_dim):
        m = np.zeros((head_dim, tiles * lanes), np.float32)
        m[index, np.arange(index.size)] = 1.0
        picks.append(np.ascontiguousarray(
            m.reshape(head_dim, tiles, lanes).transpose(1, 0, 2)))
    return tuple(picks)


def _phi(u):
    """u [..., D] -> the products of its pairs, float32 [..., tiles, lanes]
    (unweighted), for the step's one row a sequence. A selection picks
    single values, so it is exact in u's own type. (Row ``r`` of the fold is
    ``u`` times ``u`` rolled by ``r`` here too, but XLA has no cheap form of
    it: 65 ``jnp.roll`` stacked run a step 0.1 ms faster and compile 7 s a
    program slower, a gather or a reshape of ``u`` laid end to end compile
    like this and run a step 0.9-1.3 ms slower — ``PERF.md`` 6, PR 58.)"""
    precision = lax.Precision.HIGHEST if u.dtype == F32 else None
    pick = lambda m: jnp.einsum("...d,tdr->...tr", u, jnp.asarray(m, u.dtype),
                                precision=precision,
                                preferred_element_type=F32)
    sel_a, sel_b = _selectors(u.shape[-1])
    return pick(sel_a) * pick(sel_b)


def _fold(x):
    """[rows, lanes] -> [rows, 128]: the 128-lane rows of a tile added up."""
    return sum(x[:, i:i + _LANES] for i in range(0, x.shape[1], _LANES))


def _step_kernel(active_ref, decay_ref, pq_ref, pk_ref, v_ref, s_ref, z_ref,
                 o_ref, so_ref, zo_ref, *, rep, groups):
    b, g = pl.program_id(0), pl.program_id(1)
    live = active_ref[b] > 0
    decay = decay_ref[b * groups + g]
    tiles, d, lanes = s_ref.shape
    v = jnp.broadcast_to(v_ref[...], (d, lanes))       # v down the sublanes

    def tile(t, sums):
        num, den = sums
        s, z, pk, pq = s_ref[t], z_ref[t], pk_ref[t], pq_ref[t]
        new = decay * s + v * pk                                  # [D, lanes]
        norm = decay * z + pk                                     # [1, lanes]
        so_ref[t] = jnp.where(live, new, s)
        zo_ref[t] = jnp.where(live, norm, z)
        return (tuple(n + _fold(new * pq[h:h + 1]) for h, n in enumerate(num)),
                tuple(n + _fold(norm * pq[h:h + 1])
                      for h, n in enumerate(den)))

    num, den = lax.fori_loop(
        0, tiles, tile, ((jnp.zeros((d, _LANES), F32),) * rep,
                         (jnp.zeros((1, _LANES), F32),) * rep))
    o_ref[...] = jnp.zeros(o_ref.shape, F32)
    for h in range(rep):
        total = jnp.sum(den[h], axis=1, keepdims=True) + EPS      # [1, 1]
        o_ref[:, h:h + 1] = jnp.sum(num[h], axis=1, keepdims=True) / total


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step(q, k, v, log_gate, s, z, active, interpret):
    B, H, D = q.shape
    G = k.shape[1]
    rep = H // G
    pad = -rep % 8
    tiles, lanes = layout(D)
    # a query head's pairs over D (phi(q / sqrt(D)) = phi(q) / D), its K/V
    # head's heads together: [B, G, tiles, rep (+ pad), lanes]
    pq = (_phi(q) / D).reshape(B, G, rep, tiles, lanes).transpose(
        0, 1, 3, 2, 4)
    pq = jnp.pad(pq, ((0, 0),) * 3 + ((0, pad), (0, 0)))
    pk = (_phi(k) * _weights(D)[:, 0])[:, :, :, None]
    cell = lambda b, g, *_: (b, g, 0, 0, 0)
    whole = lambda rows: pl.BlockSpec((None, None, tiles, rows, lanes), cell)
    column = pl.BlockSpec((None, None, D, 1), lambda b, g, *_: (b, g, 0, 0))
    out = pl.BlockSpec((None, None, D, rep + pad),
                       lambda b, g, *_: (b, g, 0, 0))
    o, s, z = pl.pallas_call(
        functools.partial(_step_kernel, rep=rep, groups=G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, G),
            in_specs=[whole(rep + pad), whole(1), column, whole(D), whole(1)],
            out_specs=[out, whole(D), whole(1)]),
        out_shape=[jax.ShapeDtypeStruct((B, G, D, rep + pad), F32),
                   jax.ShapeDtypeStruct(s.shape, F32),
                   jax.ShapeDtypeStruct(z.shape, F32)],
        input_output_aliases={5: 1, 6: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM),
        name="power_retention_step", interpret=interpret,
    )(active.astype(jnp.int32), jnp.exp(log_gate.astype(F32)).reshape(-1),
      pq, pk, v.astype(F32)[..., None], s, z)
    return o[..., :rep].transpose(0, 1, 3, 2).reshape(B, H, D), s, z


def power_retention_step(q, k, v, log_gate, s, z, active):
    """One token a row. q: ``[B, H, D]``; k, v: ``[B, G, D]``; log_gate:
    ``[B, G]`` (``log sigmoid``, float32); s, z: the rows' states
    (``state_shapes``); active: ``[B]`` (a row at 0 keeps its state bitwise;
    its output means nothing). Returns ``(o [B, H, D] float32, s, z)``."""
    return _step(q, k, v, log_gate, s, z, active, should_interpret())


def _chunk_kernel(len_ref, *refs, block, rep):
    (*select, x_ref, v_ref, vdt_ref, col_ref, row_ref, w_ref, s_in_ref,
     z_in_ref, o_ref, s_out_ref, z_out_ref, s_scr, z_scr, num_scr,
     den_scr) = refs
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        s_scr[...] = s_in_ref[...]
        z_scr[...] = z_in_ref[...]

    tiles, d, lanes = s_scr.shape
    x, v, vdt = x_ref[...], v_ref[...], vdt_ref[...]
    mx = x.dtype
    # float32 operands are multiplied as float32 (a TPU's default would
    # round them to bfloat16 on the way in)
    exact = dict(preferred_element_type=F32,
                 precision=lax.Precision.HIGHEST if mx == F32 else None)
    dot = functools.partial(jnp.dot, **exact)
    rows_t = lambda x, y: lax.dot_general(                  # x y^T
        x, y, (((1,), (1,)), ((), ())), **exact)
    q_rows = rep * block
    # the gates' running sum from the block's start to behind each token
    # (flat over the padding), down the rows and along them
    col, row = col_ref[...], row_ref[...]
    through = jnp.exp(jnp.min(row, axis=1, keepdims=True))  # the whole block
    num_scr[...] = jnp.zeros(num_scr.shape, F32)
    den_scr[...] = jnp.zeros(den_scr.shape, F32)
    if select:
        # a head narrower than a lane row: two selections pick each pair's
        # values out of it
        a_ref, b_ref = select
        pairs = lambda t: dot(x, a_ref[t]) * dot(x, b_ref[t])
    else:
        # a head of whole lane rows: row r of the fold is x times x rolled
        # by r along the lanes, on the rotate and vector units
        xf = x.astype(F32)
        turn = lambda u, r: pltpu.roll(u, (d - r) % d, 1)
        if mx.itemsize == 2:
            # a rotation moves 32-bit lanes: two rows of x go as one
            packed = pltpu.bitcast(x, jnp.uint32)
            rolled = lambda r: pltpu.bitcast(turn(packed, r), mx).astype(F32)
        else:
            rolled = functools.partial(turn, xf)
        pairs = lambda t: jnp.concatenate(
            [xf * rolled(t * (lanes // d) + i) for i in range(lanes // d)],
            axis=1)

    def tile(t, _):
        # the pairs of every row of x, the query heads' and then the key's
        phi = pairs(t)                                    # [rows, lanes]
        s, z = s_scr[t], z_scr[t]
        pq = phi[:q_rows]
        num_scr[...] += rows_t(pq.astype(mx), s.astype(mx))
        den_scr[...] += jnp.sum(pq * z, axis=1, keepdims=True)
        # V^T (each token's decay to the block's end in it) over the keys'
        # pairs; the rows under V^T carry that decay alone: the normaliser.
        # A pair is a product of two values of x's type and so EXACTLY two
        # values of that type, high and low: the state takes both. (A pair
        # rounded to bfloat16 is off by 2^-9 of itself, each pair its own
        # way, and the pairs of q against k cancel down to (q . k)^2: a
        # token's weight would come out of the state off by 0.002 of a
        # typical weight, below zero where its true weight is small, and
        # the normalised output of a row whose weights are all small would
        # be anything.)
        pk = phi[q_rows:] * w_ref[t]
        high = pk.astype(mx)
        new = dot(vdt, high)
        if mx != F32:
            new += dot(vdt, (pk - high.astype(F32)).astype(mx))
        s_scr[t] = through * s + new[:d]
        z_scr[t] = through * z + new[d:d + 1]
        return 0

    lax.fori_loop(0, tiles, tile, 0)
    n = jnp.clip(len_ref[0] - c * block, 0, block)    # real tokens in here
    i = lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    j = lax.broadcasted_iota(jnp.int32, (1, block), 1)
    seen = jnp.logical_and(j <= i, j < n)
    decay = jnp.where(seen, jnp.exp(jnp.where(seen, col - row, 0.0)), 0.0)
    k = x[q_rows:]
    carried = jnp.exp(col) / d                   # what the state in is worth
    for h in range(rep):
        rows = slice(h * block, (h + 1) * block)
        a = rows_t(x[rows], k)
        a = a * a * decay / d                                      # [C, C]
        num = dot(a.astype(mx), v) + carried * num_scr[rows]
        den = (jnp.sum(a, axis=1, keepdims=True) + carried * den_scr[rows]
               + EPS)
        o_ref[rows] = (num / den).astype(o_ref.dtype)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        s_out_ref[...] = s_scr[...]
        z_out_ref[...] = z_scr[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _chunk(q, k, v, log_gate, s, z, real_len, interpret):
    B, S, H, D = q.shape
    G = k.shape[2]
    rep = H // G
    tiles, lanes = layout(D)
    block = min(_BLOCK, -(-S // 16) * 16)
    nb = -(-S // block)
    pad = nb * block - S
    real = jnp.arange(nb * block) < real_len                     # [S]
    # [B, S, heads, D] -> [B, G, blocks, heads of the group x block, D]
    cut = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        B, nb, block, G, -1, D).transpose(0, 3, 1, 4, 2, 5).reshape(
        B, G, nb, -1, D)
    qb, kb, vb = cut(q), cut(k), cut(v)
    gates = jnp.where(real[None, :, None], jnp.pad(
        log_gate.astype(F32), ((0, 0), (0, pad), (0, 0))), 0.0)
    cum = jnp.cumsum(gates.reshape(B, nb, block, G).transpose(0, 3, 1, 2),
                     axis=-1)                               # [B, G, nb, C]
    to_end = jnp.where(real.reshape(nb, block), jnp.exp(cum[..., -1:] - cum),
                       0.0)
    vdt = jnp.concatenate([
        (vb.astype(F32) * to_end[..., None]).swapaxes(-1, -2),
        to_end[..., None, :], jnp.zeros((B, G, nb, _AUG - 1, block), F32)],
        axis=3).astype(q.dtype)
    # a head of whole lane rows, a tile whole rows of the fold, makes its
    # pairs by rotation in the kernel
    select = [] if D % _LANES == 0 and lanes % D == 0 else [
        jnp.asarray(m, q.dtype) for m in _selectors(D)]
    at = lambda b, g, c, *_: (b, g, c, 0, 0)
    tokens = lambda rows, cols: pl.BlockSpec((None, None, None, rows, cols),
                                             at)
    fixed = lambda rows: pl.BlockSpec((tiles, rows, lanes),
                                      lambda *_: (0, 0, 0))
    state = lambda rows: pl.BlockSpec((None, None, tiles, rows, lanes),
                                      lambda b, g, c, *_: (b, g, 0, 0, 0))
    o, s, z = pl.pallas_call(
        functools.partial(_chunk_kernel, block=block, rep=rep),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, G, nb),
            in_specs=[fixed(D)] * len(select) + [
                tokens((rep + 1) * block, D), tokens(block, D),
                tokens(D + _AUG, block), tokens(block, 1), tokens(1, block),
                fixed(1), state(D), state(1)],
            out_specs=[tokens(rep * block, D), state(D), state(1)],
            scratch_shapes=[pltpu.VMEM((tiles, D, lanes), F32),
                            pltpu.VMEM((tiles, 1, lanes), F32),
                            pltpu.VMEM((rep * block, D), F32),
                            pltpu.VMEM((rep * block, 1), F32)]),
        out_shape=[jax.ShapeDtypeStruct((B, G, nb, rep * block, D), q.dtype),
                   jax.ShapeDtypeStruct(s.shape, F32),
                   jax.ShapeDtypeStruct(z.shape, F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM),
        name="power_retention_chunk", interpret=interpret,
    )(jnp.reshape(real_len, (1,)).astype(jnp.int32), *select,
      jnp.concatenate([qb, kb], axis=3), vb, vdt, cum[..., None],
      cum[..., None, :], jnp.asarray(_weights(D)), s, z)
    o = o.reshape(B, G, nb, rep, block, D).transpose(0, 2, 4, 1, 3, 5)
    return o.reshape(B, nb * block, H, D)[:, :S], s, z


def power_retention_chunk(q, k, v, log_gate, s, z, real_len):
    """``S`` tokens a row, the first ``real_len`` (a scalar) real. q: ``[B,
    S, H, D]``; k, v: ``[B, S, G, D]``; log_gate: ``[B, S, G]``; s, z: the
    rows' states (``state_shapes``). Returns ``(o [B, S, H, D] in q's type,
    s, z after real_len tokens)``; the outputs of the padding mean
    nothing."""
    return _chunk(q, k, v, log_gate, s, z, real_len, should_interpret())
