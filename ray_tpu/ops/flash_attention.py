"""Pallas TPU flash attention, forward + backward kernels.

Layout [B, S, H, D] (seq-major, as the models have it), and the kernels read
it AS IT IS, viewed [B, S, H*D]: a grid cell takes a block of sequence rows
and a block of lanes that holds whole heads, so no operand is transposed to
head-major in HBM and a head of 64 is not padded to 128 lanes there (the
head-major bf16[B, H, S, 64] the kernels took before PR 33 is stored in
128-lane tiles, twice the bytes, and its f32[B, H, S, 1] logsumexp 128
lanes a row: the compiled text's layouts). Heads narrower than a vreg share
a 128-lane window: a head's own lanes are kept and the others zeroed in ONE
operand of each product (q, dO or k, v: once a cell), so a product over the
whole window is the head's own, and of an accumulator only the head's lanes
are kept at the end. GQA maps each query head to its kv head's lanes; kv
heads are never repeated in HBM. Off-TPU the kernels run in interpreter
mode, the same code path on the CPU test mesh.

The schedule of all three kernels comes from static shapes, `tile_sizes`:
  - a grid cell owns `heads` query heads and `rows` of its own sequence
    (queries for the forward and dQ, keys for dK/dV) and holds `major` of
    the OTHER sequence in VMEM: all of it where that fits the budget
    ("resident": fetched once a head block, the index map does not depend
    on the cell's row block), else a part, streamed over a fourth grid axis
    with the running state in scratch between cells;
  - inside the cell a loop walks sub-blocks of `cols` of what it holds, and
    only the causal triangle: its trip counts come from the cell's row
    block, sub-blocks wholly on the visible side run a body without a mask,
    only those the diagonal crosses build and apply one, those beyond it
    cost no matmul, no loop trip and (streamed) no fetch;
  - the scores are held TRANSPOSED, S^T = K Q^T [keys, queries]: the
    queries lie along the lanes, so the running max and sum are rows (a
    vreg or two a head, carried by the loop, not rows / 8 vregs in
    scratch), their reductions run down the sublanes on the vector units
    (no cross-lane unit), lse and delta are read as they are stored, and
    O^T += V^T P^T, dQ^T += K^T dS^T, dV += P^T dO, dK += dS^T Q are plain
    products (a K or V tile is transposed in-kernel: free beside them);
  - a loop trip takes its heads as stages of a pipeline EMITTED skewed
    (`_pipelined`): the scheduler keeps close to program order;
  - the softmax scale multiplies q (or k) once a cell where that is exact
    (a power of two: head 64) and the f32 scores where it is not (head 128).

Forward: online softmax (FlashAttention-2), saving the per-row logsumexp
lane-dense, [B, H, 1, S]. Matmul operands stay in the model dtype (bf16 on
TPU) with f32 accumulation; all softmax arithmetic is f32. Backward: two
kernels that recompute p from (q, k, lse): dQ walks as the forward does,
dK/dV is the mirror image (the cell's own block is keys, the GQA group's
query heads sum into their kv head's accumulator in-kernel). delta =
rowsum(dO * O) is computed in XLA (cheap elementwise) and fed in.

Under a ``jax.checkpoint`` the forward kernel runs ONCE (PR 48): the forward
rule names the two arrays the backward kernels read beside q, k and v — the
output (`FLASH_OUT`) and the log-sum-exp (`FLASH_LSE`) — and both of the
models' remat policies keep them (``models/transformer.py: remat_policy``:
'full' keeps a block's input, these two and under ``tp`` the reduced ``wo``
result; 'dots' those and every matmul's output, for ``checkpoint_dots`` does
not see a Pallas call). It is the one part of a block whose recompute grows
with S squared, and what is kept is as small as the block's input ([B, S,
H*D], the float32 log-sum-exp 2/D of it). With no checkpoint around the call —
the serving programs, the reference checks — a name is an identity.

What it measures (my chip runs, PR 33, one v5e; PERF.md sections 5 and 6):
B 128, H 12, S 1024, D 64 (`gpt2s_train`): forward 12.81 -> 6.08 ms a call,
dQ 10.49 -> 6.53, dK/dV 10.49 -> 9.15; B 4, H 16 over 4 kv heads, S 4096,
D 128 (`mistral7b_train_4chip`'s shard): 4.86 -> 2.65, 3.40 -> 2.93, 5.26
-> 3.53. A kernel's time follows its pushes to and pops from the MXU at
about two cycles each, not its flops: at D 64 half of every product is
another head's lanes or zeros.

Reference parity surface: the reference delegates to torch SDPA inside
workers; this is the TPU-native equivalent of that compute path.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops._pallas import should_interpret

NEG_INF = -1e30
_LANES = 128

# What the forward kernel hands the two backward kernels, by name, so that a
# ``jax.checkpoint`` around the caller can keep them (module docstring;
# ``models/transformer.py: remat_policy``). Outside one a name is an identity.
FLASH_OUT = "flash_attn_out"
FLASH_LSE = "flash_attn_lse"

# What a cell's pipelined blocks may take of VMEM (bytes, both buffers of
# each counted, `block_bytes`): the rule that decides resident against
# streamed. Mosaic is given `_VMEM_LIMIT` for the call; the rest is the
# loop's spilled score tiles and carries (my chip runs, PR 33: with all of
# `gpt2s_train`'s 12 heads a cell and 512 x 512 tiles dK/dV ran 14.2 ms for
# 9.1, and 8 heads of 128 at 512 rows ran out of VMEM at this limit; both
# are outside what `tile_sizes` returns). v5e has 128 MiB of VMEM a core,
# of which a kernel gets 16 MiB unless it asks.
_VMEM_BUDGET = 24 * 1024 * 1024
_VMEM_LIMIT = 40 * 1024 * 1024
# a cell's query heads fill at most this many lanes, or are the fewest legal.
# Measured, PR 33: all 12 heads of 64 a cell run the forward 5.6 ms a call
# for 6.1 with 4, but every head is unrolled in the kernel's body, traced and
# lowered in every process that builds the step: 12 heads a cell added 3.8 s
# to `gpt2s_train`'s 26 s of set-up (its bound is 10%), 4 heads about 1 s;
# 8 heads of 128 gain nothing over 4.
_MAX_HEAD_LANES = 256


class Tiles(NamedTuple):
    """One kernel's schedule (module docstring)."""
    heads: int   # query heads a grid cell
    rows: int    # the cell's block of its own sequence
    cols: int    # the other sequence's sub-block one loop trip takes
    major: int   # how much of the other sequence a cell holds in VMEM


class Schedule(NamedTuple):
    fwd: Tiles
    dq: Tiles
    dkv: Tiles


def _divisor(seq: int, target: int) -> int:
    """Largest divisor of seq that is a multiple of 128 and <= target; the
    whole sequence where there is none (a block is lane- and sublane-
    aligned, or the whole axis)."""
    best = 0
    for b in range(_LANES, min(seq, target) + 1, _LANES):
        if seq % b == 0:
            best = b
    return best or seq


def _head_choices(num_heads: int, group: int, head_dim: int):
    """Heads a cell may take, ascending: whole GQA groups that divide the
    heads and fill whole 128-lane windows of q and of k/v (or all heads)."""
    out = []
    for h in range(group, num_heads + 1, group):
        if num_heads % h:
            continue
        whole = all(n * head_dim % _LANES == 0 for n in (h, h // group))
        if whole or h == num_heads:
            out.append(h)
    return out


def block_bytes(tiles: Tiles, role: str, head_dim: int, group: int,
                itemsize: int) -> int:
    """VMEM the pipeline holds for one cell of `role` ('fwd', 'dq', 'dkv'):
    every block twice (two buffers). lse and delta are f32 rows, stored
    eight sublanes a row."""
    q_lanes = tiles.heads * head_dim
    kv_lanes = q_lanes // group
    stat = tiles.heads * 8 * 4
    if role == "dkv":
        own = 4 * tiles.rows * kv_lanes * itemsize          # k, v, dk, dv
        other = tiles.major * (2 * q_lanes * itemsize + 2 * stat)
    else:
        n_own = 2 if role == "fwd" else 3                   # q, o | q, do, dq
        own = tiles.rows * (n_own * q_lanes * itemsize
                            + (1 if role == "fwd" else 2) * stat)
        other = 2 * tiles.major * kv_lanes * itemsize       # k, v
    return 2 * (own + other)


def tile_sizes(seq_q: int, seq_k: int, head_dim: int, num_heads: int,
               group: int, itemsize: int) -> Schedule:
    """The three kernels' tiles, from static shapes alone.

    rows = cols = 4 x head_dim, at most 512 (measured, PR 33, module
    docstring: 256 for heads of 64, where 512 x 512 computes 75% of the
    square for 62.5% and ran 7-9% slower; 512 for heads of 128 at S 4096,
    17% faster in the forward than 256). `heads` is the largest choice
    within `_MAX_HEAD_LANES` whose cell, holding the WHOLE other sequence,
    fits `_VMEM_BUDGET` in all three roles (resident: both benchmark cells'
    shapes do, and resident beat today's streamed grid at either, by 9-14%
    at S 4096); where no choice does, the smallest, and `major` halves
    until the cell fits (streamed). The rule is a byte count, never a
    model's name."""
    edge = min(512, max(_LANES, 4 * head_dim))
    roles = {"fwd": (seq_q, seq_k), "dq": (seq_q, seq_k),
             "dkv": (seq_k, seq_q)}

    def resident(heads):
        return {name: Tiles(heads, _divisor(own, edge), _divisor(other, edge),
                            other) for name, (own, other) in roles.items()}

    def fits(name, t):
        return block_bytes(t, name, head_dim, group, itemsize) <= _VMEM_BUDGET

    choices = _head_choices(num_heads, group, head_dim)
    narrow = [h for h in choices if h * head_dim <= _MAX_HEAD_LANES]
    for heads in reversed(narrow or choices[:1]):
        schedule = resident(heads)
        if all(fits(name, t) for name, t in schedule.items()):
            return Schedule(**schedule)
    schedule = resident(choices[0])
    for name, t in list(schedule.items()):
        while not fits(name, t) and t.major % (2 * t.cols) == 0:
            t = t._replace(major=t.major // 2)
        schedule[name] = t
    return Schedule(**schedule)


def _fit(tiles: Tiles, own: int, other: int, num_heads: int, group: int,
         head_dim: int) -> Tiles:
    """A caller's tiles (tests force a schedule on a small shape), made to
    divide the shapes: sizes clipped to the sequences, heads to a legal
    choice."""
    rows, major = min(tiles.rows, own), min(tiles.major, other)
    cols = min(tiles.cols, major)
    choices = _head_choices(num_heads, group, head_dim)
    heads = max([h for h in choices if h <= tiles.heads] or choices[:1])
    if own % rows or other % major or major % cols:
        raise ValueError(f"{tiles} does not divide sequences {own}, {other}")
    return Tiles(heads, rows, cols, major)


# ------------------------------------------------------------- cell helpers


class _Lanes(NamedTuple):
    """Where heads lie in a cell's lane blocks: `width` is the window one
    product contracts over, 128 lanes shared by 128 // D heads where both
    lane blocks are whole windows, else the head itself."""
    head_dim: int
    group: int
    width: int

    @classmethod
    def of(cls, head_dim, group, heads):
        shared = (head_dim < _LANES and _LANES % head_dim == 0
                  and heads * head_dim % _LANES == 0
                  and heads // group * head_dim % _LANES == 0)
        return cls(head_dim, group, _LANES if shared else head_dim)

    def window(self, head):
        """(first lane of the head's window in its block, offset in it)."""
        lane = head * self.head_dim
        return lane // self.width * self.width, lane % self.width

    def load(self, ref, rows, head):
        lo, _ = self.window(head)
        return ref[0, rows, lo:lo + self.width]

    def own(self, x, head, to_head=None):
        """x [n, width] with the other heads' lanes zeroed, and the head's
        moved to where `to_head` lies in ITS window (GQA under shared
        windows: a query head and its kv head at different offsets)."""
        if self.width == self.head_dim:
            return x
        off = self.window(head)[1]
        lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
        x = jnp.where((lane >= off) & (lane < off + self.head_dim), x,
                      jnp.zeros_like(x))
        to = off if to_head is None else self.window(to_head)[1]
        return x if to == off else pltpu.roll(x, (to - off) % self.width, 1)

    def store(self, ref, parts):
        """Write a cell's lane block from `parts` {head: [n, width] f32},
        each `own` at its head's lanes: a window is the sum of its heads."""
        by_window = {}
        for head, x in parts.items():
            lo = self.window(head)[0]
            by_window[lo] = x if lo not in by_window else by_window[lo] + x
        for lo, x in by_window.items():
            ref[0, :, lo:lo + self.width] = x.astype(ref.dtype)


def _dot_nt(a, b):
    """[m, c] x [n, c] -> [m, n] f32."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _dot(a, b):
    """[m, c] x [c, n] -> [m, n] f32."""
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _pipelined(n_units, *stages):
    """Every unit through `stages` (each `stage(unit, value of the stage
    before)`), EMITTED skewed: at a tick stand unit n + 1's first stage (its
    products, the MXU's work), unit n's second (the vector arithmetic), unit
    n - 1's third (the accumulating products). The scheduler keeps close to
    program order, and a unit's own chain (product, softmax, product) leaves
    the MXU idle while the vector units work and the other way round (my
    chip runs, PR 33, 12 heads of 64 a cell: the forward 6.8 ms a call in
    order, 5.6 skewed).
    Returns the last stage's values."""
    values = {}
    for tick in range(n_units + len(stages) - 1):
        for s, stage in enumerate(stages):
            if 0 <= tick - s < n_units:
                values[tick - s] = stage(tick - s, values.get(tick - s))
    return [values[n] for n in range(n_units)]


def _query_minus_key(keys, queries):
    """[keys, queries] int32: the lane's index less the sublane's."""
    return (jax.lax.broadcasted_iota(jnp.int32, (keys, queries), 1)
            - jax.lax.broadcasted_iota(jnp.int32, (keys, queries), 0))


def _exact_scale(sm_scale) -> bool:
    """Whether x * sm_scale is exact in a float dtype: a power of two (the
    exponent alone changes)."""
    return math.frexp(sm_scale)[0] == 0.5


def _prescaled(x, sm_scale):
    """The operand that carries the softmax scale, where that is exact."""
    return x * jnp.asarray(sm_scale, x.dtype) if _exact_scale(sm_scale) else x


def _scores(st, sm_scale, rel, diagonal=None):
    """A sub-block's f32 scores from the raw product: scaled here where no
    operand carried the scale, and, given `diagonal`, masked: an entry whose
    `rel` (its query's index less its key's, within the tile) lies under it
    has its key ahead of its query."""
    if not _exact_scale(sm_scale):
        st = st * sm_scale
    if diagonal is not None:
        st = jnp.where(rel >= diagonal, st, NEG_INF)
    return st


def _visible(own0, rows, other0, cols, n_sub, own_is_q):
    """Causal sub-block ranges of a cell whose own block starts at `own0`
    (rows long) over the `n_sub` sub-blocks of `cols` it holds from
    `other0`: (first, crossed_end). Queries own: sub-blocks [0, first) are
    wholly visible, [first, crossed_end) crossed by the diagonal. Keys own:
    [first, crossed_end) crossed, [crossed_end, n_sub) wholly visible.
    Positions align at 0 (`reference_attention`)."""
    clip = lambda x: jnp.clip(x, 0, n_sub)
    if own_is_q:   # key sub-block j wholly visible: its last key <= own0
        return (clip((own0 + 1 - other0) // cols),
                clip((own0 + rows - 1 - other0) // cols + 1))
    x = own0 - other0   # query sub-block i sees key own0 from i >= x // cols
    first = clip(x // cols)
    return first, jnp.maximum(first, clip(-(-(x + rows - 1) // cols)))


def _walk(step, carry, own0, rows, other0, cols, n_sub, causal, own_is_q):
    """The loop over what the cell holds: the sub-blocks the diagonal
    crosses with the mask, those wholly visible without."""
    plain = functools.partial(step, masked=False)
    if not causal:
        return jax.lax.fori_loop(0, n_sub, plain, carry)
    masked = functools.partial(step, masked=True)
    first, crossed = _visible(own0, rows, other0, cols, n_sub, own_is_q)
    if own_is_q:
        carry = jax.lax.fori_loop(0, first, plain, carry)
        return jax.lax.fori_loop(first, crossed, masked, carry)
    carry = jax.lax.fori_loop(first, crossed, masked, carry)
    return jax.lax.fori_loop(crossed, n_sub, plain, carry)


def _running(scratch, fresh):
    """A cell's running state, a tuple of values a unit: `fresh` where the
    cell holds all it walks (resident: no scratch), else what the cells
    before it on the fourth grid axis left in `scratch` (a ref a value, a
    unit a leading index), made fresh by the first of them."""
    if not scratch:
        return fresh

    @pl.when(pl.program_id(3) == 0)
    def _init():
        _keep(scratch, fresh)

    return tuple(tuple(ref[n] for ref in scratch) for n in range(len(fresh)))


def _keep(scratch, state):
    for n, unit in enumerate(state):
        for ref, x in zip(scratch, unit):
            ref[n] = x


def _finish(scratch, state, finalize):
    """Write the cell's outputs: now where it held all it walked, else
    after the last cell of the fourth grid axis, the state kept till then."""
    if not scratch:
        return finalize()
    _keep(scratch, state)
    pl.when(pl.program_id(3) == pl.num_programs(3) - 1)(finalize)


# ---------------------------------------------------------------- forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch, sm_scale,
                causal, tiles, lanes):
    heads, rows, cols, major = tiles
    qi, kj = pl.program_id(2), pl.program_id(3)
    q0, k_base = qi * rows, kj * major
    kv_of = lambda h: h // lanes.group
    qs = []
    for h in range(heads):
        q = lanes.own(lanes.load(q_ref, slice(None), h), h, kv_of(h))
        qs.append(_prescaled(q, sm_scale))
    rel = _query_minus_key(cols, rows)

    def step(j, carry, masked):
        at = pl.ds(pl.multiple_of(j * cols, cols), cols)

        def scores(h, _):                             # [cols, rows] f32
            return _dot_nt(lanes.load(k_ref, at, kv_of(h)), qs[h])

        def softmax(h, st):
            m, l, _ = carry[h]
            # query q0 + lane sees key k_base + j cols + row
            st = _scores(st, sm_scale, rel,
                         k_base + j * cols - q0 if masked else None)
            m_new = jnp.maximum(m, jnp.max(st, axis=0, keepdims=True))
            pt = jnp.exp(st - m_new)
            alpha = jnp.exp(m - m_new)
            return (m_new, alpha * l + jnp.sum(pt, axis=0, keepdims=True),
                    alpha, pt.astype(v_ref.dtype))

        def weighted(h, now):
            m, l, alpha, pt = now
            vt = lanes.load(v_ref, at, kv_of(h)).T            # [W, cols]
            return m, l, carry[h][2] * alpha + _dot(vt, pt)

        return tuple(_pipelined(heads, scores, softmax, weighted))

    carry = _running(scratch, tuple(
        (jnp.full((1, rows), NEG_INF, jnp.float32),
         jnp.zeros((1, rows), jnp.float32),
         jnp.zeros((lanes.width, rows), jnp.float32)) for _ in range(heads)))
    carry = _walk(step, carry, q0, rows, k_base, cols, major // cols, causal,
                  True)

    def finalize():
        parts = {}
        for h, (m, l, acc) in enumerate(carry):
            l_safe = jnp.where(l == 0.0, 1.0, l)
            parts[h] = lanes.own((acc / l_safe).T, kv_of(h), h)
            lse_ref[0, h] = m + jnp.log(l_safe)
        lanes.store(o_ref, parts)

    _finish(scratch, carry, finalize)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _flat(x):
    """[B, S, H, D] viewed [B, S, H*D]: no copy."""
    return x.reshape(x.shape[0], x.shape[1], -1)


def _query_cell_specs(tiles, head_dim, group, n_major, causal):
    """Block specs of a cell that owns queries (forward, dQ): its own rows
    of q-shaped operands, the keys it holds, a row of lse-shaped ones. A
    cell past the last block of keys its causal queries need asks for that
    block again, which fetches nothing."""
    heads, rows, _, major = tiles

    def held(b, h, qi, kj):
        if causal:
            kj = jnp.minimum(kj, (qi * rows + rows - 1) // major)
        return b, jnp.minimum(kj, n_major - 1), h

    return (pl.BlockSpec((1, rows, heads * head_dim),
                         lambda b, h, qi, kj: (b, qi, h)),
            pl.BlockSpec((1, major, heads // group * head_dim), held),
            pl.BlockSpec((1, heads, 1, rows),
                         lambda b, h, qi, kj: (b, h, 0, qi)))


def _flash_fwd(q, k, v, sm_scale, causal, tiles, interpret):
    """q [B, Sq, H, D], k/v [B, Sk, Hkv, D] -> (o [B, Sq, H, D],
    lse [B, H, 1, Sq] f32)."""
    batch, seq_q, num_heads, head_dim = q.shape
    _, seq_k, num_kv_heads, _ = k.shape
    group = num_heads // num_kv_heads
    heads, rows, _, major = tiles
    lanes = _Lanes.of(head_dim, group, heads)
    n_major = seq_k // major
    q_spec, kv_spec, row_spec = _query_cell_specs(tiles, head_dim, group,
                                                  n_major, causal)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          tiles=tiles, lanes=lanes),
        grid=(batch, num_heads // heads, seq_q // rows, n_major),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[
            jax.ShapeDtypeStruct((batch, seq_q, num_heads * head_dim),
                                 q.dtype),
            jax.ShapeDtypeStruct((batch, num_heads, 1, seq_q), jnp.float32),
        ],
        scratch_shapes=[] if n_major == 1 else [
            pltpu.VMEM((heads, 1, rows), jnp.float32),
            pltpu.VMEM((heads, 1, rows), jnp.float32),
            pltpu.VMEM((heads, lanes.width, rows), jnp.float32),
        ],
        compiler_params=_params(),
        name="flash_attention_fwd",
        interpret=interpret,
    )(_flat(q), _flat(k), _flat(v))
    return out.reshape(q.shape), lse


# ---------------------------------------------------------------- backward


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               *scratch, sm_scale, causal, tiles, lanes):
    heads, rows, cols, major = tiles
    qi, kj = pl.program_id(2), pl.program_id(3)
    q0, k_base = qi * rows, kj * major
    kv_of = lambda h: h // lanes.group
    qs, dos = [], []
    for h in range(heads):
        q = lanes.own(lanes.load(q_ref, slice(None), h), h, kv_of(h))
        qs.append(_prescaled(q, sm_scale))
        dos.append(lanes.own(lanes.load(do_ref, slice(None), h), h, kv_of(h)))
    rel = _query_minus_key(cols, rows)

    def step(j, carry, masked):
        at = pl.ds(pl.multiple_of(j * cols, cols), cols)

        def products(h, _):                           # [cols, rows] f32 x 2
            return (_dot_nt(lanes.load(k_ref, at, kv_of(h)), qs[h]),
                    _dot_nt(lanes.load(v_ref, at, kv_of(h)), dos[h]))

        def dscores(h, now):
            st, dpt = now
            st = _scores(st, sm_scale, rel,
                         k_base + j * cols - q0 if masked else None)
            pt = jnp.exp(st - lse_ref[0, h])   # masked entries underflow to 0
            return (pt * (dpt - delta_ref[0, h])).astype(k_ref.dtype)

        def weighted(h, dst):
            kt = lanes.load(k_ref, at, kv_of(h)).T            # [W, cols]
            return (carry[h][0] + _dot(kt, dst),)

        return tuple(_pipelined(heads, products, dscores, weighted))

    carry = _running(scratch, tuple(
        (jnp.zeros((lanes.width, rows), jnp.float32),) for _ in range(heads)))
    carry = _walk(step, carry, q0, rows, k_base, cols, major // cols, causal,
                  True)

    def finalize():
        lanes.store(dq_ref, {h: lanes.own((acc * sm_scale).T, kv_of(h), h)
                             for h, (acc,) in enumerate(carry)})

    _finish(scratch, carry, finalize)


def _dkv_slots(lanes, heads):
    """One accumulator pair for the query heads that share a kv head AND
    its place in the window (a whole GQA group where a head is a window):
    (slot of each head, number of slots)."""
    keys = [(h // lanes.group, lanes.window(h)[1]) for h in range(heads)]
    return [sorted(set(keys)).index(key) for key in keys], len(set(keys))


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *scratch, sm_scale, causal, tiles, lanes):
    """The mirror image: the cell's own block is keys, the sublanes of
    S^T; a loop trip takes a sub-block of queries."""
    heads, rows, cols, major = tiles
    ki, qj = pl.program_id(2), pl.program_id(3)
    k0, q_base = ki * rows, qj * major
    kv_of = lambda h: h // lanes.group
    slot_of, n_slots = _dkv_slots(lanes, heads)
    ks, vs = {}, {}
    for h in range(heads):
        if slot_of[h] not in ks:
            k = lanes.own(lanes.load(k_ref, slice(None), kv_of(h)),
                          kv_of(h), h)
            ks[slot_of[h]] = _prescaled(k, sm_scale)
            vs[slot_of[h]] = lanes.own(
                lanes.load(v_ref, slice(None), kv_of(h)), kv_of(h), h)
    rel = _query_minus_key(rows, cols)

    def step(i, carry, masked):
        at = pl.ds(pl.multiple_of(i * cols, cols), cols)
        carry = list(carry)

        def products(h, _):                           # [rows, cols] f32 x 2
            q, do = lanes.load(q_ref, at, h), lanes.load(do_ref, at, h)
            return (_dot_nt(ks[slot_of[h]], q), _dot_nt(vs[slot_of[h]], do),
                    q, do)

        def dscores(h, now):
            st, dpt, q, do = now
            # query q_base + i cols + lane sees key k0 + row
            st = _scores(st, sm_scale, rel,
                         k0 - q_base - i * cols if masked else None)
            pt = jnp.exp(st - lse_ref[0, h, :, at])   # lse a row [1, cols]
            dst = pt * (dpt - delta_ref[0, h, :, at])
            return dst.astype(q.dtype), pt.astype(do.dtype), q, do

        def weighted(h, now):
            dst, pt, q, do = now
            dk, dv = carry[slot_of[h]]
            carry[slot_of[h]] = (dk + _dot(dst, q), dv + _dot(pt, do))

        _pipelined(heads, products, dscores, weighted)
        return tuple(carry)

    zero = jnp.zeros((rows, lanes.width), jnp.float32)
    carry = _running(scratch, tuple((zero, zero) for _ in range(n_slots)))
    carry = _walk(step, carry, k0, rows, q_base, cols, major // cols, causal,
                  False)

    def finalize():
        dks, dvs = {}, {}
        for slot in range(n_slots):
            h = slot_of.index(slot)   # a slot's sums lie at ITS heads' lanes
            kv = kv_of(h)
            dk, dv = (lanes.own(x, h, kv) for x in carry[slot])
            dks[kv] = dk * sm_scale + dks.get(kv, 0.0)
            dvs[kv] = dv + dvs.get(kv, 0.0)
        lanes.store(dk_ref, dks)
        lanes.store(dv_ref, dvs)

    _finish(scratch, carry, finalize)


def _flash_bwd(q, k, v, o, lse, do, sm_scale, causal, dq_tiles, dkv_tiles,
               interpret):
    """Seq-major grads: q [B, Sq, H, D], k/v [B, Sk, Hkv, D] ->
    (dq, dk, dv)."""
    shape_q, shape_kv = q.shape, k.shape
    batch, seq_q, num_heads, head_dim = q.shape
    _, seq_k, num_kv_heads, _ = k.shape
    group = num_heads // num_kv_heads
    # D_i = rowsum(dO * O): cheap elementwise, XLA fuses it
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)[:, :, None, :]
    q, k, v, do = _flat(q), _flat(k), _flat(v), _flat(do)

    heads, rows, _, major = dq_tiles
    lanes = _Lanes.of(head_dim, group, heads)
    n_major = seq_k // major
    q_spec, kv_spec, row_spec = _query_cell_specs(dq_tiles, head_dim, group,
                                                  n_major, causal)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, causal=causal,
                          tiles=dq_tiles, lanes=lanes),
        grid=(batch, num_heads // heads, seq_q // rows, n_major),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[] if n_major == 1 else [
            pltpu.VMEM((heads, lanes.width, rows), jnp.float32)],
        compiler_params=_params(),
        name="flash_attention_bwd_dq",
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dK/dV: the mirror image, the cell's own block is keys
    heads, rows, _, major = dkv_tiles
    lanes = _Lanes.of(head_dim, group, heads)
    n_major = seq_q // major

    def held(ki, qj):
        """A cell before the first block of queries its causal keys need
        asks for that block already."""
        if causal:
            qj = jnp.maximum(qj, jnp.minimum(ki * rows // major, n_major - 1))
        return qj

    q_spec = pl.BlockSpec((1, major, heads * head_dim),
                          lambda b, h, ki, qj: (b, held(ki, qj), h))
    kv_spec = pl.BlockSpec((1, rows, heads // group * head_dim),
                           lambda b, h, ki, qj: (b, ki, h))
    row_spec = pl.BlockSpec((1, heads, 1, major),
                            lambda b, h, ki, qj: (b, h, 0, held(ki, qj)))
    n_slots = _dkv_slots(lanes, heads)[1]
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          tiles=dkv_tiles, lanes=lanes),
        grid=(batch, num_heads // heads, seq_k // rows, n_major),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[] if n_major == 1 else [
            pltpu.VMEM((n_slots, rows, lanes.width), jnp.float32),
            pltpu.VMEM((n_slots, rows, lanes.width), jnp.float32)],
        compiler_params=_params(),
        name="flash_attention_bwd_dkv",
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq.reshape(shape_q), dk.reshape(shape_kv), dv.reshape(shape_kv)


# ---------------------------------------------------------------- reference


def reference_attention(q, k, v, sm_scale=None, causal=True, bias=None):
    """XLA reference: [B, S, H, D] x [B, S, Hkv, D] GQA attention, f32 softmax."""
    batch, seq_q, num_heads, head_dim = q.shape
    _, seq_k, num_kv_heads, _ = k.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(head_dim)
    group = num_heads // num_kv_heads
    qg = q.reshape(batch, seq_q, num_kv_heads, group, head_dim)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if bias is not None:
        s = s + bias
    if causal:
        qpos = jnp.arange(seq_q)[:, None]
        kpos = jnp.arange(seq_k)[None, :]
        s = jnp.where(qpos >= kpos, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(batch, seq_q, num_heads, head_dim).astype(q.dtype)


# ---------------------------------------------------------------- public op


def _schedule(q, k, tiles) -> Schedule:
    _, seq_q, num_heads, head_dim = q.shape
    _, seq_k, num_kv_heads, _ = k.shape
    group = num_heads // num_kv_heads
    if tiles is None:
        return tile_sizes(seq_q, seq_k, head_dim, num_heads, group,
                          q.dtype.itemsize)
    own_q = _fit(tiles, seq_q, seq_k, num_heads, group, head_dim)
    return Schedule(own_q, own_q,
                    _fit(tiles, seq_k, seq_q, num_heads, group, head_dim))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, sm_scale=None, causal=True, tiles=None):
    """q [B, Sq, H, D], k/v [B, Sk, Hkv, D] -> [B, Sq, H, D]. `tiles`
    (a `Tiles`) forces one schedule on all three kernels, for tests on
    small shapes; the models pass none and `tile_sizes` decides."""
    out, _ = _fwd_rule(q, k, v, sm_scale, causal, tiles)
    return out


def _fwd_rule(q, k, v, sm_scale, causal, tiles):
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    out, lse = _flash_fwd(q, k, v, sm_scale, causal,
                          _schedule(q, k, tiles).fwd, should_interpret())
    # the residuals are the NAMED values: a recompute that finds both kept
    # has no use left for the call
    out, lse = checkpoint_name(out, FLASH_OUT), checkpoint_name(lse, FLASH_LSE)
    return out, (q, k, v, out, lse)


def _bwd_rule(sm_scale, causal, tiles, res, g):
    q, k, v, out, lse = res
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    schedule = _schedule(q, k, tiles)
    return _flash_bwd(q, k, v, out, lse, g, sm_scale, causal, schedule.dq,
                      schedule.dkv, should_interpret())


flash_attention.defvjp(_fwd_rule, _bwd_rule)
