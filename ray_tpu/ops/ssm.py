"""Mamba-2's state-space mixer (SSD): a selective scan on a fixed float32
state a head instead of a K/V cache, behind a short causal convolution.

    S_t = exp(dt_t A_h) S_(t-1) + dt_t x_t (x) B_t     y_t = S_t C_t + D_h x_t

with ``x_t`` [P] a head's values, ``B_t`` and ``C_t`` [N] shared by the heads
of a GROUP (head ``h`` reads group ``h // (H / G)``), ``dt_t > 0`` a step a
head and token (after its softplus), ``A_h < 0`` and ``D_h`` one scalar a
head. The decay is a scalar a head and TOKEN (input-dependent), which is
what sets this apart from ``ops/linear_attention.py``'s one decay a head.

Two states a sequence (``state_shapes``), both float32:

  ``conv``  the convolution's last ``conv - 1`` INPUTS (the joined x, B, C
            before the convolution), ``[rows, conv - 1, width]``
  ``ssm``   ``S`` transposed and a group's heads joined on the lanes,
            ``[rows, G, N, (H / G) P]``: a group's block is whole 128-lane
            rows (N x 512 at the published sizes) and the row vectors a
            token brings (its x, its decay) lie along the lanes as they
            come, with no transposition but the MXU's.

Two scans, one recurrence, each as a Pallas kernel under a name a trace
shows (``ssm_chunk_scan``, ``ssm_step``; interpreted off a TPU) and in a
``jax.numpy`` form that runs only when named (``impl='reference'``: what
the kernels are tested against):

  * ``ssd_chunk`` — ``S`` tokens in blocks of ``chunk`` (128): inside a
    block the attention form ``((C B^T) * L) (dt x)`` with ``L_ij =
    exp(sum of dt A over j < t <= i)`` for ``j <= i``, across blocks ``exp(.)
    C S_in`` and ``S_out = decay S_in + (B * w)^T (dt x)``; the state rides
    from block to block in float32 (in VMEM in the kernel, so a chunk reads
    and writes it once whatever its length). ``real_len`` tokens are real
    and the rest trailing padding, which neither decays the state nor adds
    to it.
  * ``ssd_step`` — one token a row: the state is read and written once, in
    place; a row that is not ``active`` gets its state back bitwise.

``causal_conv`` is the convolution in front, with the same two contracts:
the inputs it carries are the last REAL tokens', and a step's idle rows keep
theirs bitwise.
"""

from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops._pallas import should_interpret

_LANES = 128
_STEP_GROUPS = 4   # groups of one grid cell of the step kernel
SSM_IMPLS = ("reference", "pallas")
CHUNK_KERNEL, STEP_KERNEL = "ssm_chunk_scan", "ssm_step"


class SsmSizes(NamedTuple):
    heads: int      # H (a source's mamba_num_heads)
    head_dim: int   # P (mamba_head_dim)
    groups: int     # G (n_groups): B and C are shared by H / G heads
    state: int      # N (ssm_state_size)
    conv: int       # taps of the causal convolution (conv_kernel)
    chunk: int      # tokens of one block of the chunked scan (chunk_size)

    @property
    def inner(self) -> int:
        return self.heads * self.head_dim

    @property
    def conv_width(self) -> int:
        """The channels the convolution runs over: x, B and C joined."""
        return self.inner + 2 * self.groups * self.state

    @property
    def group_lanes(self) -> int:
        """A group's heads' values joined: the lanes of its state."""
        return self.inner // self.groups


def state_shapes(rows: int, sizes: SsmSizes) -> Dict[str, tuple]:
    """The two float32 states of ``rows`` sequences (module docstring)."""
    return {"conv": (rows, sizes.conv - 1, sizes.conv_width),
            "ssm": (rows, sizes.groups, sizes.state, sizes.group_lanes)}


def use_kernel(sizes: SsmSizes, impl: Optional[str] = None) -> bool:
    """The Pallas kernels run unless ``impl`` is 'reference' (the
    ``jax.numpy`` form, by name only: tests and ``attn_impl='reference'``);
    off a TPU they are interpreted (``ops/_pallas.py``), never swapped. A
    kernel works a tile of ``min(128, group's lanes)`` lanes at a time, so
    a group's lanes are whole tiles and a head lies within a tile or is
    whole tiles; compiled for the chip the tile and the state are whole
    128-lane rows. Other sizes are refused here."""
    if impl == "reference":
        return False
    if impl not in (None, "pallas"):
        raise ValueError(f"unknown ssm impl {impl!r}; expected one of "
                         f"{list(SSM_IMPLS)}")
    Q, P, N = sizes.group_lanes, sizes.head_dim, sizes.state
    tile = min(_LANES, Q)
    fits = Q % tile == 0 and (tile % P == 0 or P % tile == 0)
    if not should_interpret():
        fits = fits and tile == _LANES and N % _LANES == 0
    if not fits:
        raise ValueError(
            f"the state-space kernels do not take {sizes}: a group's "
            f"{Q} lanes and the state of {N} must be whole rows of "
            f"{_LANES} lanes and a head of {P} lie within a row or be "
            f"whole rows")
    return True


# ------------------------------------------------------------ convolution


def causal_conv(x, carried, weight, bias, *, real_len=None, active=None):
    """The depthwise causal convolution and its silu: token ``t`` sees its
    own input and the ``K - 1`` before it. x: [B, S, W]; carried: [B, K - 1,
    W] float32, the inputs of the K - 1 tokens before the first; weight: [K,
    W] (tap ``K - 1`` is the token's own); bias: [W]. Returns (silu(conv) [B,
    S, W] float32, carried after it): after the ``real_len`` real tokens of a
    chunk (a scalar; default all S), and for a step (S == 1) with ``active``
    [B] a row at 0 keeps its own bitwise."""
    K = weight.shape[0]
    S = x.shape[1]
    w = weight.astype(jnp.float32)
    seen = jnp.concatenate([carried, x.astype(jnp.float32)], axis=1)
    out = sum(w[k] * seen[:, k:k + S] for k in range(K))
    out = jax.nn.silu(out + bias.astype(jnp.float32))
    if S == 1:
        new = seen[:, 1:]
        if active is not None:
            new = jnp.where((active > 0)[:, None, None], new, carried)
        return out, new
    # row ``r`` of ``seen`` is token ``r - (K - 1)``: the last K - 1 real
    # tokens are rows real_len .. real_len + K - 2
    start = S if real_len is None else real_len
    return out, lax.dynamic_slice_in_dim(seen, start, K - 1, axis=1)


# ------------------------------------------------------------------- scan


def _block_sums(dt, a, real_len, block: int):
    """What both forms of the chunked scan start from. dt: [B, S, H] float32
    (S whole blocks), a: [H], real_len a scalar. Returns (dt with the
    padding's zeroed, cs [B, S, H]: the running sum of ``dt a`` inside each
    block, up to and with the token; tot [B, nb, H]: a block's whole sum)."""
    B, S, H = dt.shape
    real = (jnp.arange(S) < real_len)[None, :, None]
    dt = jnp.where(real, dt, 0.0)
    la = (dt * a).reshape(B, S // block, block, H)
    cs = jnp.cumsum(la, axis=2)
    return dt, cs.reshape(B, S, H), cs[:, :, -1]


def _chunk_reference(xdt, Bm, Cm, cs, tot, state, sizes: SsmSizes,
                     block: int):
    """The chunked scan in ``jax.numpy``: xdt [B, S, H, P] (``dt x``, the
    padding's zero), Bm, Cm [B, S, G, N], cs [B, S, H], tot [B, nb, H],
    state [B, G, N, Q] float32 -> (y [B, S, H, P] float32, state)."""
    B, S, H, P = xdt.shape
    G, N = sizes.groups, sizes.state
    E = H // G
    nb = S // block
    f32 = jnp.float32
    blocks = lambda a, *rest: jnp.moveaxis(
        a.reshape(B, nb, block, *rest), 1, 0)
    causal = jnp.tril(jnp.ones((block, block), bool))

    def one(s, args):
        x, b, c, cs, tot = args   # x [B, L, G, E, P]; cs [B, L, G, E]
        s = s.reshape(B, G, N, E, P)
        scores = jnp.einsum("bign,bjgn->bgij", c, b,
                            preferred_element_type=f32)
        # log-decay from behind token j to behind token i, j <= i
        between = cs[:, :, None] - cs[:, None, :]       # [B, i, j, G, E]
        decay = jnp.where(causal[None, :, :, None, None],
                          jnp.exp(jnp.minimum(between, 0.0)), 0.0)
        weights = (scores[..., None] * jnp.moveaxis(decay, 3, 1)
                   ).astype(x.dtype)                    # [B, G, i, j, E]
        y = jnp.einsum("bgije,bjgep->bigep", weights, x,
                       preferred_element_type=f32)
        y += jnp.exp(cs)[..., None] * jnp.einsum(
            "bign,bgnep->bigep", c.astype(f32), s)
        left = jnp.exp(tot[:, None] - cs)               # [B, L, G, E]
        s = (jnp.exp(tot)[:, :, None, :, None] * s
             + jnp.einsum("bjgn,bjgep->bgnep", b.astype(f32),
                          x.astype(f32) * left[..., None]))
        return s.reshape(B, G, N, E * P), y

    state, ys = lax.scan(one, state, (
        blocks(xdt, G, E, P), blocks(Bm, G, N), blocks(Cm, G, N),
        blocks(cs, G, E), tot.reshape(B, nb, G, E).swapaxes(0, 1)))
    return jnp.moveaxis(ys, 0, 1).reshape(B, S, H, P), state


def _chunk_kernel(tot_ref, x_ref, b_ref, c_ref, col_ref, row_ref, s_in_ref,
                  y_ref, s_out_ref, s_scr, *, heads, head_dim, blocks):
    """One (row, group, block): module docstring. x [L, Q] (``dt x``), B and
    C [L, N], col [E, L, 1] and row [E, L] the running log-decay of the
    group's E heads as columns and as rows, tot (SMEM, flat [B H nb]) each
    head's sum over the block."""
    b, g, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    f32 = jnp.float32
    E, P = row_ref.shape[0], head_dim

    @pl.when(c == 0)
    def _():
        s_scr[...] = s_in_ref[...]

    bm, cm = b_ref[...], c_ref[...]
    L = bm.shape[0]
    scores = lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=f32)          # [L, L]
    causal = (lax.broadcasted_iota(jnp.int32, (1, L), 1)
              <= lax.broadcasted_iota(jnp.int32, (L, 1), 0))
    state = s_scr[...]                                            # [N, Q]
    from_state = jnp.dot(cm.astype(f32), state, preferred_element_type=f32)
    base = ((b * heads + g * E) * blocks + c)
    T = min(_LANES, state.shape[1])  # the tile of lanes (``use_kernel``)
    for t in range(state.shape[1] // T):
        lanes = slice(t * T, (t + 1) * T)
        head_of = (t * T + lax.broadcasted_iota(jnp.int32, (1, T), 1)) // P
        x = x_ref[:, lanes]
        y = jnp.zeros((L, T), f32)
        behind = jnp.zeros((L, T), f32)  # exp(cs): decay since the start
        left = jnp.zeros((L, T), f32)    # exp(tot - cs): to the end
        whole = jnp.zeros((1, T), f32)   # exp(tot)
        for e in range(t * T // P, ((t + 1) * T - 1) // P + 1):
            col, row = col_ref[e], row_ref[pl.ds(e, 1), :]
            total = tot_ref[base + e * blocks]
            decay = jnp.where(causal, jnp.exp(jnp.minimum(col - row, 0.0)),
                              0.0)
            mine = head_of == e
            y = jnp.where(mine, jnp.dot((scores * decay).astype(x.dtype), x,
                                        preferred_element_type=f32), y)
            behind = jnp.where(mine, jnp.exp(col), behind)
            left = jnp.where(mine, jnp.exp(total - col), left)
            whole = jnp.where(mine, jnp.exp(total), whole)
        y_ref[:, lanes] = (y + behind * from_state[:, lanes]).astype(
            y_ref.dtype)
        s_scr[:, lanes] = whole * state[:, lanes] + lax.dot_general(
            bm.astype(f32), x.astype(f32) * left, (((0,), (0,)), ((), ())),
            preferred_element_type=f32)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        s_out_ref[...] = s_scr[...]


@functools.partial(jax.jit, static_argnames=("sizes", "block", "interpret"))
def _chunk_pallas(xdt, Bm, Cm, cs, tot, state, sizes, block, interpret):
    B, S, H, P = xdt.shape
    G, N, Q = sizes.groups, sizes.state, sizes.group_lanes
    E, nb = H // G, S // block
    by_head = cs.transpose(0, 2, 1)                              # [B, H, S]
    tokens = lambda width: pl.BlockSpec(
        (None, block, width), lambda b, g, c, *_: (b, c, g))
    whole = pl.BlockSpec((None, None, N, Q), lambda b, g, c, *_: (b, g, 0, 0))
    y, new_state = pl.pallas_call(
        functools.partial(_chunk_kernel, heads=H, head_dim=P, blocks=nb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, G, nb),
            in_specs=[tokens(Q), tokens(N), tokens(N),
                      pl.BlockSpec((None, E, block, 1),
                                   lambda b, g, c, *_: (b, g, c, 0)),
                      pl.BlockSpec((None, None, E, block),
                                   lambda b, g, c, *_: (b, g, 0, c)),
                      whole],
            out_specs=[tokens(Q), whole],
            scratch_shapes=[pltpu.VMEM((N, Q), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((B, S, H * P), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name=CHUNK_KERNEL, interpret=interpret,
    )(tot.transpose(0, 2, 1).reshape(-1), xdt.reshape(B, S, H * P),
      Bm.reshape(B, S, G * N), Cm.reshape(B, S, G * N),
      by_head[..., None], by_head.reshape(B, G, E, S), state)
    return y.reshape(B, S, H, P), new_state


def ssd_chunk(x, dt, a, Bm, Cm, d, state, sizes: SsmSizes, real_len=None,
              impl: Optional[str] = None):
    """``S`` tokens a row, the first ``real_len`` (a scalar; default all)
    real. x: [B, S, H, P]; dt: [B, S, H] float32, the steps after their
    softplus; a, d: [H] float32; Bm, Cm: [B, S, G, N]; state: [B, G, N, Q]
    float32. Returns (y [B, S, H, P] float32, ``D x`` in it, state after
    real_len tokens); the outputs of the padding mean nothing."""
    B, S, H, P = x.shape
    block = min(sizes.chunk, -(-S // 8) * 8)
    pad = -S % block
    padded = lambda t: jnp.pad(
        t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
    dt, cs, tot = _block_sums(padded(dt.astype(jnp.float32)),
                              a.astype(jnp.float32),
                              S if real_len is None else real_len, block)
    xdt = (padded(x).astype(jnp.float32) * dt[..., None]).astype(x.dtype)
    args = (xdt, padded(Bm), padded(Cm), cs, tot, state)
    if use_kernel(sizes, impl):
        y, state = _chunk_pallas(*args, sizes, block, should_interpret())
    else:
        y, state = _chunk_reference(*args, sizes, block)
    return y[:, :S] + d[:, None] * x.astype(jnp.float32), state


def _step_reference(decay, xdt, Bm, Cm, state, live):
    """decay, xdt: [B, G, Q] (a head's decay on each of its lanes); Bm, Cm:
    [B, G, N] float32; state [B, G, N, Q] -> (y [B, G, Q], state)."""
    new = (decay[:, :, None] * state
           + Bm[..., None] * xdt[:, :, None])
    y = jnp.einsum("bgn,bgnq->bgq", Cm, new)
    return y, jnp.where(live[:, None, None, None], new, state)


def _step_kernel(active_ref, decay_ref, x_ref, b_ref, c_ref, s_ref,
                 y_ref, so_ref, *, groups):
    live = active_ref[pl.program_id(0)] > 0
    first = lax.broadcasted_iota(jnp.int32, (8, 1), 0) == 0
    rows8 = lambda row: jnp.where(first, row, 0.0)  # [1, n] -> [8, n]
    for g in range(groups):
        s = s_ref[0, g]                                         # [N, Q]
        # x (x) B on the MXU: eight rows of which one is not zero
        new = decay_ref[0, g] * s + lax.dot_general(
            rows8(b_ref[0, g]), rows8(x_ref[0, g]),
            (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        y_ref[0, g] = jnp.dot(rows8(c_ref[0, g]), new,
                              preferred_element_type=jnp.float32)[:1]
        so_ref[0, g] = jnp.where(live, new, s)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _step_pallas(decay, xdt, Bm, Cm, state, active, interpret):
    B, G, N, Q = state.shape
    groups = next(n for n in range(min(_STEP_GROUPS, G), 0, -1)
                  if G % n == 0)
    cell = lambda b, g, *_: (b, g, 0, 0)
    row = lambda width: pl.BlockSpec((1, groups, 1, width), cell)
    whole = pl.BlockSpec((1, groups, N, Q), cell)
    y, new_state = pl.pallas_call(
        functools.partial(_step_kernel, groups=groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, G // groups),
            in_specs=[row(Q), row(Q), row(N), row(N), whole],
            out_specs=[row(Q), whole]),
        out_shape=[jax.ShapeDtypeStruct((B, G, 1, Q), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=(8 * groups * N * Q * 4) + (8 << 20)),
        name=STEP_KERNEL, interpret=interpret,
    )(active.astype(jnp.int32), decay[:, :, None], xdt[:, :, None],
      Bm[:, :, None], Cm[:, :, None], state)
    return y[:, :, 0], new_state


def ssd_step(x, dt, a, Bm, Cm, d, state, active, sizes: SsmSizes,
             impl: Optional[str] = None):
    """One token a row. x: [B, H, P]; dt: [B, H] float32 (after softplus);
    a, d: [H]; Bm, Cm: [B, G, N]; state: [B, G, N, Q] float32; active: [B]
    (a row at 0 keeps its state bitwise; its output means nothing). Returns
    (y [B, H, P] float32, state)."""
    B, H, P = x.shape
    G, Q = sizes.groups, sizes.group_lanes
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    lanes = lambda t: jnp.repeat(t, P, axis=-1).reshape(B, G, Q)
    decay = lanes(jnp.exp(dt * a.astype(f32)))
    xdt = (x * dt[..., None]).reshape(B, G, Q)
    args = (decay, xdt, Bm.astype(f32), Cm.astype(f32), state)
    if use_kernel(sizes, impl):
        y, state = _step_pallas(*args, active, should_interpret())
    else:
        y, state = _step_reference(*args, active > 0)
    return y.reshape(B, H, P) + d[:, None] * x, state
