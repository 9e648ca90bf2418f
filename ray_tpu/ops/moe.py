"""Mixture-of-Experts layer: dropless top-k routing over grouped matmuls.

One routing for training and serving (the reference has no MoE at all —
SURVEY §5): the router scores every row in float32 by one of two rules
(``SCORINGS``: a softmax over the experts, OLMoE / Mixtral style, or a
sigmoid an expert with a bias that corrects the CHOICE and not the weight,
DeepSeek-V3 style), each row takes its top-k experts, the (row, expert)
pairs are sorted by expert and the three SwiGLU matmuls run as GROUPED
matmuls over the ragged per-expert groups, then the pairs are un-sorted and
summed under the router's weights; a SHARED expert, where the layer has one,
is a dense SwiGLU every row takes beside its routed sum. No capacity, so no
row is ever dropped, and nothing of size [rows, experts, capacity] exists.

Which call runs which grouped matmul (two paths below the sort, because
the needs conflict; the input says which, no option does):

  * a call WITHOUT ``layer`` holds one layer's experts (training, under
    ``scan``): three ``jax.lax.ragged_dot``, which has a backward and which
    GSPMD partitions over ``ep``;
  * a call that passes the STACK of every layer's experts and a ``layer``
    index (the paged serving programs and ``decode_step``, whose layers are
    a Python loop) runs ``expert_mlp``: the Pallas kernel
    ``moe_grouped_matmul`` (that name in a trace; interpreted off a TPU).
    One call a layer does all three products. Its grid walks the VISITS
    (expert, row tile) in the order of the sorted pairs, a map computed from
    the counts and handed in by scalar prefetch; the ``BlockSpec``s index
    the stacked weights ``[layers x experts, ...]`` in place, so no layer is
    sliced off the stack (805 MB copied a call at OLMoE's widths), an expert
    that received rows is read from HBM exactly once (consecutive row tiles
    of one expert keep its weights in VMEM, the pipeline fetches the next
    visit's while this one's are multiplied; once a visit where an expert
    is too wide for VMEM and its hidden width is walked in column tiles) and
    an expert without rows is never fetched. Gate and up read the row tile
    once, ``silu(gate) * up`` stays in VMEM and is rounded once, then down;
    operands in the model's dtype, float32 accumulation. Row tiles follow
    the STATIC pair count (``tile_sizes``). No backward: nothing trains on
    this path.

Rows that are not live (a slot without a sequence, a chunk's padding) are
marked by ``valid``: they sort behind every group, cost no expert a row,
are not counted and come out as zeros.

Expert parallelism: the expert weights carry the logical axis ``expert``
(``ep`` on a mesh); under plain jit GSPMD partitions or gathers as it sees
fit. How experts exchange rows across chips is not decided here.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops._pallas import should_interpret


SOFTMAX, SIGMOID = "softmax", "sigmoid"
SCORINGS = (SOFTMAX, SIGMOID)
# an expert's form: ``W_down (silu(x W_gate) * (x W_up))``, three matrices,
# or ``W_down relu(x W_up)^2``, two
SWIGLU, RELU2 = "swiglu", "relu2"
ACTIVATIONS = (SWIGLU, RELU2)
# what a seeded choice bias is drawn with: sigmoid scores of seeded weights
# lie tenths apart, so a bias of this spread moves a measurable share of the
# rows' top-k (a bias of zeros could be dropped and no test would tell)
_BIAS_STD = 0.1


def init_moe_params(key, embed_dim: int, hidden_dim: int, num_experts: int,
                    param_dtype=jnp.float32, *, choice_bias: bool = False,
                    shared_dim: int = 0, activation: str = SWIGLU,
                    held: Optional[int] = None) -> Dict[str, Any]:
    """SwiGLU experts: router [d,E] + per-expert gate/up [E,d,f], down
    [E,f,d]. ``choice_bias``: ``e_bias`` [E] float32 beside them, what a
    sigmoid router adds to its scores to CHOOSE (never to weigh).
    ``shared_dim``: the width of the shared expert (its experts' widths
    joined; 0: none), ``ws_gate``/``ws_up`` [d,fs] and ``ws_down`` [fs,d].
    ``activation`` 'relu2': no gate, the experts' nor the shared one's, and
    the experts' up-projection is kept HIDDEN-MAJOR, ``w_up_t`` [E,f,d]: a
    width that is no whole number of 128-lane rows (1856) would otherwise be
    the minor axis, which the chip lays out transposed and copies back for
    the kernel on every call (639 MB a layer, compiled for the chip, PR 59).
    ``held``: the experts whose weights are made, where that is fewer than
    the ``num_experts`` the router scores (the chip's share of the layer)."""
    if activation not in ACTIVATIONS:
        raise ValueError(f"unknown expert activation {activation!r}; "
                         f"expected one of {ACTIVATIONS}")
    held = num_experts if held is None else held
    ks = jax.random.split(key, 4)
    # what the other two bring is drawn from keys of their own: the four
    # above are what a model without them has always been made from
    more = jax.random.split(jax.random.fold_in(key, 4), 4)
    init = jax.nn.initializers.normal(0.02, param_dtype)
    gated = activation == SWIGLU
    p = {
        "w_router": init(ks[0], (embed_dim, num_experts)),
        "w_down": init(ks[3], (held, hidden_dim, embed_dim)),
    }
    if gated:
        p["w_gate"] = init(ks[1], (held, embed_dim, hidden_dim))
        p["w_up"] = init(ks[2], (held, embed_dim, hidden_dim))
    else:
        p["w_up_t"] = init(ks[2], (held, hidden_dim, embed_dim))
    if choice_bias:
        p["e_bias"] = _BIAS_STD * jax.random.normal(
            more[0], (num_experts,), jnp.float32)
    if shared_dim:
        p.update(ws_up=init(more[2], (embed_dim, shared_dim)),
                 ws_down=init(more[3], (shared_dim, embed_dim)))
        if gated:
            p["ws_gate"] = init(more[1], (embed_dim, shared_dim))
    return p


def moe_logical_axes(choice_bias: bool = False, shared: bool = False,
                     activation: str = SWIGLU
                     ) -> Dict[str, Tuple[Optional[str], ...]]:
    gated = activation == SWIGLU
    axes = {
        "w_router": ("embed", None),
        "w_down": ("expert", "mlp", "embed"),
    }
    if gated:
        axes.update(w_gate=("expert", "embed", "mlp"),
                    w_up=("expert", "embed", "mlp"))
    else:
        axes["w_up_t"] = ("expert", "mlp", "embed")
    if choice_bias:
        axes["e_bias"] = (None,)
    if shared:
        axes.update(ws_up=("embed", "mlp"), ws_down=("mlp", "embed"))
        if gated:
            axes["ws_gate"] = ("embed", "mlp")
    return axes

# ------------------------------------------- the serving path's experts

_LANES = 128
_VMEM_BUDGET = 48 << 20   # a grid cell: every block twice + its products
_MAX_ROWS = 128           # up to here a visit's products hide behind the
#                           next expert's copy (PERF.md 6, PR 36 / 37)
_GROUPS_A_TILE = 16       # a row tile spans so many groups of mean size


class Tiles(NamedTuple):
    rows: int  # pairs a row tile holds
    cols: int  # columns of the experts' hidden width a grid cell takes


def _vmem_bytes(t: Tiles, d: int, itemsize: int, matrices: int = 3) -> int:
    """VMEM a grid cell holds: the row tile, the weight tiles (three of a
    SwiGLU expert, two of a 'relu2' one) and the out tile twice (two buffers
    each), the float32 products before they are rounded (gate, up, down) and
    the down product's accumulator."""
    blocks = 2 * t.rows * d + matrices * d * t.cols
    return 2 * blocks * itemsize + (2 * t.rows * t.cols
                                    + 2 * t.rows * d) * 4


def tile_sizes(pairs: int, groups: int, d: int, f: int, itemsize: int,
               vmem_bytes: int = _VMEM_BUDGET, matrices: int = 3) -> Tiles:
    """The kernel's tiles, from static shapes alone.

    rows: ``_GROUPS_A_TILE`` groups of mean size, as a power of two between
    the dtype's sublane tile (16 rows of bf16) and ``_MAX_ROWS``, never
    more than the pairs there are: 64 for a decode step's 256 pairs over 64
    experts, 128 for a 512-token chunk's 4,096. What a tile costs is its
    VISITS (one a group that shares a row with it: a grid step, and the
    copy engine idle while a group's second tile is multiplied), not its
    rows: a visit's products run beside the next expert's copy and take
    less than it up to 128 rows (measured on the v5e at OLMoE's widths,
    PERF.md 6). cols: all of the hidden width ``f`` where a whole expert
    fits ``vmem_bytes`` twice over (OLMoE: 12.6 MB, three contiguous
    copies), else the widest whole-lane divisor of ``f`` that does (256 of
    DeepSeek's 2048 at d 7168: 88 MB an expert); the down product then
    accumulates over the column tiles, and the weights are read once a
    VISIT, not once an expert: a group across two row tiles streams its
    expert twice, a padding visit nothing (``_column``)."""
    sublanes = 8 * 4 // itemsize
    want = _GROUPS_A_TILE * max(pairs // max(groups, 1), 1)
    rows = max(sublanes, min(_MAX_ROWS, 1 << (want - 1).bit_length()))
    rows = min(rows, -(-pairs // sublanes) * sublanes)
    lanes = f // _LANES if f % _LANES == 0 else 0
    for m in range(lanes, 0, -1):
        t = Tiles(rows, m * _LANES)
        if lanes % m == 0 and _vmem_bytes(t, d, itemsize,
                                          matrices) <= vmem_bytes:
            return t
    return Tiles(rows, f if not lanes else _LANES)


def _visits(counts, first_group, n_tiles: int, rows: int):
    """The grid's walk: one VISIT for every (group, row tile) pair that
    shares a row, in the order of the rows. Consecutive groups share at
    most one tile, so there are at most ``n_tiles + G - 1``; the walk is
    padded to that by repeating the last visit, which is not computed. What
    a padding visit FETCHES is the weight blocks' index maps' to say
    (``hidden_block``, ``down_block``): the same group and row tile is the
    same block, and no copy, only where the hidden width is one column tile.
    Returns int32 arrays a visit (its group in the weight stack, its row
    tile, the group's first row and its end) and the number of visits."""
    ends = jnp.cumsum(counts)
    starts = ends - counts
    first = starts // rows
    n = jnp.where(counts > 0, (ends - 1) // rows - first + 1, 0)
    upto = jnp.cumsum(n)
    total = upto[-1]
    i = jnp.minimum(jnp.arange(n_tiles + counts.shape[0] - 1),
                    jnp.maximum(total - 1, 0))
    g = jnp.minimum((i[:, None] >= upto[None, :]).sum(1),
                    counts.shape[0] - 1)
    tile = jnp.clip(first[g] + i - (upto - n)[g], 0, n_tiles - 1)
    return first_group + g, tile, starts[g], ends[g], total[None]


def walk_lengths(counts, pairs: int, d: int, f: int, itemsize: int,
                 matrices: int = 3) -> Tuple[int, int]:
    """``_visits``' arithmetic on the host, for counters: ``counts`` [calls,
    G] (numpy), the rows each group received in each kernel call of
    ``pairs`` static pairs -> (the visits that carry rows, summed over the
    calls; the grid's visits a call, ``n_tiles + G - 1``), on
    ``tile_sizes``' rows. Their difference is the walk's padding."""
    rows = tile_sizes(pairs, counts.shape[-1], d, f, itemsize,
                      matrices=matrices).rows
    ends = np.cumsum(counts, axis=-1)
    tiles = np.where(counts > 0,
                     (ends - 1) // rows - (ends - counts) // rows + 1, 0)
    return int(tiles.sum()), -(-pairs // rows) + counts.shape[-1] - 1


def _column(i, j, total, f_tiles: int):
    """The column tile whose weights grid step (visit ``i``, column tile
    ``j``) holds. A visit that carries rows (``i < total``) walks the
    ``f_tiles`` tiles of its expert. A PADDING visit's ``j`` runs over them
    again, and a block index that changes is a copy whatever the kernel then
    does with it (at DeepSeek's widths 11 MB a step, a whole 88 MB expert a
    padding visit, two thirds of the walk on a sixteenth of the experts):
    it stays on the last real step's tile, so from there to the end of the
    grid no index changes and the pipeline issues no copy; a walk without a
    real visit fetches its one opening block. One column tile: ``j`` is 0
    throughout and the maps are what they were before there were several."""
    if f_tiles == 1:
        return j
    return jnp.where(i < total[0], j, f_tiles - 1)


def hidden_block(i, j, group, total, f_tiles: int):
    """Index map of a ``[d, cols]`` tile of a ``[stack, d, f]`` matrix (gate,
    up): the visit's group in the stack, and ``_column``."""
    return group[i], 0, _column(i, j, total, f_tiles)


def down_block(i, j, group, total, f_tiles: int):
    """Index map of a ``[cols, d]`` tile of a hidden-major ``[stack, f, d]``
    matrix (down; a 'relu2' expert's up)."""
    return group[i], _column(i, j, total, f_tiles), 0


def _expert_kernel(group_ref, tile_ref, start_ref, end_ref, total_ref,
                   x_ref, *refs, rows, gated=True):
    """One visit (and one column tile of the hidden width): the row tile
    through the group's SwiGLU (``gated``: gate, up, down) or its
    ``relu(up)^2`` (up, down: no third matrix is read), stored into the rows
    of the tile that are the group's. The out block stays in VMEM while
    consecutive visits share its tile, and goes back to HBM once."""
    i, j = pl.program_id(0), pl.program_id(1)
    up_ref, down_ref, o_ref, *acc = refs[gated:]

    @pl.when(i < total_ref[0])
    def _():
        x = x_ref[...]
        if gated:
            gate = jnp.dot(x, refs[0][...],
                           preferred_element_type=jnp.float32)
            up = jnp.dot(x, up_ref[...], preferred_element_type=jnp.float32)
            hidden = (jax.nn.silu(gate) * up).astype(x.dtype)
        else:  # up is hidden-major, [cols, d]: x up^T
            up = lax.dot_general(x, up_ref[...], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            hidden = jnp.square(jnp.maximum(up, 0.0)).astype(x.dtype)
        y = jnp.dot(hidden, down_ref[...],
                    preferred_element_type=jnp.float32)

        def store(y):
            row = tile_ref[i] * rows + lax.broadcasted_iota(
                jnp.int32, (rows, 1), 0)
            mine = jnp.logical_and(row >= start_ref[i], row < end_ref[i])
            o_ref[...] = jnp.where(mine, y, o_ref[...].astype(jnp.float32)
                                   ).astype(o_ref.dtype)

        if not acc:  # the whole hidden width in one cell
            store(y)
            return
        acc_ref, = acc

        @pl.when(j == 0)
        def _():
            acc_ref[...] = y

        @pl.when(j > 0)
        def _():
            acc_ref[...] += y

        @pl.when(j == pl.num_programs(1) - 1)
        def _():
            store(acc_ref[...])


# jitted on its own: a program calls it once a layer with the same shapes,
# and the kernel's body is then traced and lowered once a shape, not once a
# layer (first_group is an argument, not a constant)
@functools.partial(jax.jit, static_argnames=("tiles",))
def expert_mlp(xs, w_gate, w_up, w_down, counts, first_group, tiles=None):
    """``silu(xs @ gate_g) * (xs @ up_g) @ down_g`` for every row's group;
    with ``w_gate`` None ``relu(xs @ up_g^T)^2 @ down_g``, the two-matrix
    expert, whose ``w_up`` is hidden-major, ``[.., f, d]`` as ``w_down`` is.

    xs: [pairs, d], sorted by group; counts: [G] int32 rows of each group;
    the weights are a STACK ``[>= first_group + G, d, f]`` (gate, up) and
    ``[.., f, d]`` (down) of which the G groups start at ``first_group``
    (layer x experts; an int32 scalar). Rows past the last group are
    undefined. ``tiles``: ``tile_sizes``' unless a test names its own."""
    pairs, d = xs.shape
    f = w_down.shape[1]
    gated = w_gate is not None
    matrices = 2 + gated
    t = tiles or tile_sizes(pairs, counts.shape[0], d, f, xs.dtype.itemsize,
                            matrices=matrices)
    if f % t.cols:
        raise ValueError(f"{t} does not divide the hidden width {f}")
    n_tiles, f_tiles = -(-pairs // t.rows), f // t.cols
    if n_tiles * t.rows != pairs:   # whole tiles; the rows added are dead
        xs = jnp.pad(xs, ((0, n_tiles * t.rows - pairs), (0, 0)))
    visits = _visits(counts.astype(jnp.int32),
                     jnp.asarray(first_group, jnp.int32), n_tiles, t.rows)

    def rows_map(i, j, group, tile, *_):
        return tile[i], 0

    def into_hidden(i, j, group, tile, start, end, total):
        return hidden_block(i, j, group, total, f_tiles)

    def from_hidden(i, j, group, tile, start, end, total):
        return down_block(i, j, group, total, f_tiles)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(visits),
        grid=(visits[0].shape[0], f_tiles),
        in_specs=[pl.BlockSpec((t.rows, d), rows_map)]
        + [pl.BlockSpec((None, d, t.cols), into_hidden)] * (2 * gated)
        + [pl.BlockSpec((None, t.cols, d), from_hidden)] * (2 - gated),
        out_specs=pl.BlockSpec((t.rows, d), rows_map),
        scratch_shapes=([pltpu.VMEM((t.rows, d), jnp.float32)]
                        if f_tiles > 1 else []),
    )
    out = pl.pallas_call(
        functools.partial(_expert_kernel, rows=t.rows, gated=gated),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles * t.rows, d), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_vmem_bytes(t, d, xs.dtype.itemsize, matrices)
            * 5 // 4 + (4 << 20)),
        name="moe_grouped_matmul",
        interpret=should_interpret(),
    )(*visits, xs, *((w_gate,) if gated else ()), w_up, w_down)
    return out[:pairs]


def limit_to_groups(chosen_by, groups: int, top_groups: int):
    """Group-limited routing (DeepSeek-V3's ``noaux_tc``): the experts in
    ``groups`` runs of consecutive indices, a group's score the sum of its
    TWO largest entries of ``chosen_by`` [N, E], the ``top_groups`` best
    groups kept (ties to the lower group) -> ``chosen_by`` with every expert
    of another group at -inf, so no top-k takes one."""
    n, e = chosen_by.shape
    if e % groups or e // groups < 2 or not 0 < top_groups <= groups:
        raise ValueError(
            f"{e} experts in {groups} groups of at least two, of which "
            f"{top_groups} are kept")
    best = jax.lax.top_k(chosen_by.reshape(n, groups, e // groups), 2)[0]
    kept = jax.lax.top_k(best.sum(-1), top_groups)[1]            # [N, kept]
    keep = (kept[..., None] == jnp.arange(groups)).any(axis=1)  # [N, groups]
    return jnp.where(jnp.repeat(keep, e // groups, axis=1), chosen_by,
                     -jnp.inf)


def route(logits, top_k: int, renormalize: bool, scoring: str = SOFTMAX,
          bias=None, routed_scale: float = 1.0, groups: int = 1,
          top_groups: int = 1):
    """Router logits [N, E] float32 -> (scores [N, E], the k experts a row
    takes [N, k] best first, their weights [N, k]), float32.

    'softmax': scores are the softmax over the experts, the choice their k
    largest, the weights those (divided by their sum with ``renormalize``).
    'sigmoid': scores are a sigmoid an expert; the choice is the k largest
    of ``scores + bias`` ([E] float32: the bias corrects who is chosen) and
    the weights the UNBIASED scores of the chosen, divided by their sum plus
    1e-20 with ``renormalize``; with ``groups`` > 1 the choice is made among
    the experts of the ``top_groups`` best groups alone (``limit_to_groups``;
    one group is the identity and adds nothing to the program). Either way
    times ``routed_scale``. Ties go to the lower index
    (``jax.lax.top_k``)."""
    if scoring == SOFTMAX:
        scores = jax.nn.softmax(logits, axis=-1)
        gate_vals, gate_idx = jax.lax.top_k(scores, top_k)  # [N, k]
        if renormalize:
            gate_vals = gate_vals / jnp.maximum(
                gate_vals.sum(-1, keepdims=True), 1e-9)
    elif scoring == SIGMOID:
        scores = jax.nn.sigmoid(logits)
        chosen_by = scores if bias is None else scores + bias.astype(
            jnp.float32)
        if groups > 1:
            chosen_by = limit_to_groups(chosen_by, groups, top_groups)
        gate_idx = jax.lax.top_k(chosen_by, top_k)[1]
        gate_vals = jnp.take_along_axis(scores, gate_idx, axis=-1)
        if renormalize:
            gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-20)
    else:
        raise ValueError(f"unknown router scoring {scoring!r}; expected one "
                         f"of {SCORINGS}")
    if routed_scale != 1.0:
        gate_vals = gate_vals * routed_scale
    return scores, gate_idx, gate_vals


def held_index(experts, live, num_experts: int,
               held: Optional[Tuple[int, int]]):
    """The group each (row, choice) pair sorts into: ``experts`` [..., k]
    (chosen over all ``num_experts``), ``live`` [...] -> the expert's index
    among the G experts held here (``held`` = (first, G); None: all of
    them), and G — behind every group — for a pair of a row that is not
    live or of an expert this chip does not hold."""
    if held is None:
        return jnp.where(live[..., None], experts, num_experts)
    first, groups = held
    mine = live[..., None] & (experts >= first) & (experts < first + groups)
    return jnp.where(mine, experts - first, groups)


def moe_layer(p: Dict[str, Any], x, *, num_experts: int, top_k: int = 2,
              renormalize: bool = True, dtype=jnp.bfloat16, valid=None,
              layer: Optional[int] = None, scoring: str = SOFTMAX,
              routed_scale: float = 1.0,
              held: Optional[Tuple[int, int]] = None,
              activation: str = SWIGLU, expert_groups: int = 1,
              top_groups: int = 1):
    """x: [B, S, d] -> (y [B, S, d], aux_loss, counts [E], routes [B, S, k]).

    ``scoring``, ``renormalize``, ``routed_scale``: the router's rule
    (``route``): softmax probabilities taken as they are (OLMoE) or divided
    by their sum (Mixtral, GShard), or sigmoid scores chosen by ``score +
    p["e_bias"]`` and weighed without it (DeepSeek-V3's ``noaux_tc``; among
    the ``top_groups`` best of ``expert_groups`` groups of experts where there
    is more than one group). Where ``p`` holds a shared expert (``ws_gate``,
    ``ws_up``,
    ``ws_down``) every live row takes it beside its routed sum, unweighted.
    ``activation``: the experts' form and the shared one's (``ACTIVATIONS``;
    'relu2' has no gate matrix and none is read).
    ``held`` = (first, count): ``p`` holds the weights of ``count`` of the
    ``num_experts`` experts, from ``first`` on — the chip's share of a layer
    whose experts lie over several. The router scores ALL experts and each
    row's weights are normalised over all its ``top_k`` choices; the pairs
    that chose an expert held elsewhere are dropped before the grouped
    products (they sort behind every group: no row of a tile, no visit, no
    count) and y is this chip's experts' part of the routed sum, plus the
    shared expert. What the other chips would add is left out, and nothing
    stands in for them. ``counts`` is then over the ``count`` held experts.
    ``valid``: optional bool [B, S]; a row marked False is routed nowhere.
    ``counts``: int32 rows each expert received (valid rows only; they sum
    to valid rows x k: the dropless witness). ``routes``: the experts each
    row chose, best first (also for rows that are not valid).
    ``layer``: ``p`` holds ALL layers' weights stacked on a leading axis and
    this is the layer to apply: the serving path, whose grouped matmuls are
    the kernel (``expert_mlp``), which takes the stack whole and the layer
    as an offset into it; a layer sliced off the stack would first be
    copied (on the v5e 805 MB a call at OLMoE's widths). Without ``layer``
    the three products are ``jax.lax.ragged_dot`` (module docstring).

    aux_loss is the Switch load-balancing loss over the valid rows
    (E * sum_e fraction_of_rows_whose_first_choice_is_e * mean_prob_e, a
    sigmoid router's scores divided by their sum over the experts); add
    it to the task loss scaled by ~1e-2.
    """
    b, s, d = x.shape
    n = b * s
    xt = x.reshape(n, d).astype(dtype)
    own = (lambda a: a) if layer is None else (lambda a: a[layer])
    logits = jnp.einsum("nd,de->ne", xt, own(p["w_router"]).astype(dtype),
                        preferred_element_type=jnp.float32)
    probs, gate_idx, gate_vals = route(
        logits, top_k, renormalize, scoring,
        own(p["e_bias"]) if "e_bias" in p else None, routed_scale,
        expert_groups, top_groups)
    if scoring != SOFTMAX:  # the auxiliary loss wants a distribution
        probs = probs / probs.sum(-1, keepdims=True)
    live = (jnp.ones((n,), bool) if valid is None
            else valid.reshape(n).astype(bool))

    # (row, choice) pairs sorted by expert; pairs of rows that are not live
    # (and of experts held elsewhere) carry the id behind the last group, so
    # they sort behind every group
    groups = held[1] if held else num_experts
    pair_group = held_index(gate_idx, live, num_experts, held)  # [N, k]
    pair_expert = pair_group.reshape(-1)
    order = jnp.argsort(pair_expert, stable=True)
    counts = jnp.zeros((groups,), jnp.int32).at[pair_expert].add(
        1, mode="drop")
    xs = xt[order // top_k]  # [N*k, d]
    gated = activation == SWIGLU
    w_gate, w_up, w_down = (p[k].astype(dtype) if k in p else None for k in (
        "w_gate", "w_up" if gated else "w_up_t", "w_down"))
    if layer is None and gated:
        gate = jax.lax.ragged_dot(xs, w_gate, counts)
        up = jax.lax.ragged_dot(xs, w_up, counts)
        out = jax.lax.ragged_dot(jax.nn.silu(gate) * up, w_down, counts)
    elif layer is None:
        up = jax.lax.ragged_dot(xs, w_up.swapaxes(1, 2), counts)
        out = jax.lax.ragged_dot(jnp.square(jax.nn.relu(up)), w_down, counts)
    else:  # [layers, experts, ...] seen as layers x experts groups
        out = expert_mlp(xs, *(a if a is None else a.reshape(-1, *a.shape[2:])
                               for a in (w_gate, w_up, w_down)),
                         counts, layer * groups)
    # back to (row, choice) order; the weighted sum over a row's k experts
    # accumulates in float32. Pairs past the last group hold nothing
    # defined: they are masked, not multiplied by 0
    out = out[jnp.argsort(order)].reshape(n, top_k, d)
    kept = (live[:, None, None] if held is None
            else (pair_group < groups)[:, :, None])
    y = jnp.einsum("nkd,nk->nd",
                   jnp.where(kept, out, 0).astype(jnp.float32),
                   gate_vals).astype(dtype)
    if "ws_up" in p:
        # the shared expert: a dense expert of every row (a layer's slice of
        # a dense matrix fuses into the product; a row that is not live
        # comes out as zeros here too)
        ws_gate, ws_up, ws_down = (
            own(p[k]).astype(dtype) if k in p else None
            for k in ("ws_gate", "ws_up", "ws_down"))
        hidden = (jax.nn.silu(xt @ ws_gate) * (xt @ ws_up) if gated
                  else jnp.square(jax.nn.relu(xt @ ws_up)))
        shared = hidden @ ws_down
        y = y + jnp.where(live[:, None], shared, 0).astype(dtype)

    # Switch aux loss: encourage uniform routing
    rows = jnp.maximum(live.sum(), 1).astype(jnp.float32)
    top1 = jax.nn.one_hot(gate_idx[:, 0], num_experts, dtype=jnp.float32)
    fraction = (top1 * live[:, None]).sum(0) / rows
    mean_prob = (probs * live[:, None]).sum(0) / rows
    aux = num_experts * jnp.sum(fraction * mean_prob)
    return (y.reshape(b, s, d), aux, counts,
            gate_idx.reshape(b, s, top_k))
