"""Device-mesh construction for DP / FSDP / TP / SP / EP / PP axes.

This is the TPU-native replacement for the reference's process-group world
(`ray.util.collective` + torch.distributed NCCL groups, SURVEY §2.2/§5):
instead of N ranks and explicit NCCL calls, parallelism is expressed as named
axes of a `jax.sharding.Mesh`; XLA/GSPMD inserts the ICI collectives.

Canonical axis names (used by sharding rules and the trainer):
  * ``dp``   — pure data parallel (gradient all-reduce over ICI/DCN)
  * ``fsdp`` — data parallel with parameter/optimizer sharding (ZeRO-3-style,
               all-gather params forward, reduce-scatter grads)
  * ``tp``   — tensor (megatron) parallelism within attention/MLP blocks.
               A training block pays four reduces of a residual-sized array
               over it: two forward (``wo``'s and the mlp's last dot's
               partial sums), two backward (the gradient of each norm's
               output), none again in the recompute, which keeps ``wo``'s
               reduced result (``models/transformer.py: ATTN_OUT``).
               ``forward`` states where the residual lives (batch over
               ``dp``/``fsdp``, the hidden dimension whole) and the fused
               cross-entropy where a chunk's rows do, so no activation is
               reduced over ``fsdp``. Each of the four is two reduces of
               half the bytes where ``forward`` carries a chip's rows as
               two streams (``transformer.streams``), so that one stream's
               reduce can run under the other's matmuls
               (``training.OVERLAP_REDUCES``); ``collective_census`` below
               counts what a compiled step holds, by op and axis, and how
               many of them the compiler scheduled matmuls under
  * ``sp``   — sequence/context parallelism (ring attention over this axis)
  * ``ep``   — expert parallelism for MoE layers
  * ``pp``   — pipeline stages (usually over DCN between slices)

Mesh-axis ordering follows the scaling-book recipe: the innermost (fastest
varying) axes map to the densest ICI links, so tp/sp live innermost, dp/fsdp
outermost, pp over DCN.
"""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, List, Optional, Sequence, Tuple

AXIS_ORDER = ("pp", "dp", "fsdp", "ep", "sp", "tp")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """A declarative mesh: axis name → size. Unlisted axes have size 1."""

    axes: Tuple[Tuple[str, int], ...]

    @classmethod
    def of(cls, **sizes: int) -> "MeshSpec":
        for name in sizes:
            if name not in AXIS_ORDER:
                raise ValueError(f"unknown mesh axis {name!r}; valid: {AXIS_ORDER}")
        ordered = tuple((a, sizes.get(a, 1)) for a in AXIS_ORDER if sizes.get(a, 1) > 1)
        return cls(ordered if ordered else (("dp", 1),))

    @property
    def size(self) -> int:
        return math.prod(s for _, s in self.axes)

    @property
    def names(self) -> Tuple[str, ...]:
        return tuple(a for a, _ in self.axes)

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(s for _, s in self.axes)

    def axis_size(self, name: str) -> int:
        for a, s in self.axes:
            if a == name:
                return s
        return 1

    @classmethod
    def auto(cls, n_devices: int, *, model_needs_tp: int = 1, fsdp: bool = True) -> "MeshSpec":
        """Simple auto-layout: give tp what the model needs, rest to fsdp/dp."""
        tp = min(model_needs_tp, n_devices)
        rest = n_devices // tp
        if fsdp:
            return cls.of(fsdp=rest, tp=tp)
        return cls.of(dp=rest, tp=tp)


def build_mesh(spec: MeshSpec, devices: Optional[Sequence] = None):
    """Build a `jax.sharding.Mesh` with the spec's named axes.

    Devices default to all visible devices; their count must equal spec.size.
    """
    import jax
    import numpy as np

    if devices is None:
        devices = jax.devices()
    if len(devices) != spec.size:
        raise ValueError(
            f"mesh spec needs {spec.size} devices ({dict(spec.axes)}), "
            f"got {len(devices)}"
        )
    arr = np.array(devices).reshape(spec.shape)
    from jax.sharding import Mesh

    return Mesh(arr, spec.names)


def local_mesh(**sizes: int):
    """Convenience: mesh over this process's visible devices."""
    return build_mesh(MeshSpec.of(**sizes))


def data_sharding(mesh, batch_axes: Sequence[str] = ("dp", "fsdp")):
    """NamedSharding for a [batch, ...] input: batch split over data axes."""
    from jax.sharding import NamedSharding, PartitionSpec

    present = [a for a in batch_axes if a in mesh.axis_names]
    return NamedSharding(mesh, PartitionSpec(tuple(present) if present else None))


def replicated(mesh):
    from jax.sharding import NamedSharding, PartitionSpec

    return NamedSharding(mesh, PartitionSpec())


# ---------------------------------------------------------------------------
# what the compiler put on the interconnect: a count from the program's text

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
                "s16": 2, "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4,
                "f32": 4, "s64": 8, "u64": 8, "f64": 8}
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(?P<type>\(.*?\)|\S+)\s+"
    r"(?P<op>" + "|".join(_COLLECTIVES) + r")(?P<start>-start)?\(")
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")
_CALLED = re.compile(
    r"(?:body|condition|calls|to_apply|true_computation|false_computation)"
    r"=%?([\w.\-]+)|branch_computations=\{([^}]*)\}")
# any instruction: its name, and the op that stands before its operands
_ANY = re.compile(r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
                  r"(?:\(.*?\)|\S+)\s+(?P<op>[\w\-]+)\((?P<args>[^)]*)")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
# the chip compiler's asynchronous form: a fusion whose computation holds
# the collective and a custom call of the first name starts it, fusions of
# the work it lies under carry it on (each repeats the instruction), and one
# whose computation holds the second name ends it
_ASYNC_START, _ASYNC_DONE = "AsyncCollectiveStart", "AsyncCollectiveDone"
_KERNEL = "tpu_custom_call"


def _group_axes(line: str, op: str, shape: Dict[str, int]) -> Tuple[str, ...]:
    """The mesh axes one group of the instruction spans: its devices'
    places in the mesh (a device's number is its place in ``mesh.devices``,
    row-major, which is how ``jit`` numbers the partitions), and the axes
    along which they differ."""
    import numpy as np

    sizes = tuple(shape.values())
    n = math.prod(sizes)
    if op == "collective-permute":
        pairs = re.search(r"source_target_pairs=\{(.*?)\}\}", line)
        groups = [[int(i) for i in pair.split(",")] for pair in
                  re.findall(r"\{(\d+,\d+)", pairs.group(1) + "}")]
    elif (iota := re.search(r"replica_groups=\[([\d,]+)\]<=\[([\d,]+)\]"
                            r"(?:T\(([\d,]+)\))?", line)):
        dims, reshape, perm = (tuple(int(i) for i in g.split(","))
                               if g else None for g in iota.groups())
        ids = np.arange(math.prod(reshape)).reshape(reshape)
        groups = ids.transpose(perm).reshape(dims).tolist()
    else:
        listed = re.search(r"replica_groups=\{(.*?)\}\}", line)
        groups = [[int(i) for i in g.split(",")] for g in
                  re.findall(r"\{([\d,]+)", (listed.group(1) + "}")
                             if listed else "")]
    if not groups:  # no groups named: every device is in the one group
        groups = [list(range(n))]
    differ = set()
    for group in groups:
        places = np.array(np.unravel_index(group, sizes))
        differ |= {i for i, row in enumerate(places) if len(set(row)) > 1}
    return tuple(name for i, name in enumerate(shape) if i in differ)


def collectives(hlo_text: str, mesh) -> List[Dict[str, object]]:
    """Every collective of a compiled program's text
    (``compiled.as_text()``), one row each: ``op`` (``all-reduce-scatter``
    for an all-reduce inside the chip compiler's fusion of that name);
    ``axes``, the mesh axes its groups span; ``shapes`` and ``bytes`` of
    what ONE device holds of its result (an ``-start`` counts once, by what
    its ``-done`` yields); ``loop``, whether a ``while`` body reaches it (a
    layer scan's, forward or backward, or the cross-entropy's chunks') or
    only the entry does; and ``op_name``, the source op it serves, as the
    compiler's metadata has it (``transpose(`` the backward pass,
    ``rematted_computation`` a recompute). A collective the compiler made
    asynchronous — ``<op>-start`` and ``<op>-done``, or the chip compiler's
    fused form (``_ASYNC_START``), which is counted ONCE however many
    fusions carry it on — also has ``between``: how many instructions that
    multiply are scheduled between its start and its end in that text (a
    fusion that holds a convolution or a dot, either of them bare, a Pallas
    kernel's call). 0 there, or no such field: whatever needs the result
    waits for the interconnect. Text in, rows out: nothing is compiled or
    run here."""
    shape = dict(mesh.shape)
    computation, rows, homes, calls, bodies = None, [], [], {}, set()
    # a computation's instructions in the order they are scheduled, (name,
    # op, the computation it calls, a custom call's target, operands); the
    # computations that hold a matmul; the role of one that starts or ends
    # the chip compiler's asynchronous form
    order, multiplies, role = {}, set(), {}
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            computation = head.group(2)
            continue
        for one, many in _CALLED.findall(line):
            names = [one] if one else re.findall(r"[\w.\-]+", many)
            calls.setdefault(computation, set()).update(names)
        bodies.update(re.findall(r"\bbody=%?([\w.\-]+)", line))
        any_ = _ANY.match(line)
        if any_:
            called = re.search(r"\bcalls=%?([\w.\-]+)", line)
            target = _TARGET.search(line)
            target = target.group(1) if target else ""
            order.setdefault(computation, []).append((
                any_.group("name"), any_.group("op"),
                called.group(1) if called else None, target,
                re.findall(r"%([\w.\-]+)", any_.group("args"))))
            if any_.group("op") in ("convolution", "dot"):
                multiplies.add(computation)
            if target.startswith("AsyncCollective"):
                if target not in (_ASYNC_START, _ASYNC_DONE):
                    raise ValueError(f"{computation}: an asynchronous "
                                     f"collective's {target!r} is no start "
                                     f"and no end that is known here")
                role[computation] = target
        found = _INSTRUCTION.match(line)
        if not found:
            continue
        arrays = [(dtype, tuple(int(d) for d in dims.split(",") if d))
                  for dtype, dims in _ARRAY.findall(found.group("type"))
                  if dtype in _DTYPE_BYTES]
        op = found.group("op")
        if computation.startswith("all-reduce-scatter"):
            # the chip's compiler fuses the reduce with the slice that
            # keeps a device's share: a reduce-scatter by another name
            op = "all-reduce-scatter"
        if found.group("start") and op in ("all-gather",
                                           "collective-permute"):
            # (operands..., results..., [two counters of a permute])
            arrays = [a for a in arrays if a[1] or op == "all-gather"]
            arrays = arrays[len(arrays) // 2:]
        name = re.search(r'op_name="([^"]*)"', line)
        channel = re.search(r"channel_id=(\d+)", line)
        rows.append({
            "op": op, "axes": _group_axes(line, op, shape),
            "shapes": [dims for _, dims in arrays],
            "bytes": sum(_DTYPE_BYTES[dtype] * math.prod(dims)
                         for dtype, dims in arrays),
            "op_name": name.group(1) if name else "",
            # where it stands in its computation, whether it is a
            # ``-start``, and the channel a fused chain's parts share
            "_at": (len(order[computation]) - 1, bool(found.group("start")),
                    channel.group(1) if channel else None)})
        homes.append(computation)
    in_loop, stack = set(), list(bodies)
    while stack:
        name = stack.pop()
        if name not in in_loop:
            in_loop.add(name)
            stack.extend(calls.get(name, ()))
    # the instructions that call a computation, to find where an
    # asynchronous fusion stands in its caller's schedule
    callers = {called: (comp, i) for comp, instrs in order.items()
               for i, (_, _, called, _, _) in enumerate(instrs) if called}
    channels = {home: row["_at"][2] for row, home in zip(rows, homes)}

    started = {channels[home] for home, target in role.items()
               if target == _ASYNC_START}

    def between(comp, start, ends) -> Optional[int]:
        """Instructions that multiply after ``order[comp][start]`` and
        before the first one that ``ends(instruction)``; None without one."""
        count = 0
        for instr in order[comp][start + 1:]:
            _, op, called, target, _ = instr
            if ends(instr):
                return count
            count += (op in ("convolution", "dot")
                      or op == "fusion" and called in multiplies
                      or op == "custom-call" and target == _KERNEL)
        return None

    kept = []
    for row, home in zip(rows, homes):
        at, start, channel = row.pop("_at")
        row["loop"] = home in in_loop
        if home in role or home.startswith("async_collective_fusion"):
            # the fused form is the chip compiler's own and nowhere written
            # down: text that is not the chain known here (a start, fusions
            # of one channel that carry it on, an end) is refused, not read
            # as a collective with nothing under it
            if role.get(home) != _ASYNC_START:
                if channel not in started:
                    raise ValueError(f"{home}: carries on or ends a "
                                     f"collective of channel {channel}, "
                                     f"which no {_ASYNC_START} starts")
                continue  # the same collective, carried on or ended
            row["between"] = between(
                *callers[home], lambda instr: role.get(instr[2]) == _ASYNC_DONE
                and channels.get(instr[2]) == channel)
            if row["between"] is None:
                raise ValueError(f"{home}: no {_ASYNC_DONE} of channel "
                                 f"{channel} follows its start")
        elif start:
            name = order[home][at][0]
            row["between"] = between(
                home, at, lambda instr: instr[1].endswith("-done")
                and instr[4][:1] == [name]) or 0  # no end: not a pair
        kept.append(row)
    return kept


def collective_census(hlo_text: str, mesh) -> Dict[tuple, Dict[str, int]]:
    """``collectives`` added up: ``(where, op, axes) -> {"calls", "bytes",
    "hidden"}``, ``where`` "loop" (the scan bodies: a layer-step) or
    "entry"; ``hidden`` the calls with matmuls scheduled ``between`` their
    start and their end."""
    census: Dict[tuple, Dict[str, int]] = {}
    for row in collectives(hlo_text, mesh):
        key = ("loop" if row["loop"] else "entry", row["op"], row["axes"])
        tally = census.setdefault(key, {"calls": 0, "bytes": 0, "hidden": 0})
        tally["calls"] += 1
        tally["bytes"] += row["bytes"]
        tally["hidden"] += row.get("between", 0) > 0
    return census
