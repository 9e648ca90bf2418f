"""Logical-axis sharding rules (flax-style) for model state.

Replaces the reference's DDP/FSDP wrapping step
(`train/torch/train_loop_utils.py:158` `prepare_model`): instead of wrapping
modules, parameters carry *logical axis names* and a rule table maps them to
mesh axes; `jax.device_put` with the resulting NamedSharding both shards and
(under fsdp) ZeRO-partitions the state in one step. XLA then inserts the
all-gathers/reduce-scatters GSPMD-style.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Sequence, Tuple, Union

LogicalAxes = Tuple[Optional[str], ...]


# Default rule table: logical axis name -> mesh axis (or None = replicate).
DEFAULT_RULES: Dict[str, Optional[Union[str, Tuple[str, ...]]]] = {
    # activations
    "batch": ("dp", "fsdp"),
    "seq": "sp",
    # params
    "embed": "fsdp",  # ZeRO-shard the embed dim of params over fsdp
    "embed_notp": "fsdp",  # embed-sized vectors (norm scales): fsdp only
    "vocab": "tp",
    "mlp": "tp",
    "heads": "tp",
    "kv": "tp",
    "head_dim": None,
    "layers": None,
    "expert": "ep",
}


def free_axes(logical: str, *dims: int):
    """The axes of the mesh in scope (``jax.sharding.get_abstract_mesh()``)
    that ``DEFAULT_RULES`` give ``logical`` and that are still the
    compiler's to place — not manual under a caller's ``shard_map`` — as a
    ``PartitionSpec`` entry: one name, a tuple of names, or None where there
    is none or their size does not divide every one of ``dims``. What code
    that traces under ``make_train_step``'s mesh asks before it states a
    layout; with no mesh in scope the answer is None."""
    import jax

    mesh = jax.sharding.get_abstract_mesh()
    target = DEFAULT_RULES[logical]
    names = tuple(a for a in ((target,) if isinstance(target, str)
                              else target or ())
                  if a in mesh.axis_names and a not in mesh.manual_axes)
    size = math.prod(mesh.shape[a] for a in names)
    if size == 1 or any(d % size for d in dims):
        return None
    return names if len(names) > 1 else names[0]


def free_parts(logical: str, *dims: int) -> int:
    """Into how many parts ``free_axes(logical, *dims)`` splits a dimension:
    the product of its axes' sizes, 1 where it gives None."""
    import jax

    names = free_axes(logical, *dims)
    shape = jax.sharding.get_abstract_mesh().shape
    return math.prod(shape[a] for a in (
        (names,) if isinstance(names, str) else names or ()))


@dataclasses.dataclass
class ShardingRules:
    rules: Dict[str, Optional[Union[str, Tuple[str, ...]]]] = dataclasses.field(
        default_factory=lambda: dict(DEFAULT_RULES)
    )

    def with_overrides(self, **overrides) -> "ShardingRules":
        new = dict(self.rules)
        new.update(overrides)
        return ShardingRules(new)

    def spec(self, logical: Sequence[Optional[str]], mesh) -> "Any":
        """PartitionSpec for one array's logical axes, dropping mesh axes the
        mesh doesn't have (so the same model runs on any mesh)."""
        from jax.sharding import PartitionSpec

        out = []
        used = set()
        for name in logical:
            target = self.rules.get(name) if name else None
            if target is None:
                out.append(None)
                continue
            targets = (target,) if isinstance(target, str) else tuple(target)
            present = tuple(
                t for t in targets if t in mesh.axis_names and t not in used
            )
            used.update(present)
            if not present:
                out.append(None)
            elif len(present) == 1:
                out.append(present[0])
            else:
                out.append(present)
        return PartitionSpec(*out)


def logical_to_spec(rules: ShardingRules, logical_tree, mesh):
    """Map a pytree of logical-axis tuples to a pytree of PartitionSpecs."""
    import jax

    return jax.tree.map(
        lambda ax: rules.spec(ax, mesh),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            a is None or isinstance(a, str) for a in x
        ),
    )


def infer_logical_axes(params) -> Any:
    """Heuristic logical axes for a params pytree when the model doesn't
    annotate: 2D [in, out] weights shard ('embed','mlp')-style; 1D replicate.

    Good enough for FSDP (shard the largest dim over fsdp); models in
    ray_tpu.models annotate explicitly instead.
    """
    import jax
    import numpy as np

    def leaf_axes(x):
        shape = getattr(x, "shape", ())
        if len(shape) <= 1:
            return (None,) * len(shape)
        axes: list = [None] * len(shape)
        axes[int(np.argmax(shape))] = "embed"
        return tuple(axes)

    return jax.tree.map(leaf_axes, params)


def sanitize_spec(spec, shape, mesh):
    """Drop mesh axes from a PartitionSpec on dims they don't divide evenly
    (e.g. 2 kv heads can't split over tp=8 — replicate instead)."""
    from jax.sharding import PartitionSpec

    out = []
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape):
            out.append(entry)
            continue
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        size = math.prod(mesh.shape[a] for a in axes)
        if size and shape[i] % size == 0:
            out.append(entry)
        else:
            out.append(None)
    return PartitionSpec(*out)


def param_specs(params, mesh, rules: Optional[ShardingRules] = None,
                logical=None):
    """Shape-checked PartitionSpec pytree for a params pytree."""
    import jax

    rules = rules or ShardingRules()
    if logical is None:
        logical = infer_logical_axes(params)
    specs = logical_to_spec(rules, logical, mesh)
    return jax.tree.map(
        lambda x, s: sanitize_spec(s, getattr(x, "shape", ()), mesh),
        params, specs)


def shard_params(params, mesh, rules: Optional[ShardingRules] = None, logical=None):
    """Place a params pytree onto the mesh per the rules (ZeRO/fsdp aware)."""
    import jax
    from jax.sharding import NamedSharding

    specs = param_specs(params, mesh, rules, logical)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), params, specs
    )
