"""Attention dispatcher: picks the Pallas flash kernel on TPU (or when forced),
the XLA reference otherwise. Single entry point for all models."""

from __future__ import annotations

import math
from typing import Optional

import jax

from ray_tpu.ops.flash_attention import flash_attention, reference_attention

ATTN_IMPLS = ("auto", "flash", "reference")


def attention(q, k, v, *, causal: bool = True, sm_scale: Optional[float] = None,
              impl: str = "auto", bias=None):
    """Multi-head / grouped-query attention.

    q: [B, Sq, H, D]; k, v: [B, Sk, Hkv, D] with H % Hkv == 0.
    impl: 'auto' | 'flash' | 'reference'. 'auto' uses the Pallas kernel on TPU
    and the XLA reference elsewhere (the kernel still runs everywhere via
    interpret mode when explicitly selected, which is how CPU tests cover it).
    """
    if impl not in ATTN_IMPLS:
        # a typo must not silently fall through to the reference path —
        # the caller believes it selected a kernel
        raise ValueError(
            f"unknown attention impl {impl!r}; expected one of "
            f"{list(ATTN_IMPLS)}")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if bias is not None:
        if impl == "flash":  # never give way to the reference silently
            raise ValueError("the flash kernel takes no bias; call with "
                             "impl='reference' (or 'auto')")
        impl = "reference"  # shape rule: only the reference adds a bias
    if impl == "auto":
        impl = "flash" if jax.default_backend() == "tpu" else "reference"
    if impl == "flash":
        return _flash_on_mesh(q, k, v, sm_scale, causal)
    return reference_attention(q, k, v, sm_scale, causal, bias=bias)


def _flash_on_mesh(q, k, v, sm_scale, causal):
    """The flash kernel, partitioned by hand when a mesh is in scope.

    GSPMD cannot partition a Mosaic kernel ("wrap the call in a
    shard_map"), so under a mesh — ``make_train_step`` puts its mesh in
    scope — the kernel runs per shard: batch over the axes the sharding
    rules give ``batch``, heads over the axis they give ``heads`` (only
    when it divides H and Hkv alike, so every shard keeps whole GQA
    groups); sequence and head_dim stay whole, which is all the kernel
    needs. Axes that are already manual (a caller's own shard_map) are
    left alone. With no mesh in scope the call is bare, as before."""
    mesh = jax.sharding.get_abstract_mesh()
    free = [a for a in mesh.axis_names if a not in mesh.manual_axes]
    if not free:
        return flash_attention(q, k, v, sm_scale, causal)
    from jax.sharding import PartitionSpec as P

    from ray_tpu.parallel.sharding import free_axes

    spec = P(free_axes("batch", q.shape[0]), None,
             free_axes("heads", q.shape[2], k.shape[2]), None)
    return jax.shard_map(
        lambda q, k, v: flash_attention(q, k, v, sm_scale, causal),
        in_specs=(spec, spec, spec), out_specs=spec,
        axis_names=set(free), check_vma=False)(q, k, v)
