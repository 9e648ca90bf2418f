"""Shared Pallas-kernel plumbing for ops/.

One place answers "should this kernel run in interpret mode?" — off-TPU
backends (CPU/GPU containers, unit tests) interpret the kernel so the SAME
code path is exercised everywhere, and ``RAY_TPU_PALLAS_INTERPRET=1``
forces interpret mode even on TPU (bisecting Mosaic lowering issues vs
kernel-math bugs). The knob is one-way: it can force interpretation ON,
never force a non-TPU backend to attempt a Mosaic compile (which would
just crash), so falsy values simply defer to backend detection.

On a TPU backend without the knob a kernel is always compiled, never
interpreted and never swapped for a reference; `chip_smoke.py` fails when
interpretation is in force for any reason.
"""

from __future__ import annotations

import os

import jax

_ENV_KNOB = "RAY_TPU_PALLAS_INTERPRET"


def force_interpret() -> bool:
    """True iff the env knob explicitly forces interpret mode."""
    return os.environ.get(_ENV_KNOB, "").lower() in ("1", "true", "yes", "on")


def should_interpret() -> bool:
    """Whether Pallas kernels must run in interpret mode: any backend
    without a Mosaic compiler (everything but TPU), or the force knob."""
    return force_interpret() or jax.default_backend() != "tpu"
