"""Where compiled XLA programs are kept between processes and between runs.

The cache is placed from OUTSIDE: if ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it and this module sets nothing. Only if it is not set does the
cache go to one fixed directory inside the checkout (git ignores it). The
directory is part of the cache key, so it is never built from a temp name,
a pid, a session directory or the time — a cache that moves never hits.

``enable()`` is called where a process first becomes a device process
(chip-holding worker start-up, ``bench.py``, ``chip_smoke.py``); every
process it starts inherits the variable through its environment.
"""

from __future__ import annotations

import os
import sys

_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def enable() -> str:
    """Make sure this process and its children have a persistent compile
    cache, and return its directory."""
    path = os.environ.get(_ENV)
    if path:
        return path  # placed from outside: JAX reads the variable itself
    os.environ[_ENV] = DEFAULT_DIR
    if "jax" in sys.modules:  # imported before us: it has read its env
        sys.modules["jax"].config.update("jax_compilation_cache_dir",
                                         DEFAULT_DIR)
    return DEFAULT_DIR


def entries(path: str) -> int:
    """Number of cached programs under ``path`` (0 if it does not exist)."""
    try:
        return sum(1 for name in os.listdir(path)
                   if not name.endswith("-atime"))
    except FileNotFoundError:
        return 0
