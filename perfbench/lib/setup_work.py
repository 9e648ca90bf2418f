"""What the readers of set-up's own record share (PR 57).

The program keeps, in the process that holds the chip, a compile record
(``ray_tpu/_private/compile_cache.py``: every jitted program's trace, lower
and compile-or-cache-load seconds and its persistent-cache hits and misses,
by function name, from ``jax.monitoring``) and the replica's build by phase
(``ray_tpu/serve/llm.py: BUILD_PHASES``), and ``scheduler_stats()`` carries
both. The readers take them from ``counters["end"]``, the snapshot at the
window's end, whole: a process's life up to there, which is set-up and, in a
good run, nothing else (``compiles_in_window`` is 0 or the run is not
correct; a recompile shows in the run's ``delta`` note by itself, as
``jit_compile_events``).

A program that reports none of the keys (the parent of PR 57, which the
driver runs traced with these readers laid over it) has nothing to read and
says so with 0, as ``layers.predates_phase_clock`` and
``turns.predates_turns`` do. A program that reports a key and counted
nothing (the recorder off: no phase is stamped; no cache in use: neither a
hit nor a miss) returns nothing. No reader raises.

The training cells hand the readers ``"counters": {}``
(``train_cell.run``), so the six are listed for the serving cells only.
"""

from __future__ import annotations

from typing import Any, Dict, Optional


def _end(ctx: Dict[str, Any]) -> Dict[str, Any]:
    return ctx.get("counters", {}).get("end", {})


def total(ctx: Dict[str, Any], *keys: str) -> Optional[float]:
    """The sum of ``keys`` at the window's end: 0 for a program that lacks
    one of them, nothing where all are there and none counted anything."""
    end = _end(ctx)
    if any(key not in end for key in keys):
        return 0
    return sum(end[key] for key in keys) or None


def cache_hit_share_percent(ctx: Dict[str, Any]) -> Optional[float]:
    """Persistent-cache hits over hits and misses, the process's whole
    life: 100 on a warm machine, 0 right after the cache was emptied."""
    end = _end(ctx)
    if "jit_cache_hits" not in end or "jit_cache_misses" not in end:
        return 0
    asked = end["jit_cache_hits"] + end["jit_cache_misses"]
    return 100.0 * end["jit_cache_hits"] / asked if asked else None
