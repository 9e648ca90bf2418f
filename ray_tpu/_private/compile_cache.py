"""Where compiled XLA programs are kept between processes and between runs,
and what this process spent making or loading them.

The cache is placed from OUTSIDE: if ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it and this module sets nothing. Only if it is not set does the
cache go to one fixed directory inside the checkout (git ignores it). The
directory is part of the cache key, so it is never built from a temp name,
a pid, a session directory or the time — a cache that moves never hits.

``enable()`` is called where a process first becomes a device process
(chip-holding worker start-up, ``bench.py``, ``chip_smoke.py``); every
process it starts inherits the variable through its environment.

The compile record. ``watch()`` listens to what JAX reports of every jitted
program (``jax.monitoring``: ``/jax/core/compile/jaxpr_trace_duration``,
``jaxpr_to_mlir_module_duration`` and ``backend_compile_duration``, each
with ``fun_name=``, and the persistent cache's ``cache_hits``,
``cache_misses`` and ``compile_time_saved_sec`` inside the last) and keeps,
process-wide and by function name, a row of ``n`` (backend-compile events: a
real compile or a cache load), ``trace_s``, ``lower_s``, ``compile_s``,
``cache_hits``, ``cache_misses`` and ``cache_saved_s``. A listener runs only
while something compiles, so the record is always on. ``record()`` is the
table and its totals: ``LLMServerImpl.scheduler_stats()`` carries it, the
``profile`` RPC's kind ``compiles`` returns it for any worker.

JAX sends a stage's name as the stage starts (a scalar) and its seconds as it
ends, on the thread that compiles, so a thread's open stages are a stack:
  * a trace inside a trace (every ``jnp`` function is a jitted function, and
    is traced inside the program that calls it) is the outer trace's time and
    no row of its own;
  * any other stage inside a stage (an eager operation met while tracing,
    which compiles) is its own row, and its seconds are taken off the
    enclosing stage's, so the totals never count a second twice;
  * a cache event, which carries no name, goes to the backend compile that
    encloses it on its thread.
Each recorded stage is also a ``flight`` SPAN named ``jit.trace <name>``,
``jit.lower <name>`` or ``jit.compile <name>`` (end = the listener's call,
start = end less the duration) and, while a ``jax.profiler`` session is
open, a host event of that session under the same name: a compile inside a
traced window labels its idle gap with the program's name.
"""

from __future__ import annotations

import os
import sys
import threading
from collections import defaultdict
from typing import Any, Dict, List

from ray_tpu._private import flight

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


# ---------------------------------------------------------- compile record

# the event JAX sends -> (the row's column, the span's name)
_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": ("trace_s", "jit.trace"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("lower_s", "jit.lower"),
    "/jax/core/compile/backend_compile_duration":
        ("compile_s", "jit.compile"),
}
_TRACE, _COMPILE = "trace_s", "compile_s"
_CACHE_COUNTS = {"/jax/compilation_cache/cache_hits": "cache_hits",
                 "/jax/compilation_cache/cache_misses": "cache_misses"}
_CACHE_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
COLUMNS = ("n", "trace_s", "lower_s", "compile_s", "cache_hits",
           "cache_misses", "cache_saved_s")
# the totals beside the table, in the columns' order
TOTALS = ("jit_compile_events", "jit_trace_s", "jit_lower_s",
          "jit_compile_s", "jit_cache_hits", "jit_cache_misses",
          "jit_cache_saved_s")
ORPHAN = "?"  # a cache event no backend compile encloses: never seen

_lock = threading.Lock()
_rows: Dict[str, Dict[str, Any]] = defaultdict(
    lambda: dict.fromkeys(COLUMNS, 0))
_tls = threading.local()
_watching = False


class _Stage:
    """One open stage of a thread: its column, the seconds of the stages
    recorded inside it, its host event, and (a backend compile) the cache
    events met so far."""

    __slots__ = ("column", "inner_s", "event", "cache")

    def __init__(self, column: str, event):
        self.column = column
        self.inner_s = 0.0
        self.event = event
        self.cache = None


def _bare(fun_name: str) -> str:
    """The function's own name: tracing reports ``f``, lowering and the
    backend ``jit(f)``."""
    if fun_name.startswith("jit(") and fun_name.endswith(")"):
        return fun_name[4:-1]
    return fun_name


def _stack() -> List[_Stage]:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def _on_start(event: str, value, fun_name: str = "", **_) -> None:
    stage = _STAGES.get(event)
    if stage is None:
        return
    column, span = stage
    stack = _stack()
    host_event = None
    folded = bool(stack) and column == _TRACE == stack[-1].column
    if not folded:
        annotate = flight._profiler_annotation()
        if annotate is not None:
            host_event = annotate(f"{span} {_bare(str(fun_name))}")
            host_event.__enter__()
    stack.append(_Stage(column, host_event))


def _on_duration(event: str, duration_secs: float, fun_name: str = "",
                 **_) -> None:
    stage = _STAGES.get(event)
    if stage is None:
        if event == _CACHE_SAVED:
            _on_cache("cache_saved_s", duration_secs)
        return
    column, span = stage
    stack = _stack()
    # a stage that opened before watch() did has no entry: nothing inside
    # it was heard either
    own = stack.pop() if stack and stack[-1].column == column else None
    if own is not None and own.event is not None:
        own.event.__exit__(None, None, None)
    outer = stack[-1] if stack else None
    if outer is not None:
        if column == _TRACE == outer.column:
            # a function traced inside a function: the outer's time, less
            # what was recorded inside this one
            outer.inner_s += own.inner_s if own is not None else 0.0
            return
        outer.inner_s += duration_secs
    name = _bare(str(fun_name))
    t1 = flight.now()
    flight.span_between(flight.intern(f"{span} {name}"),
                        t1 - int(duration_secs * 1e9), t1)
    with _lock:
        row = _rows[name]
        if own is not None:
            duration_secs = max(duration_secs - own.inner_s, 0.0)
        row[column] += duration_secs
        if column == _COMPILE:
            row["n"] += 1
            for key, value in ((own and own.cache) or {}).items():
                row[key] += value


def _on_event(event: str, **_) -> None:
    column = _CACHE_COUNTS.get(event)
    if column is not None:
        _on_cache(column, 1)


def _on_cache(column: str, value) -> None:
    for stage in reversed(_stack()):
        if stage.column == _COMPILE:
            if stage.cache is None:
                stage.cache = {}
            stage.cache[column] = stage.cache.get(column, 0) + value
            return
    with _lock:
        _rows[ORPHAN][column] += value


def watch() -> bool:
    """Start this process's compile record, once: called where the program
    first becomes a JAX process, and never the reason a process imports JAX
    (False in a process that has not, and nothing is registered)."""
    global _watching
    if _watching:
        return True
    if "jax" not in sys.modules:
        return False
    from jax import monitoring

    with _lock:
        if _watching:
            return True
        monitoring.register_scalar_listener(_on_start)
        monitoring.register_event_duration_secs_listener(_on_duration)
        monitoring.register_event_listener(_on_event)
        _watching = True
    return True


def record() -> Dict[str, Any]:
    """The totals (``TOTALS``) and ``jit_programs``: function name -> row
    (``COLUMNS``). All zeros and an empty table before ``watch()``."""
    with _lock:
        programs = {name: dict(row) for name, row in _rows.items()}
    out: Dict[str, Any] = {
        total: sum(row[column] for row in programs.values())
        for total, column in zip(TOTALS, COLUMNS)}
    out["jit_programs"] = programs
    return out


def summary(top: int = 5) -> str:
    """The record on one line, for a log: the totals and the ``top`` rows
    that cost most."""
    rec = record()
    rows = sorted(rec["jit_programs"].items(),
                  key=lambda kv: -(kv[1]["trace_s"] + kv[1]["lower_s"]
                                   + kv[1]["compile_s"]))[:top]
    totals = " ".join(
        f"{k}={rec[k]:.3f}" if isinstance(rec[k], float) else f"{k}={rec[k]}"
        for k in TOTALS)
    costliest = "; ".join(
        f"{name} n={r['n']} trace={r['trace_s']:.3f} lower={r['lower_s']:.3f}"
        f" compile={r['compile_s']:.3f} hits={r['cache_hits']}"
        f" misses={r['cache_misses']}" for name, r in rows)
    return f"compile record: {totals} | costliest: {costliest}"
