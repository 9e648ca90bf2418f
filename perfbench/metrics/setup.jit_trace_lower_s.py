"""setup.jit_trace_lower_s (s): the seconds the process that holds the chip
spent TRACING its jitted programs and LOWERING them to a module, summed over
every program up to the window's end (``jit_trace_s`` + ``jit_lower_s`` of
``scheduler_stats()``). What the persistent cache cannot save: it is paid in
every process, warm or cold. A program without the record reads 0. Layer:
jitted step. Moves setup_s."""

from perfbench.lib import setup_work


def read(ctx):
    return setup_work.total(ctx, "jit_trace_s", "jit_lower_s")
