"""setup.jit_compile_s (s): the seconds the process that holds the chip spent
in the backend's compile of its jitted programs, a real compile or a load from
the persistent cache, summed up to the window's end (``jit_compile_s``). Warm
it is the loads; cold it is most of a first run's extra set-up. A program
without the record reads 0. Layer: jitted step. Moves setup_s."""

from perfbench.lib import setup_work


def read(ctx):
    return setup_work.total(ctx, "jit_compile_s")
