"""setup.jit_compile_events (programs): backend-compile events up to the
window's end (``jit_compile_events``): a COUNT of programs, a cache load being
an event too, so warm and cold read the same and every seed reads the same;
one more is a program the change added to set-up. A program without the
record reads 0. Layer: jitted step. Moves setup_s."""

from perfbench.lib import setup_work


def read(ctx):
    return setup_work.total(ctx, "jit_compile_events")
