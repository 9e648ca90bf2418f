"""step.decode_ms (ms): median device time of one execution of the
scheduler's decode program in the traced window, found by its name
(``jit_paged_decode_step``; it agrees with the by-fingerprint median in the
run's ``program_runs`` note). A window without a decode step reads nothing
and the run is refused; a program that does not name its programs yet reads
0. Layer: jitted step. Moves serve_tokens_per_s."""

from perfbench.lib import layers

PROGRAM = "paged_decode_step"


def read(ctx):
    if not ctx.get("trace"):
        return None
    hit = layers.program(ctx, PROGRAM)
    if hit is None:
        return 0.0 if layers.predates_phase_clock(ctx) else None
    return 1e3 * hit["median_s"]
