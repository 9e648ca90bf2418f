"""step.prefill_share (%): summed device time of the prefill program's
executions (``jit_paged_prefill_chunk``) over that of prefill and decode
(``jit_paged_decode_step``) together, in the traced window, first device. A
window without a prefill chunk reads 0; one without a decode step reads
nothing and the run is refused; a program that does not name its programs
yet reads 0. Layer: jitted step. Moves serve_tokens_per_s."""

from perfbench.lib import layers

PREFILL, DECODE = "paged_prefill_chunk", "paged_decode_step"


def read(ctx):
    if not ctx.get("trace"):
        return None
    decode = layers.program(ctx, DECODE)
    if decode is None:
        return 0.0 if layers.predates_phase_clock(ctx) else None
    prefill = layers.program(ctx, PREFILL)
    prefill_s = prefill["sum_s"] if prefill else 0.0
    return 100.0 * prefill_s / (prefill_s + decode["sum_s"])
