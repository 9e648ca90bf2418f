"""sched.prefill_turn_ms (ms): mean gap of the tokens emitted in the window
beside prefill — as ``sched.decode_turn_ms``, for the tokens between whose
sampling program and the one before it the scheduler dispatched prompt
tokens (a chunk today, the prompt rows of one fused program after ROADMAP
S3): delta of ``gap_prefill_s`` over delta of ``gap_prefill_tokens``. 0 for
a program that does not count its gaps yet; nothing where the window held no
such token. Layer: scheduler. Moves serve_tokens_per_s."""

from perfbench.lib import turns


def read(ctx):
    return turns.mean_gap_ms(ctx, "prefill")
