"""sched.decode_turn_ms (ms): mean gap of the tokens emitted in the window
behind decode work only — the scheduler's own stamp at the read that emitted
a token less its stamp at the read that emitted the sequence's previous one,
where no prompt token was dispatched between the two programs that sampled
them (scheduler counters: delta of ``gap_plain_s`` over delta of
``gap_plain_tokens``). Keyed on what a turn carried, not on a program's
name. 0 for a program that does not count its gaps yet; nothing where the
window held no such token. Layer: scheduler. Moves serve_tokens_per_s."""

from perfbench.lib import turns


def read(ctx):
    return turns.mean_gap_ms(ctx, "plain")
