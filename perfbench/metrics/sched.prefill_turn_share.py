"""sched.prefill_turn_share (%): of the tokens emitted in the window that
have a gap (all but each sequence's first), the share whose gap held prefill
work: delta of ``gap_prefill_tokens`` over delta of ``gap_plain_tokens`` +
``gap_prefill_tokens``. The variable that decides which of the two turns a
percentile of the gaps reads. 0 for a program that does not count its gaps
yet; nothing where the window held no gap. Layer: scheduler. Moves
serve_tokens_per_s."""

from perfbench.lib import turns


def read(ctx):
    return turns.prefill_share_percent(ctx)
