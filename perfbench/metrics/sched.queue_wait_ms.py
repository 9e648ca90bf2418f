"""sched.queue_wait_ms (ms): mean time from ``submit`` to admission into a
slot over the requests admitted in the window (scheduler counters: delta of
``queue_wait_s`` over delta of ``admitted``); 0 where none was admitted, and
for a program that does not count it yet. With ``first_token_wait_s`` /
``first_tokens`` (in the run's ``delta`` note) it splits the client's time
to first token. Layer: scheduler. Moves serve_tokens_per_s."""

from perfbench.lib import layers


def read(ctx):
    return layers.mean_ms(ctx, "queue_wait_s", "admitted")
