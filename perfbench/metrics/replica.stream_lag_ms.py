"""replica.stream_lag_ms (ms): mean time a token lies between the scheduler
thread's hand-off (``call_soon_threadsafe``) and the ``await q.get()`` of
the replica's event loop that receives it, over the tokens received in the
window (replica counters: delta of ``stream_lag_s`` over delta of
``stream_tokens``); 0 where none was received, and for a program that does
not stamp its tokens yet. Layer: handle, router and replica. Moves
serve_tokens_per_s."""

from perfbench.lib import layers


def read(ctx):
    return layers.mean_ms(ctx, "stream_lag_s", "stream_tokens")
