"""kernel.window_attn_roofline (%): the windowed paged-attention call's share
of its roofline, in the layers of kind ``sliding_attention``. Least time
(``window_work.attention_least_seconds``): what the mask admits, counted by
the program from the cursors (``window_attn_step_keys``: the keys a decode
row reads, ``min(context, window)`` a row and layer, their keys and values
read once at the memory's bandwidth; ``window_attn_chunk_pairs``: the
(query, key) pairs of a chunk's real rows, ``4 x head_dim`` operations a pair
and query head at the peak rate), over the window and brought to the traced
part by ``sala_work.traced_share``, so a fused turn keeps both halves. Time:
the summed device time of the events named ``window_attention`` (the
windowed call's own ``name=``; the full layers' call keeps
``paged_attention``). The kernel streams whole blocks of 512 tokens, of
which the window's first is partly behind it: that lowers the reading, as it
should. A program without the counters or the kernel reads nothing. Layer:
kernels. Moves gap_p95_ms."""

from perfbench.lib import window_work


def read(ctx):
    return window_work.attention_roofline_percent(ctx, "window")
