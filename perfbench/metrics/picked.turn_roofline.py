"""picked.turn_roofline (%): the least time of the program the window's
turns ran — a prefill chunk with the live decode rows along — over that
program's median device time in the traced window: a share of the WHOLE
turn. Least time (``picked_work.turn_least_seconds``): the larger of its
bytes at the memory's bandwidth (every layer's weights AS HELD once, the
head, the index keys its rows score once, the chosen latent rows once a
query) and its operations at the peak rate (its rows through each layer's
weights, its index pairs and its chosen pairs), the mean run of the window
by the program's counters. A window none of whose turns carried a chunk, a
program without the counters or a trace without the chunk's program reads
nothing. Layer: jitted step. Moves gap_p95_ms."""

from perfbench.lib import picked_work


def read(ctx):
    return picked_work.turn_roofline_percent(ctx)
