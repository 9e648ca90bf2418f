"""latent.turn_roofline (%): the least time of the program the window's
turns ran — a prefill chunk with the live decode rows along — over that
program's median device time in the traced window: a share of the WHOLE
turn. Least time (``latent_work.turn_least_seconds``): the larger of its
bytes at the memory's bandwidth (every layer's weights once, all routed
experts and the shared one among them, the head, the latents and shared keys
its step rows attend) and its operations at the peak rate (its rows through
each layer's attention projections, the dense SwiGLU or the top-k and shared
experts, its chunk's (query, key) pairs absorbed), the mean run of the window
by the program's counters. A window none of whose turns carried a chunk, a
program without the counters or a trace without the chunk's program reads
nothing. Layer: jitted step. Moves gap_p95_ms."""

from perfbench.lib import latent_work


def read(ctx):
    return latent_work.turn_roofline_percent(ctx)
