"""kernel.latent_attn_roofline (%): the share of its roofline of the paged
latent attention (MLA, absorbed), the step's kernel and the chunk's together.
Least time (``latent_work.attention_least_seconds``): a step's rows read the
latents and shared keys of their contexts once, 1,152 B a token and layer,
at the memory's bandwidth (37.8 operations a byte: the bytes bound it); a
chunk's (query, key) pairs, ``H (2 (rank + rope) + 2 rank)`` operations each,
at the peak rate — counted by the program (``latent_step_tokens_context``,
``latent_chunk_pairs``: what a query attends is a function of its position)
over the window and brought to the traced part of it. Time: the summed device
time of the events named ``latent_step_attention`` and
``latent_chunk_attention``. ONE metric over both names, as PR 51 folded its
two: a 3 s window of this traffic can pass without a live decode row (a
60k-token document is 118 chunks, behind which the re-asks queue), and a
metric that then finds no step kernel would fail the line. A step's 20 query
rows fill 20/128 of an MXU pass, which puts that kernel at the knee; the
yardstick counts required work at the published peaks all the same. A
program without the counters or the kernels reads nothing. Layer: kernels.
Moves gap_p95_ms."""

from perfbench.lib import latent_work


def read(ctx):
    return latent_work.attention_roofline_percent(ctx)
