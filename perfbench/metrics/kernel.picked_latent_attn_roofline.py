"""kernel.picked_latent_attn_roofline (%): the share of its roofline of the
attention over the latents an indexer PICKED (absorbed), the step's kernel
and the chunk's together. Least time
(``picked_work.attention_least_seconds``): a chosen (query, key) pair's row,
1,152 B a layer, read ONCE a query at the memory's bandwidth, or its heads'
``H (2 (rank + rope) + 2 rank)`` operations (2,176 a head) at the peak rate,
whichever is longer (at 128 heads the two are within a hundredth) — counted
by the program (``picked_chosen_pairs``, ``picked_step_chosen_pairs``: what a
query attends is ``min(t + 1, topk)``, a function of its position) over the
window and brought to the traced part of it. Time: the MOST the events named
``picked_latent_step_attention`` and ``picked_latent_chunk_attention`` can
have taken (``ssm_work.kernel_seconds_at_most``), so the share is a floor and
never passes what the kernel reached. The gather that brings the rows
together is not in it (``step.picked_share`` has it). ONE metric over both
names, as ``kernel.latent_attn_roofline``. A program without the counters or
the kernels reads nothing. Layer: kernels. Moves gap_p95_ms."""

from perfbench.lib import picked_work


def read(ctx):
    return picked_work.roofline_percent(
        ctx, picked_work.attention_least_seconds(ctx), picked_work.STEP,
        picked_work.CHUNK)
