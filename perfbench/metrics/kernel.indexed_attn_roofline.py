"""kernel.indexed_attn_roofline (%): the share of its roofline of the
attention over the tokens the indexer picked. Least time
(``indexed_work.attention_least_seconds``): a step reads the keys and values
of the tokens its rows attend once at the memory's bandwidth; a chunk's
(query, attended token) pairs, ``4 H D`` operations each, at the peak rate —
counted by the program (``indexed_tokens_attended``,
``indexed_step_tokens_attended``: what a query attends is a function of its
position) over the window and brought to the traced part of it. Time: the
summed device time of the events named ``indexed_chunk_attention`` and
``indexed_step_attention`` (the step's sort and gather before its kernel run
as unnamed ops and are not in it: PERF.md 7). The chunk's kernel runs over
the slot's whole context with the choice as a mask, so it reads low by
construction: about the share of the context that is attended, times the
kernel's own efficiency. A program without the counters or the kernels reads
nothing. Layer: kernels. Moves gap_p95_ms."""

from perfbench.lib import indexed_work


def read(ctx):
    return indexed_work.roofline_percent(
        ctx, indexed_work.attention_least_seconds(ctx), indexed_work.CHUNK,
        indexed_work.STEP)
