"""kernel.sparse_attn_roofline (%): the block-selected attention's share of
its roofline. Least time (``sala_work.sparse_least_seconds``): for a step
the chosen tokens' keys and values once a K/V head and the pooled keys of
its rows' contexts once, at the memory's bandwidth; for a chunk its queries'
scores over the pooled keys and their scores and values over the tokens they
chose, at the peak rate — counted by the program (``sparse_tokens_*``,
``sparse_step_tokens_*``: what a query attends is a function of its
position) over the window and brought to the traced part of it. Time: the
summed device time of the events named ``sparse_select`` and
``sparse_paged_attention`` (the top-k between them runs as unnamed ops and
is not in it: PERF.md 7). A program without the counters or the kernels
reads nothing. Layer: kernels. Moves gap_p95_ms."""

from perfbench.lib import sala_work


def read(ctx):
    if not ctx.get("trace"):
        return None
    least = sala_work.sparse_least_seconds(ctx)
    spent = sala_work.kernel_seconds(ctx, "sparse_select",
                                     "sparse_paged_attention")
    if least is None or not spent:
        return None
    return 100.0 * least / spent
