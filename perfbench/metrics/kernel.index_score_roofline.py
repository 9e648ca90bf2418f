"""kernel.index_score_roofline (%): the index-score kernel's share of its
roofline. Least time (``indexed_work.score_least_seconds``): a step's rows
read their contexts' index keys and write a float32 score a token at the
memory's bandwidth; a chunk's (query, token) pairs, ``2 Hi Di`` operations
each, at the peak rate — counted by the program (``indexed_tokens_scored``,
``indexed_step_tokens_context``) over the window and brought to the traced
part of it (``sala_work.traced_share``). Time: the summed device time of the
events named ``index_score``. The kernel scores whole key tiles up to a
query tile's last position, the count only the pairs at or before each
query. A program without the counters or the kernel reads nothing. Layer:
kernels. Moves gap_p95_ms."""

from perfbench.lib import indexed_work


def read(ctx):
    return indexed_work.roofline_percent(
        ctx, indexed_work.score_least_seconds(ctx), indexed_work.SCORE)
