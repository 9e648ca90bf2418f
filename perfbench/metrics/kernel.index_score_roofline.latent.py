"""kernel.index_score_roofline.latent (%): the index-score kernel's share of
its roofline in a model whose indexer sits INSIDE latent attention (the
counters are the kind's own, ``picked_index_pairs`` and a step's share, and
the sizes the source's flat keys ``index_n_heads`` / ``index_head_dim``:
``kernel.index_score_roofline`` reads ``sa_config`` and the K/V form's
counters). Least time (``picked_work.score_least_seconds``): a step's rows
read their contexts' index keys and write a float32 score a token at the
memory's bandwidth; a chunk's (query, token) pairs, ``2 Hi Di`` operations
each, at the peak rate. Time: the most the events named ``index_score`` can
have taken. A program without the counters or the kernel reads nothing.
Layer: kernels. Moves gap_p95_ms."""

from perfbench.lib import picked_work


def read(ctx):
    return picked_work.roofline_percent(
        ctx, picked_work.score_least_seconds(ctx), picked_work.SCORE)
