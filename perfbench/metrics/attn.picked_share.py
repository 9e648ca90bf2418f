"""attn.picked_share (%): of the causal (query, key) pairs of the layers
whose indexer picks latents, the share attended, over the window: the
program's counters ``picked_chosen_pairs`` over ``picked_index_pairs`` (a
query within ``index_topk`` of its sequence's start counts its whole
context; one at 40k attends 5%). 100 means no query ever selected. A program
without the counters reads nothing. Layer: kernels. Moves gap_p95_ms."""

from perfbench.lib import picked_work


def read(ctx):
    return picked_work.chosen_share_percent(ctx)
