"""attn.indexed_share (%): of the tokens of their contexts, the share the
queries of the token-selected layers attended, over the window: the
program's counters ``indexed_tokens_attended`` over
``indexed_tokens_context`` (a query within ``topk`` of its sequence's start
counts its whole context; one at 49k attends 4%). 100 means no query ever
selected. A program without the counters reads nothing. Layer: kernels.
Moves gap_p95_ms."""

from perfbench.lib import indexed_work


def read(ctx):
    d = indexed_work.window_counters(ctx)
    if d is None:
        return None
    return (100.0 * d.get("indexed_tokens_attended", 0)
            / d["indexed_tokens_context"])
