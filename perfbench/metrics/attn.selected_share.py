"""attn.selected_share (%): of the tokens of their contexts, the share the
queries of the block-selected layers attended, over the window: the
program's counters ``sparse_tokens_attended`` over ``sparse_tokens_context``
(a query at or under ``dense_len`` counts its whole context; one at 33k
attends 19%). 100 means the dense path ran throughout. A program without the
counters reads nothing. Layer: kernels. Moves gap_p95_ms."""

from perfbench.lib import sala_work


def read(ctx):
    d = sala_work.window_counters(ctx)
    if d is None or not d.get("sparse_tokens_context"):
        return None
    return 100.0 * d["sparse_tokens_attended"] / d["sparse_tokens_context"]
