"""step.indexer_share (%): the indexer's and the selected attention's
kernels' share of the device's busy time in the traced window: the summed
device time of the events named ``index_score``, ``indexed_select``,
``indexed_chunk_attention`` and ``indexed_step_attention`` over ``busy_s``.
What is left is the weights' matrix products (the experts most of all), the
gathers of a slot's context out of its pages and the unnamed ops around the
kernels. A trace without the kernels reads nothing. Layer: jitted step.
Moves gap_p95_ms."""

from perfbench.lib import indexed_work, sala_work


def read(ctx):
    t = ctx.get("trace")
    if not t or not t.get("busy_s"):
        return None
    spent = sala_work.kernel_seconds(ctx, *indexed_work.KERNELS)
    return 100.0 * spent / t["busy_s"] if spent else None
