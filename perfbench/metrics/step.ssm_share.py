"""step.ssm_share (%): the two state-space kernels' share of the device's
busy time in the traced window: the summed device time of the events named
``ssm_step`` and ``ssm_chunk_scan`` over ``busy_s``. What is left is the
experts' grouped products, the projections, the head, the paged attention of
the one attention layer, and the unnamed ops around the kernels (the
convolution over its carried inputs, the gate and its norm). The kernels'
column of the trace's table leaves out an asynchronous copy that falls
inside one of their events (``ssm_work.kernel_seconds_at_most``), so this
share is a floor where XLA overlaps such copies. A trace without
the kernels reads nothing. Layer: jitted step. Moves gap_p95_ms."""

from perfbench.lib import ssm_work


def read(ctx):
    return ssm_work.ssm_share_percent(ctx)
