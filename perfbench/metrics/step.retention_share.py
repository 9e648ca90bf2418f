"""step.retention_share (%): the two power-retention kernels' share of the
device's busy time in the traced window: the summed device time of the
events named ``power_retention_step`` and ``power_retention_chunk`` over
``busy_s``. What is left is the weights' matrix products (the dense SwiGLU
most of all), the head, and the unnamed ops around the kernels (a step's
pairs of q and k are made outside its kernel). A trace without the kernels
reads nothing. Layer: jitted step. Moves gap_p95_ms."""

from perfbench.lib import retention_work, sala_work


def read(ctx):
    t = ctx.get("trace")
    if not t or not t.get("busy_s"):
        return None
    spent = sala_work.kernel_seconds(ctx, *retention_work.KERNELS)
    return 100.0 * spent / t["busy_s"] if spent else None
