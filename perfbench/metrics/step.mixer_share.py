"""step.mixer_share (%): the two mixers' kernels' share of the device's busy
time in the traced window: the summed device time of the events named
``linear_attention_chunk``, ``linear_attention_step``, ``sparse_select`` and
``sparse_paged_attention`` over ``busy_s``. What is left is the weights'
matrix products (the dense SwiGLU most of all) and the unnamed ops around
the kernels. A trace without the kernels reads nothing. Layer: jitted step.
Moves gap_p95_ms."""

from perfbench.lib import sala_work


def read(ctx):
    t = ctx.get("trace")
    if not t or not t.get("busy_s"):
        return None
    spent = sala_work.kernel_seconds(ctx, *sala_work.KERNELS)
    return 100.0 * spent / t["busy_s"] if spent else None
