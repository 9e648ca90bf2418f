"""kernel.retention_chunk_roofline (%): the power-retention chunk kernel's
share of its roofline. Least time (``retention_work.chunk_least_seconds``):
a real token's operations a layer at the peak rate — its key's update of
its K/V head's state and every query head's product with it, ``(8 + 40) x 2
x 8256 x 128``, and the causal half of its block's scores and products —
plus the slot's state read and written once a layer-call at the memory's
bandwidth; counted by the program (``retention_chunk_tokens``,
``retention_chunk_calls``) over the window and brought to the traced part by
the chunk program's traced runs over d``prefill_chunks``. Time: the summed
device time of the events named ``power_retention_chunk``. What the kernel
spends on MAKING the pairs of a token (two selection matmuls a tile) is no
part of the least time: it is what the share is under 100 for. A program
without the counters or the kernel reads nothing. Layer: kernels. Moves
gap_p95_ms."""

from perfbench.lib import retention_work, sala_work


def read(ctx):
    if not ctx.get("trace"):
        return None
    least = retention_work.chunk_least_seconds(ctx)
    spent = sala_work.kernel_seconds(ctx, retention_work.CHUNK)
    if not least or not spent:
        return None
    return 100.0 * least / spent
