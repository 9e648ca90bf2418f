"""kernel.linear_attn_roofline (%): the linear-attention kernels' share of
their roofline. Least time (``sala_work.linear_least_seconds``): the chunked
scan's operations at the peak rate (a layer-call of a 512 chunk: 4 blocks x
32 heads x four products of 128 x 128 x 128) and a step's live states read
and written once in float32 at the memory's bandwidth, counted by the
program (``linear_chunk_calls``, ``linear_step_rows``) over the window and
brought to the traced part by the share of the window's programs the trace
holds. Time: the summed device time of the events named
``linear_attention_chunk`` and ``linear_attention_step``. A program without
the counters or the kernels reads nothing. Layer: kernels. Moves
gap_p95_ms."""

from perfbench.lib import sala_work


def read(ctx):
    if not ctx.get("trace"):
        return None
    least = sala_work.linear_least_seconds(ctx)
    spent = sala_work.kernel_seconds(ctx, "linear_attention_chunk",
                                     "linear_attention_step")
    if least is None or not spent:
        return None
    return 100.0 * least / spent
