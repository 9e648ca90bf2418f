"""kernel.ssm_chunk_roofline (%): the chunked state-space scan's share of its
roofline. Least time (``ssm_work.chunk_least_seconds``): the larger of the
real tokens' operations at the peak rate (a token and layer: the state read
and updated, 4 x 4096 x 128, and the causal half of a 128-token block's
scores and of their product with the values) and the bytes at the memory's
bandwidth (a token's x, B, C in and y out in the served type, the slot's scan
state read and written once a layer-call), counted by the program
(``ssm_chunk_tokens``, ``ssm_chunk_calls``) over the window and brought to
the traced part by the chunk program's traced runs over d``prefill_chunks``.
Time: the most the events named ``ssm_chunk_scan`` can have taken
(``ssm_work.kernel_seconds_at_most``: their own column of the trace's table
plus the asynchronous ``-done`` ops of the chunk's program), so the reading
is AT LEAST the kernel's share. The kernel works on whole blocks of 128 (a chunk's padding too) and makes the
running decays outside: both lower the reading, as they should. A program
without the counters or the kernel reads nothing. Layer: kernels. Moves
gap_p95_ms."""

from perfbench.lib import ssm_work


def read(ctx):
    return ssm_work.kernel_roofline_percent(ctx, ssm_work.CHUNK,
                                            ssm_work.chunk_least_seconds)
