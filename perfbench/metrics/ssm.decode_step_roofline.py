"""ssm.decode_step_roofline (%): the least time of a decode step over the
median device time of the program that ran it in the traced window. Least
time (``ssm_work.decode_step_least_seconds``): the bytes a step must move
once at the memory's bandwidth — the Mamba-2 and attention layers'
projections, of every expert layer the router, the shared expert and the
HELD experts that received a row (the window's mean a layer-call:
``moe_experts_hit`` / ``moe_layer_calls``), the output head; the live rows'
two states a Mamba-2 layer read and written (the window's mean live rows a
step, from ``ssm_step_rows`` / d``decode_steps``); and the keys and values of
the live contexts (from the client's records, over the traced runs that
carried decode rows). By window as ``retention.decode_step_roofline`` reads:
the plain step's median, or the chunk program's where every traced turn
carried a chunk. A program without the counters or without a named program
that carried decode rows reads nothing. Layer: jitted step. Moves
gap_p95_ms."""

from perfbench.lib import ssm_work


def read(ctx):
    return ssm_work.decode_step_roofline_percent(ctx)
