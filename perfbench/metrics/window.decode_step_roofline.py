"""window.decode_step_roofline (%): the least time of a decode step over the
median device time of the program that ran it in the traced window, for a
model with window layers beside full ones under experts. Least time
(``window_work.decode_step_least_seconds``): the bytes a step must read once
at the memory's bandwidth — every layer's attention weights (heads of the
configuration's own ``head_dim``), router and norms, the experts that
received a row (``moe_experts_hit`` / ``moe_layer_calls``, experts of
``moe_intermediate_size``), the output head, and the mean step's keys and
values: the full layers' at the live contexts, the window layers' at
``min(context, window)`` (the program's two ``*_step_keys`` over
d``decode_steps``).

By window, as ``moe.decode_step_roofline`` reads (``turn_work``): the PLAIN
step's median where the trace holds one; where every turn carried a chunk,
the chunk program's median if it carried decode rows
(``row_carrying_runs``), a lower reading of the same thing. (This cell is
not on ``moe.decode_step_roofline``'s list: ``moe_work`` takes
``intermediate_size`` for an expert's width and ``hidden_size /
num_attention_heads`` for the head size, both wrong for this model.) A
program without the counters or without a named program that carried decode
rows reads nothing. Layer: jitted step. Moves gap_p95_ms."""

from perfbench.lib import turn_work, window_work


def read(ctx):
    if not ctx.get("trace"):
        return None
    least = window_work.decode_step_least_seconds(ctx)
    hit = turn_work.runs(ctx)
    ran = hit["step"] or (hit["chunk"] if turn_work.row_carrying_runs(ctx)
                          else None)
    if not least or not ran or not ran.get("median_s"):
        return None
    return 100.0 * least / ran["median_s"]
