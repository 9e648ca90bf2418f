"""retention.decode_step_roofline (%): the least time of a decode step over
the median device time of the program that ran it in the traced window.
Least time (``retention_work.decode_step_least_seconds``): the bytes a step
must move once at the memory's bandwidth — every layer's multiplied weights
and the output head read, and the live rows' retention states read and
written (the window's mean live rows a step, from ``retention_step_rows`` /
layers / d``decode_steps``). A model of this kind has no keys or values to
read: the step costs the same at any context.

By window, as ``moe.decode_step_roofline`` reads (``turn_work``): plain
steps only and mixed read the least time over the PLAIN step's median; one
in which every turn carried a chunk holds a plain step only if the turn is
two programs, as this model's is today; once it is fused
(``row_carrying_runs`` counts the chunk's runs that carried rows) the same
least time stands over the chunk program's median, a lower reading of the
same thing. A program without the counters or without a named program that
carried decode rows reads nothing. Layer: jitted step. Moves gap_p95_ms."""

from perfbench.lib import retention_work, turn_work


def read(ctx):
    if not ctx.get("trace"):
        return None
    least = retention_work.decode_step_least_seconds(ctx)
    hit = turn_work.runs(ctx)
    ran = hit["step"] or (hit["chunk"] if turn_work.row_carrying_runs(ctx)
                          else None)
    if not least or not ran or not ran.get("median_s"):
        return None
    return 100.0 * least / ran["median_s"]
