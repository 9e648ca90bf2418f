"""kernel.retention_step_roofline (%): the power-retention step kernel's
share of its roofline. Least time (``retention_work.step_least_seconds``):
the live rows' states and normalisers (8256 rows of 129 float32 values a K/V
head, 8 a layer: 34.08 MB a row and layer) read once and written once at the
memory's bandwidth, counted by the program (``retention_step_rows`` = live
rows x layers) over the window and brought to the traced part by the traced
runs that CARRIED decode rows over d``decode_steps``. Time: the summed device
time of the events named ``power_retention_step``. The kernel also reads and
writes the states of rows that are not live (they come back bitwise) and
holds 8320 rows for the 8256: both lower the reading, as they should. A
program without the counters or the kernel reads nothing. Layer: kernels.
Moves gap_p95_ms."""

from perfbench.lib import retention_work, sala_work


def read(ctx):
    if not ctx.get("trace"):
        return None
    least = retention_work.step_least_seconds(ctx)
    spent = sala_work.kernel_seconds(ctx, retention_work.STEP)
    if not least or not spent:
        return None
    return 100.0 * least / spent
