"""step.picked_share (%): the share of the device's busy time in the traced
window that the picked latent attention takes END TO END: the summed device
time of the events named ``index_score``, ``indexed_select``,
``picked_latent_step_attention``, ``picked_latent_chunk_attention`` and of
every op whose name holds ``gather`` over ``busy_s``. A FLOOR: where XLA
makes the gather of the chosen rows a fusion without a name of its own (as
on the v5e, PERF.md 7, PR 61) its time lies in ``fusion`` and is not read
here. What is left is the weights' matrix products, the absorbing and
expanding products around the kernels and the unnamed ops. A trace without
the picked kernels reads nothing. Layer: jitted step. Moves
gap_p95_ms."""

from perfbench.lib import picked_work


def read(ctx):
    return picked_work.picked_share_percent(ctx)
