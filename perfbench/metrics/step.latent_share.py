"""step.latent_share (%): the latent attention kernels' share of the
device's busy time in the traced window: the summed device time of the
events named ``latent_step_attention`` and ``latent_chunk_attention`` over
``busy_s``. What is left is the weights' matrix products (the experts most of
all), the absorbing and expanding products around the kernels and the unnamed
ops. A trace without the kernels reads nothing. Layer: jitted step. Moves
gap_p95_ms."""

from perfbench.lib import latent_work


def read(ctx):
    return latent_work.latent_share_percent(ctx)
