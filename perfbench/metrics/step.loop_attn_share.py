"""step.loop_attn_share (%): the paged-attention kernel's share of the
device's busy time in the traced window of a LOOPED model: the summed device
time of the events named ``paged_attention`` over ``busy_s`` — whether the
four reads of the stack's weights or the 192 pools of the cache set the
step. A configuration without passes, or a trace without the kernel, reads
nothing. Layer: jitted step. Moves gap_p95_ms."""

from perfbench.lib import loop_work


def read(ctx):
    return loop_work.attention_share_percent(ctx)
