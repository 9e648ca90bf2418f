"""step.prefill_share.moe: ``step.prefill_share`` in the cell of the expert (MoE) configuration, which
reports ``gap_p95_ms`` (the same reader; see ``step.prefill_share.py``). Layer:
jitted step. Moves gap_p95_ms."""

from perfbench.lib import manifest


def read(ctx):
    return manifest.metric_reader("step.prefill_share")(ctx)
