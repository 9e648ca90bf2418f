"""step.decode_ms.moe: ``step.decode_ms`` in the cell of the expert (MoE) configuration, which
reports ``gap_p95_ms`` (the same reader; see ``step.decode_ms.py``). Layer:
jitted step. Moves gap_p95_ms."""

from perfbench.lib import manifest


def read(ctx):
    return manifest.metric_reader("step.decode_ms")(ctx)
