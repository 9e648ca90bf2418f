"""sched.host_share.moe: ``sched.host_share`` in the cell of the expert (MoE) configuration, which
reports ``gap_p95_ms`` (the same reader; see ``sched.host_share.py``). Layer:
scheduler. Moves gap_p95_ms."""

from perfbench.lib import manifest


def read(ctx):
    return manifest.metric_reader("sched.host_share")(ctx)
