"""sched.host_share.gap: ``sched.host_share`` in the cells that report ``gap_p95_ms`` and not
``serve_tokens_per_s`` (the same reader; see ``sched.host_share.py``). Moves gap_p95_ms."""

from perfbench.lib import manifest


def read(ctx):
    return manifest.metric_reader("sched.host_share")(ctx)
