"""device.idle_share.gap: ``device.idle_share.serve`` in the cells that
report ``gap_p95_ms`` and not ``serve_tokens_per_s`` (the same reader): the
idle time between two decode steps is part of every gap. Moves gap_p95_ms."""

from perfbench.lib import manifest


def read(ctx):
    return manifest.metric_reader("device.idle_share.serve")(ctx)
