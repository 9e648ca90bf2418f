"""replica.stream_lag_ms.gap: ``replica.stream_lag_ms`` in the cells that report ``gap_p95_ms`` and not
``serve_tokens_per_s`` (the same reader; see ``replica.stream_lag_ms.py``). Moves gap_p95_ms."""

from perfbench.lib import manifest


def read(ctx):
    return manifest.metric_reader("replica.stream_lag_ms")(ctx)
