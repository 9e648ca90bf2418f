"""sched.queue_wait_ms.gap: ``sched.queue_wait_ms`` in the cells that report ``gap_p95_ms`` and not
``serve_tokens_per_s`` (the same reader; see ``sched.queue_wait_ms.py``). Moves gap_p95_ms."""

from perfbench.lib import manifest


def read(ctx):
    return manifest.metric_reader("sched.queue_wait_ms")(ctx)
