"""sched.decode_turn_ms.gap: ``sched.decode_turn_ms`` in the cells that report ``gap_p95_ms`` and not
``serve_tokens_per_s`` (the same reader; see ``sched.decode_turn_ms.py``). Moves gap_p95_ms."""

from perfbench.lib import manifest


def read(ctx):
    return manifest.metric_reader("sched.decode_turn_ms")(ctx)
