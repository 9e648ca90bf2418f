"""sched.prefill_turn_ms.gap: ``sched.prefill_turn_ms`` in the cells that report ``gap_p95_ms`` and not
``serve_tokens_per_s`` (the same reader; see ``sched.prefill_turn_ms.py``). Moves gap_p95_ms."""

from perfbench.lib import manifest


def read(ctx):
    return manifest.metric_reader("sched.prefill_turn_ms")(ctx)
