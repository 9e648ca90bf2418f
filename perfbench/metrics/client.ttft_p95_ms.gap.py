"""client.ttft_p95_ms.gap: ``client.ttft_p95_ms`` in the cells that report ``gap_p95_ms`` and not
``serve_tokens_per_s`` (the same reader; see ``client.ttft_p95_ms.py``). Moves gap_p95_ms."""

from perfbench.lib import manifest


def read(ctx):
    return manifest.metric_reader("client.ttft_p95_ms")(ctx)
