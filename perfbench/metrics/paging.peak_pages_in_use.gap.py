"""paging.peak_pages_in_use.gap: ``paging.peak_pages_in_use`` in the cells that report ``gap_p95_ms`` and not
``serve_tokens_per_s`` (the same reader; see ``paging.peak_pages_in_use.py``). Moves gap_p95_ms."""

from perfbench.lib import manifest


def read(ctx):
    return manifest.metric_reader("paging.peak_pages_in_use")(ctx)
