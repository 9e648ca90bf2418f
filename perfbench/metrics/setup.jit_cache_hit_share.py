"""setup.jit_cache_hit_share (%): of the backend compiles that asked the
persistent cache, the share it served (``jit_cache_hits`` over hits +
``jit_cache_misses``), the process's whole life: 100 says the run was warm, by
hits and misses and not by a count of files. Nothing where neither was
counted; a program without the record reads 0. Layer: jitted step. Moves
setup_s."""

from perfbench.lib import setup_work


def read(ctx):
    return setup_work.cache_hit_share_percent(ctx)
