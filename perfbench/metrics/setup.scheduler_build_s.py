"""setup.scheduler_build_s (s): the replica's build phase ``setup.scheduler``
(``setup_scheduler_s``): the drafter, ``ContinuousScheduler`` and the
allocation of its page pools and states. A program without the build's clock
reads 0; a clock that stamped nothing reads nothing. Layer: paging. Moves
setup_s."""

from perfbench.lib import setup_work


def read(ctx):
    return setup_work.total(ctx, "setup_scheduler_s")
