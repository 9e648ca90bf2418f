"""paging.window_held_share (%): the tokens the window layers' page pool held
over the tokens it would have held of the same sequences had nothing been
released, both sampled by the scheduler every turn, behind the turn's
releases and allocations (``window_tokens_held`` /
``window_tokens_unreleased``, the window's deltas). A slot holds there the
pages that cover its window and the chunk being written, whatever the
context, so the share falls as the contexts grow past the window: 100 means
no page was ever behind a window, and the pool's memory is the share of what
one kind of layer would have needed. Lower is better. A program without the
counters reads nothing. Layer: paging. Moves gap_p95_ms."""

from perfbench.lib import window_work


def read(ctx):
    return window_work.held_share_percent(ctx)
