"""sched.stall_share (%): time the scheduler's loop stood still — the part
beyond 1 s of every loop turn that took longer than 1 s while a slot was
live (delta of ``stall_s``) — over the scheduler thread's whole time in the
window (all phases, ``serve.park`` too). 0 is the healthy reading; the
phase that held a stall is ``stall_phase`` in ``scheduler_stats()`` and the
argument of the ``serve.stall`` flight instant. A clock that stood still
all window reads nothing; a program without the clock reads 0. Layer:
scheduler. Moves serve_tokens_per_s."""

from perfbench.lib import layers


def read(ctx):
    if layers.predates_phase_clock(ctx):
        return 0.0
    thread_s = sum(layers.phase_seconds(ctx).values())
    if thread_s <= 0:
        return None
    return 100.0 * ctx["counters"]["delta"].get("stall_s", 0.0) / thread_s
