"""sched.host_share (%): the share of the scheduler thread's working time in
which the HOST works and the device may wait for it — every phase but the
two in which the thread waits for the device (``serve.decode.wait``,
``serve.prefill.wait``), over every phase but ``serve.park`` (no work to
do); deltas of ``phase_*_s`` over the whole window. It should lie near the
cell's ``device.idle_share``: the device idles while the host prepares,
fetches, samples and emits (dispatch runs ahead only by the prefill chunk).
A clock that stood still all window reads nothing; a program without the
clock reads 0. Layer: scheduler. Moves serve_tokens_per_s."""

from perfbench.lib import layers


def read(ctx):
    if layers.predates_phase_clock(ctx):
        return 0.0
    phases = layers.phase_seconds(ctx)
    working = sum(v for k, v in phases.items() if k != layers.PARK)
    if working <= 0:
        return None
    waiting = sum(phases.get(k, 0.0) for k in layers.DEVICE_WAITS)
    return 100.0 * (working - waiting) / working
