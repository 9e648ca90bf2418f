"""own.worker_start_s (s): the ``ray_tpu.init()`` call to the first line
executed inside the worker that holds the chip (cluster start, lease, worker
spawn). Layer: process and device ownership. Moves setup_s."""


def read(ctx):
    return ctx["clock"].get("worker_start_s")
