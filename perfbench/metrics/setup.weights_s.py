"""setup.weights_s (s): the replica's build phase ``setup.weights``
(``setup_weights_s``): the loader and ``device_put``, up to where they
RETURN; device work they leave running belongs to the phase that next waits.
A program without the build's clock reads 0; a clock that stamped nothing
(the recorder off) reads nothing. Layer: handle, router and replica. Moves
setup_s."""

from perfbench.lib import setup_work


def read(ctx):
    return setup_work.total(ctx, "setup_weights_s")
