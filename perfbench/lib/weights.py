"""Weights from ``--seed``: made on the device, in one jitted call, in the
type they are served in. The parameter tree is the program's own
(``init_params`` lays it out); only the key and the call are ours. The
``rbg`` generator is used because it runs at memory speed on a TPU, where
the default counter-based one spends seconds on billions of values."""

from __future__ import annotations


def key_for(seed: int, impl=None):
    """A key from any whole-number seed (the driver's pass 2**31)."""
    import jax

    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl=impl)
    return jax.random.fold_in(key, seed >> 31)


def make_params(cfg, seed: int):
    import jax

    from ray_tpu.models.transformer import init_params

    return jax.jit(lambda k: init_params(cfg, k))(key_for(seed, "rbg"))
