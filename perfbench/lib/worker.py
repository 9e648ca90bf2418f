"""What runs only inside the process that holds the chip: the device stamp,
the refusal of anything but a TPU, peak memory, and the count of
compilations inside the measured window."""

from __future__ import annotations

import resource
from typing import Any, Dict


def device_stamp(require_tpu: bool) -> Dict[str, Any]:
    """The device as JAX reports it here. With ``require_tpu`` (always, in
    the command) anything else raises; the tests' rehearsal passes False and
    the stamp then says ``cpu``."""
    import jax

    devices = jax.devices()
    stamp = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices)}
    if require_tpu:
        from ray_tpu.ops._pallas import should_interpret

        if stamp["platform"] != "tpu":
            raise RuntimeError(f"the worker runs on {stamp['platform']!r}, "
                               "not on a TPU: nothing is measured")
        if should_interpret():
            raise RuntimeError("Pallas kernels are interpreted on the chip")
    return stamp


def memory_peak_bytes() -> int:
    """Peak bytes held on the fullest device: the peak of live arrays
    (``peak_bytes_in_use``) plus the peak the runtime reserved for the
    temporaries of running programs (``peak_bytes_reserved``; the TPU keeps
    them out of ``bytes_in_use`` — a train step with 10 GB of temporaries
    read 2.7 GB "in use"). On a backend that keeps no such count (the CPU of
    the rehearsal) the process's peak resident set."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()]
    peaks = [s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
             for s in stats]
    if max(peaks) > 0:
        return int(max(peaks))
    return int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss) * 1024


class CompileCounter:
    """Counts XLA compilations in this process (``jax.monitoring`` reports
    each backend compile); the window must see none."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if event == self.EVENT:
            self.count += 1
