"""The test-only entry: the command's ``main`` on the CPU, at a tiny size.

It shares all code with ``perfbench/run.py`` except the refusal of anything
but a TPU, reads the tiny manifest beside it, and stamps ``platform: cpu``
(the device is reported as JAX sees it). The CPU has no published peak, so a
stand-in is entered here, for the rehearsal only: what it yields is a
count, never a device number.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from perfbench import run  # noqa: E402
from perfbench.lib import peaks  # noqa: E402

TINY_MANIFEST = os.path.join(HERE, "tiny", "BENCHMARK.json")

if __name__ == "__main__":
    peaks.PEAKS.setdefault("cpu", {"flops_bf16": 1e12,
                                   "hbm_bytes_per_s": 1e11,
                                   "hbm_bytes": 1e10})
    sys.exit(run.main(sys.argv[1:], manifest_path=TINY_MANIFEST,
                      require_tpu=False))
