"""``rehearse.py`` over the fourth tiny manifest (``BENCHMARK_sala.json``:
the third one plus a toy-size MiniCPM-SALA configuration, its cell ``tiny_longdoc``
and the per-layer metrics of its two mixers), so the CPU
rehearsal runs layers of two kinds through the served path and those readers
too. Same tiny tree, same stand-in peak, counts only."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from perfbench import run  # noqa: E402
from perfbench.lib import peaks  # noqa: E402

SALA_MANIFEST = os.path.join(HERE, "tiny", "BENCHMARK_sala.json")

if __name__ == "__main__":
    peaks.PEAKS.setdefault("cpu", {"flops_bf16": 1e12,
                                   "hbm_bytes_per_s": 1e11,
                                   "hbm_bytes": 1e10})
    sys.exit(run.main(sys.argv[1:], manifest_path=SALA_MANIFEST,
                      require_tpu=False))
