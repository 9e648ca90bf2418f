"""``rehearse.py`` over the fifth tiny manifest (``BENCHMARK_turns.json``:
the second one plus the six readers of the scheduler's own gaps), so the CPU
rehearsal runs those readers too. Same tiny tree, same stand-in peak, counts
only.

The trace goes to a directory of this caller's own. ``run.build_context``
clears and names ``<ROOT>/.perfbench_trace/<workload>``, which every
rehearsal of ``tiny_chat`` shares: the one that starts or ends first removes
what another is still writing ("the profiler wrote no trace"; ROADMAP R0).
So the root it builds that one path from is, for that call, a directory
under ``.perfbench_trace/`` that no other test file uses."""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from perfbench import run  # noqa: E402
from perfbench.lib import peaks  # noqa: E402

TURNS_MANIFEST = os.path.join(HERE, "tiny", "BENCHMARK_turns.json")
TRACE_ROOT = os.path.join(run.ROOT, ".perfbench_trace", "rehearse_turns")
_build_context = run.build_context


def build_context(args, manifest, require_tpu):
    root, run.ROOT = run.ROOT, TRACE_ROOT
    try:
        return _build_context(args, manifest, require_tpu)
    finally:
        run.ROOT = root


if __name__ == "__main__":
    peaks.PEAKS.setdefault("cpu", {"flops_bf16": 1e12,
                                   "hbm_bytes_per_s": 1e11,
                                   "hbm_bytes": 1e10})
    run.build_context = build_context
    sys.exit(run.main(sys.argv[1:], manifest_path=TURNS_MANIFEST,
                      require_tpu=False))
