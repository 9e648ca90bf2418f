"""What the host did to a window: a note beside the counters, no metric.

A serving window now and then holds one turn of two or three seconds in
which the scheduler waits on a program (``stalls`` in the ``delta`` note;
PERF.md, section 6). To tell a pause of the whole machine from one of the
replica alone, the client's process, which only waits through the window,
waits in short slices and keeps every slice that came back late; both
processes read their own CPU seconds and full garbage collections before
and after. (Switches, faults and ``/proc/stat`` read nothing on the machine
with the chip.) Nothing here touches the program.
"""

from __future__ import annotations

import gc
import time
from typing import Dict, List

SLICE_S = 0.02
LATE_S = 0.1


def process_reading() -> Dict[str, float]:
    """This process: CPU seconds of all its threads, full collections."""
    return {"cpu_s": time.process_time(),
            "gc_full_collections": gc.get_stats()[2]["collections"]}


def delta(before: Dict[str, float], after: Dict[str, float]):
    return {k: after[k] - before[k] for k in after}


def sleep_until(deadline: float, t0: float, late: List[List[float]]) -> None:
    """Sleep to ``deadline`` (``time.perf_counter``) in slices; a slice that
    came back more than ``LATE_S`` late goes to ``late`` as [seconds into
    the window, seconds late]."""
    while True:
        now = time.perf_counter()
        if now >= deadline:
            return
        want = min(SLICE_S, deadline - now)
        time.sleep(want)
        over = time.perf_counter() - now - want
        if over > LATE_S:
            late.append([round(now - t0, 3), round(over, 3)])
