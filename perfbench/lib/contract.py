"""The last line of a run: built here, checked here, printed only from here.

The driver reads the last line of the command's standard output as one JSON
object with the keys ``correct``, ``attempted``, ``failed``, ``metrics`` and
``device`` (and ``breakdown`` in a traced run). ``metrics`` holds every
metric ``BENCHMARK.json`` lists for the cell and the kind of run (end-to-end
with ``--trace 0``, per-layer with ``--trace 1``), each as a finite value
with its unit. ``device`` holds ``platform``, ``kind``, ``count`` and
``memory_peak_bytes`` and, in a traced run, ``window_s`` and ``busy_s`` with
``0 < busy_s <= window_s``.

``check_line`` returns the list of what is wrong with a line (empty: good).
``emit`` prints a line only if that list is empty; otherwise it raises
``ContractError`` and the command exits non-zero with the reasons on earlier
lines, so the driver never reads a line it cannot use. As a command,

    python -m perfbench.lib.contract --workload NAME --trace 0|1 < output

checks the last non-empty line of a recorded run (every chip run the
builder makes is piped through it).
"""

from __future__ import annotations

import argparse
import json
import math
import sys
from typing import Any, Dict, List, Optional

from perfbench.lib import manifest as manifest_lib

TOP_KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
BREAKDOWN_KEYS = ("device_ops", "idle_gaps")
BREAKDOWN_MAX = 10


class ContractError(Exception):
    """The line a run would print does not meet the contract."""

    def __init__(self, problems: List[str]):
        super().__init__("; ".join(problems))
        self.problems = problems


def _is_number(x: Any) -> bool:
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def _is_count(x: Any) -> bool:
    return isinstance(x, int) and not isinstance(x, bool) and x >= 0


def build_line(*, correct: bool, attempted: int, failed: int,
               metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
               breakdown: Optional[Dict[str, list]] = None,
               compared: Optional[Dict[str, Dict[str, float]]] = None
               ) -> Dict[str, Any]:
    """The object a run prints. ``metrics`` maps name -> {"value", "unit"};
    a value a reader could not produce is simply absent, and the check then
    names it. ``compared`` (name -> {"value", "limit"}: each number the
    run's ``correct`` was decided from, beside its limit) comes last; the
    driver ignores it and keeps it where a run was not correct."""
    line: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = {
            k: [[str(n), float(s)] for n, s in breakdown.get(k, [])
                ][:BREAKDOWN_MAX] for k in BREAKDOWN_KEYS}
    if compared is not None:
        line["compared"] = compared
    return line


def check_line(line: Any, manifest: Dict[str, Any], workload: str,
               traced: bool) -> List[str]:
    """Everything that is wrong with ``line`` (an object, or the text of
    one) as the last line of a run of ``workload``."""
    if isinstance(line, (str, bytes)):
        try:
            line = json.loads(line, parse_constant=_refuse_constant)
        except ValueError as e:
            return [f"the line is not JSON: {e}"]
    if not isinstance(line, dict):
        return ["the line is not a JSON object"]
    problems = [f"key {k!r} is missing" for k in TOP_KEYS if k not in line]
    if "breakdown" in line and not traced:
        problems.append("'breakdown' belongs to a traced run only")
    if "correct" in line and not isinstance(line["correct"], bool):
        problems.append("'correct' is not true or false")
    for k in ("attempted", "failed"):
        if k in line and not _is_count(line[k]):
            problems.append(f"{k!r} is not a count: {line[k]!r}")
    if (_is_count(line.get("attempted")) and _is_count(line.get("failed"))
            and line["failed"] > line["attempted"]):
        problems.append("more failed than attempted")
    if _is_count(line.get("attempted")) and line["attempted"] == 0:
        problems.append("nothing was attempted")

    cell = manifest_lib.workload(manifest, workload)
    wanted = manifest_lib.metrics_for(manifest, workload, traced)
    metrics = line.get("metrics")
    if "metrics" in line and not isinstance(metrics, dict):
        problems.append("'metrics' is not an object")
    elif isinstance(metrics, dict):
        for m in wanted:
            name = m["name"]
            got = metrics.get(name)
            if got is None:
                problems.append(f"metric {name!r} is listed for "
                                f"{workload!r} and has no value")
                continue
            if not isinstance(got, dict) or "value" not in got \
                    or "unit" not in got:
                problems.append(f"metric {name!r} is not "
                                "{'value': ..., 'unit': ...}")
                continue
            if not _is_number(got["value"]):
                problems.append(f"metric {name!r} is not a finite number: "
                                f"{got['value']!r}")
            elif not traced and got["value"] == 0:
                problems.append(f"end-to-end metric {name!r} reads 0")
            elif (m["unit"] == "%" and ("roofline" in name or "mfu" in name)
                  and got["value"] > 105):
                problems.append(f"{name!r} reads {got['value']}% of a peak")
            if got["unit"] != m["unit"]:
                problems.append(f"metric {name!r} has unit {got['unit']!r}, "
                                f"BENCHMARK.json says {m['unit']!r}")
        listed = {m["name"] for m in wanted}
        for name in metrics:
            if name not in listed:
                problems.append(f"metric {name!r} is not listed for "
                                f"{workload!r} in this kind of run")

    device = line.get("device")
    if "device" in line and not isinstance(device, dict):
        problems.append("'device' is not an object")
    elif isinstance(device, dict):
        for k in DEVICE_KEYS:
            if k not in device:
                problems.append(f"device.{k} is missing")
        for k in ("platform", "kind"):
            if k in device and not (isinstance(device[k], str) and device[k]):
                problems.append(f"device.{k} is not a name: {device[k]!r}")
        if "count" in device and device["count"] != cell["chips"]:
            problems.append(f"device.count is {device['count']!r}, the cell "
                            f"asks for {cell['chips']}")
        peak = device.get("memory_peak_bytes")
        if "memory_peak_bytes" in device and not (
                _is_count(peak) and peak > 0):
            problems.append(f"device.memory_peak_bytes is {peak!r}")
        if traced:
            w, b = device.get("window_s"), device.get("busy_s")
            if not (_is_number(w) and w > 0):
                problems.append(f"device.window_s is {w!r} in a traced run")
            if not (_is_number(b) and b > 0):
                problems.append(f"device.busy_s is {b!r} in a traced run "
                                "(no operation seen on the device)")
            if _is_number(w) and _is_number(b) and b > w:
                problems.append(f"device.busy_s {b} exceeds window_s {w}")

    bd = line.get("breakdown")
    if bd is not None:
        if not isinstance(bd, dict):
            problems.append("'breakdown' is not an object")
        else:
            for k in BREAKDOWN_KEYS:
                rows = bd.get(k)
                if not isinstance(rows, list) or len(rows) > BREAKDOWN_MAX:
                    problems.append(f"breakdown.{k} is not a list of at "
                                    f"most {BREAKDOWN_MAX}")
                    continue
                for row in rows:
                    if not (isinstance(row, list) and len(row) == 2
                            and isinstance(row[0], str)
                            and _is_number(row[1])):
                        problems.append(f"breakdown.{k} row {row!r} is not "
                                        "[name, seconds]")
                        break
    return problems


def _refuse_constant(name: str):
    raise ValueError(f"{name} is not a finite number")


def emit(line: Dict[str, Any], manifest: Dict[str, Any], workload: str,
         traced: bool, out=None) -> None:
    """Print ``line`` as the run's last line, or raise ``ContractError``."""
    problems = check_line(line, manifest, workload, traced)
    if not problems:
        try:
            text = json.dumps(line, allow_nan=False)
        except ValueError as e:
            problems = [f"the line cannot be written as JSON: {e}"]
    if problems:
        raise ContractError(problems)
    print(text, file=out or sys.stdout, flush=True)


def last_line(text: str) -> str:
    lines = [ln for ln in text.splitlines() if ln.strip()]
    return lines[-1] if lines else ""


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="check the last line of a recorded run (stdin)")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--manifest", default=None)
    args = ap.parse_args(argv)
    manifest = manifest_lib.load(args.manifest)
    problems = check_line(last_line(sys.stdin.read()), manifest,
                          args.workload, bool(args.trace))
    for p in problems:
        print(f"contract: {p}")
    print(f"contract: {args.workload} --trace {args.trace}: "
          + ("REFUSED" if problems else "ok"))
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
