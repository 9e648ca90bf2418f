"""A cell's set-up as a table: the program's own record beside the
benchmark's stamps (PR 57).

    python3 tests/perfbench/setup_table.py --workload W --seed N --seconds S --trace 0|1

The command itself (``perfbench/run.py: main``: same files, same notes, same
last line, same exit code) with one thing more. In a serving cell the
replica's ``scheduler_stats()`` is asked where the benchmark stamps its
set-up phases — once deployed, before and after the reference check — and
the window's end is the run's own ``counters["end"]``; what
``jit_programs`` gained between two snapshots is what that phase compiled.
In a training cell, whose record never reaches the command (``PERF.md`` 7),
the worker's one log line is taken from the run's logs. The table goes to
``chiprun_out/setup_table/<workload>.seed<N>.trace<T>.json`` and, on one
line, to standard error: standard output stays the command's.

The three extra calls cost set-up a few milliseconds; a run whose
``setup_s`` is to be compared is made with ``perfbench/run.py``.
"""

import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from perfbench import run  # noqa: E402
from perfbench.lib import serve_cell  # noqa: E402

STAGES = ("trace_s", "lower_s", "compile_s")
SNAPSHOTS = []  # (the phases a snapshot closes, scheduler_stats())
RESULT = {}     # the run's own setup_phases, once it has ended


def _deploy(ctx, deploy=serve_cell.deploy):
    send, call = deploy(ctx)
    SNAPSHOTS.append(("replica_build+deployed", call("scheduler_stats")))

    def snapshotting(method, *args):
        if method != "reference_check":
            return call(method, *args)
        SNAPSHOTS.append(("first_request", call("scheduler_stats")))
        out = call(method, *args)
        SNAPSHOTS.append(("reference_check", call("scheduler_stats")))
        return out

    return send, snapshotting


def _run(ctx, cell_run=serve_cell.run):
    result = cell_run(ctx)
    SNAPSHOTS.append(("warmup_traffic+window", result["counters"]["end"]))
    RESULT.update(setup_phases=result["setup_phases"])
    return result


def gained(before, after, most=12):
    """What the record gained between two snapshots: the totals' difference
    and the rows that moved, costliest first."""
    rows = {}
    was = before.get("jit_programs", {})
    for name, row in after.get("jit_programs", {}).items():
        old = was.get(name, {})
        d = {k: row[k] - old.get(k, 0) for k in row}
        if any(d.values()):
            rows[name] = d
    ranked = sorted(rows.items(),
                    key=lambda kv: -sum(kv[1][s] for s in STAGES))
    totals = {k: after[k] - before.get(k, 0) for k in after
              if k.startswith("jit_") and k != "jit_programs"}
    return {"totals": totals, "programs_moved": len(rows),
            "costliest": dict(ranked[:most])}


def table():
    out = {"setup_phases": RESULT.get("setup_phases"), "phases": {}}
    before = {}
    for phases, snapshot in SNAPSHOTS:
        out["phases"][phases] = gained(before, snapshot)
        before = snapshot
    if SNAPSHOTS:
        end = SNAPSHOTS[-1][1]
        out["build"] = {k: end[k] for k in end if k.startswith("setup_")}
        out["whole"] = gained({}, end, most=5)
    return out


def worker_log_lines(logs):
    found = []
    for name in sorted(os.listdir(logs)) if os.path.isdir(logs) else ():
        with open(os.path.join(logs, name), errors="replace") as f:
            found += [f"{name}: {ln.strip()}" for ln in f
                      if "compile record:" in ln]
    return found


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    serve_cell.deploy, serve_cell.run = _deploy, _run
    logs = os.environ.setdefault(
        "PERFBENCH_KEEP_LOGS", tempfile.mkdtemp(prefix="setup_table_logs"))
    code = run.main(argv)
    out = table()
    out["worker_log"] = worker_log_lines(logs)
    args = dict(zip(argv[::2], argv[1::2]))
    path = os.path.join(ROOT, "chiprun_out", "setup_table")
    os.makedirs(path, exist_ok=True)
    name = "{}.seed{}.trace{}.json".format(
        args.get("--workload"), args.get("--seed", 0),
        args.get("--trace", 0))
    with open(os.path.join(path, name), "w") as f:
        json.dump({"argv": argv, "exit_code": code, **out}, f, indent=1)
    print("setup_table: " + json.dumps(out), file=sys.stderr)
    return code


if __name__ == "__main__":
    sys.exit(main())
