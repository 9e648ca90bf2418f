"""From a profiler trace to numbers: the one reduction every PR shares.

``record()`` wraps ``jax.profiler`` in the process that holds the chip;
``load()`` turns the ``.xplane.pb`` it wrote into a plain ``Trace`` (lists of
``(name, start_ns, duration_ns)``); ``summarize()`` reduces a ``Trace`` to
the JSON-able summary the per-layer metric readers select from. The
arithmetic lives in ``summarize`` and works on the plain form, so the tests
check it on hand-made events as well as on a recorded trace.

What a trace looks like (looked at by hand first, on the v5e and on the CPU):
  * TPU: one plane ``/device:TPU:<n>`` per chip, with a line ``XLA Modules``
    (one event per execution of a jitted program, named
    ``jit_<function>(<fingerprint>)``) and a line ``XLA Ops`` (one event per
    HLO op, nested where an op such as ``while`` encloses others).
  * CPU (the tests' rehearsal): no device plane; the ops are the events of
    ``/host:CPU`` that carry an ``hlo_op`` stat, with ``hlo_module``,
    ``run_id`` and ``device_ordinal`` beside it.
  * ``/host:CPU`` lines are the host's threads; their events (runtime
    TraceMe spans) say what the host was doing in a device gap.

Definitions:
  window_s   first device event's start to the last one's end, all devices
  busy_s     union of the op intervals of one device; mean over devices
             (never a sum, which could pass the window)
  exposed collective time   union of collective-op intervals minus the union
             of the other leaf ops' intervals, per device; mean over devices
"""

from __future__ import annotations

import glob
import os
import re
import statistics
from bisect import bisect_left, bisect_right
from typing import Any, Dict, List, Optional, Tuple

Event = Tuple[str, int, int]  # name, start_ns, duration_ns

COLLECTIVE_PREFIXES = ("all-reduce", "all-gather", "reduce-scatter",
                       "collective-permute", "all-to-all")
MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
_LABELLED_GAPS = 200  # the longest gaps are labelled one by one


def start(log_dir: str) -> None:
    """Start tracing this process's devices. The Python tracer is off: it
    slows a host-bound loop and would be read as idle time."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=options)


def stop(log_dir: str) -> str:
    """Stop tracing; returns the path of the ``.xplane.pb`` written."""
    import jax

    jax.profiler.stop_trace()
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not found:
        raise RuntimeError(f"the profiler wrote no trace under {log_dir}")
    return found[-1]


# --------------------------------------------------------------------------
# .xplane.pb -> Trace


def program_name(event_name: str) -> str:
    """``jit_step_fn(1234)`` -> ``jit_step_fn``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def load(path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: List[Dict[str, Any]] = []
    host: List[Event] = []
    host_plane = None
    for plane in data.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            dev = {"name": plane.name, "ops": [], "programs": []}
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    dev["programs"] = [
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events]
                elif line.name == OPS_LINE:
                    dev["ops"] = [(e.name, int(e.start_ns),
                                   int(e.duration_ns)) for e in line.events]
            if dev["ops"] or dev["programs"]:
                devices.append(dev)
        elif plane.name == "/host:CPU":
            host_plane = plane
    by_ordinal: Dict[int, Dict[str, Any]] = {}
    if host_plane is not None:
        for line in host_plane.lines:
            for e in line.events:
                dur = int(e.duration_ns)
                if dur <= 0:
                    continue
                if devices:
                    host.append((e.name, int(e.start_ns), dur))
                    continue
                stats = dict(e.stats)
                if "hlo_op" not in stats:
                    host.append((e.name, int(e.start_ns), dur))
                    continue
                ordinal = int(stats.get("device_ordinal", 0))
                dev = by_ordinal.setdefault(ordinal, {
                    "name": f"/host:CPU device {ordinal}", "ops": [],
                    "runs": {}})
                dev["ops"].append((e.name, int(e.start_ns), dur))
                key = (stats.get("hlo_module", "?"), stats.get("run_id", 0))
                lo, hi = dev["runs"].get(key, (None, None))
                s, t = int(e.start_ns), int(e.start_ns) + dur
                dev["runs"][key] = (s if lo is None else min(lo, s),
                                    t if hi is None else max(hi, t))
    for ordinal in sorted(by_ordinal):
        dev = by_ordinal[ordinal]
        dev["programs"] = sorted(
            ((module, lo, hi - lo)
             for (module, _), (lo, hi) in dev.pop("runs").items()),
            key=lambda p: p[1])
        devices.append(dev)
    return {"devices": devices, "host": host}


def describe(path: str, per_line: int = 6) -> str:
    """A trace's planes, lines and first events as text — for looking at a
    new trace by hand before code is written against it."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        out.append(f"PLANE {plane.name} ({len(lines)} lines)")
        for line in lines:
            events = list(line.events)
            out.append(f"  LINE {line.name} ({len(events)} events)")
            for e in events[:per_line]:
                stats = {k: v for k, v in list(e.stats)[:8]}
                out.append(f"    {e.name!r} start={e.start_ns} "
                           f"dur={e.duration_ns} {stats}")
    return "\n".join(out)


# --------------------------------------------------------------------------
# Trace -> summary


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def total(intervals: List[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Tuple[int, int]],
             b: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """The parts of disjoint sorted ``a`` that no interval of ``b`` covers."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def self_times(ops: List[Event]) -> List[Tuple[str, int, int, int, bool]]:
    """``(name, start, end, self_ns, is_leaf)`` for each op of one line: an
    op that encloses others (``while``, a call) keeps only the time none of
    its children covers."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    child_ns = [0] * len(ops)
    has_child = [False] * len(ops)
    stack: List[int] = []
    for i in order:
        _, s, d = ops[i]
        # a parent encloses its child whole; a partial overlap is a sibling
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] < s + max(d, 1):
            stack.pop()
        if stack:
            child_ns[stack[-1]] += d
            has_child[stack[-1]] = True
        stack.append(i)
    return [(ops[i][0], ops[i][1], ops[i][1] + ops[i][2],
             max(ops[i][2] - child_ns[i], 0), not has_child[i])
            for i in range(len(ops))]


def parse_op(event_name: str) -> Tuple[str, str]:
    """``(name, opcode)`` of an op event. On the TPU the event's name is the
    whole HLO instruction, ``%fusion.3 = bf16[8,128]{1,0:T(8,128)}
    fusion(...), kind=...``: the name stands before `` = ``, the opcode
    after the result type (which may hold brackets of every kind). On the
    CPU the event's name is the op's name alone, and the opcode is unknown
    (empty)."""
    head, sep, rest = event_name.partition(" = ")
    name = head.strip().lstrip("%")
    if not sep:
        return name, ""
    depth, i = 0, 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            break
    return name, rest[i + 1:].partition("(")[0].strip()


def is_collective(event_name: str) -> bool:
    name, opcode = parse_op(event_name)
    return (name.startswith(COLLECTIVE_PREFIXES)
            or opcode.startswith(COLLECTIVE_PREFIXES))


def op_family(event_name: str) -> str:
    """``%fusion.123 = ... fusion(...)`` and ``fusion.7`` -> ``fusion``: ops
    aggregate by what they are, not by their number in one compiled
    program. An opcode that says more than the name is set beside it."""
    name, opcode = parse_op(event_name)
    family = re.sub(r"[.\d]+$", "", name) or name
    if opcode and opcode not in family:
        family = f"{family} [{opcode}]"
    return family


def _label_gap(gap: Tuple[int, int], before: str, after: str,
               host: List[Event], host_starts: List[int]) -> str:
    s, e = gap
    best, best_overlap, best_dur = None, 0, 0
    # host events are sorted by start; none starting after the gap overlaps
    hi = bisect_left(host_starts, e)
    for name, hs, hd in host[max(0, hi - 400):hi]:
        overlap = min(e, hs + hd) - max(s, hs)
        if overlap > best_overlap or (overlap == best_overlap > 0
                                      and hd < best_dur):
            best, best_overlap, best_dur = name, overlap, hd
    doing = best if best is not None else "no runtime span (Python)"
    return f"{before} -> {after} | host: {doing}"[:180]


def summarize(trace: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Reduce a ``Trace``; ``None`` if no operation ran on any device."""
    devices = [d for d in trace["devices"] if d["ops"] or d["programs"]]
    if not devices:
        return None
    starts, ends, busy_ns, exposed_ns = [], [], [], []
    for dev in devices:
        events = dev["ops"] or dev["programs"]
        starts.append(min(s for _, s, _ in events))
        ends.append(max(s + d for _, s, d in events))
    t0, t1 = min(starts), max(ends)
    per_device = []
    for dev in devices:
        timed = self_times(dev["ops"] or dev["programs"])
        busy = union([(s, e) for _, s, e, _, _ in timed])
        coll = union([(s, e) for n, s, e, _, leaf in timed
                      if leaf and is_collective(n)])
        compute = union([(s, e) for n, s, e, _, leaf in timed
                         if leaf and not is_collective(n)])
        busy_ns.append(total(busy))
        exposed_ns.append(total(subtract(coll, compute)))
        per_device.append((timed, busy))

    timed, busy = per_device[0]
    first = devices[0]
    # by name (``jit_step_fn``) for the readers, and by the event's whole
    # name (``jit_step_fn(<fingerprint>)``) for whoever reads the notes: two
    # programs that share a name are told apart only there
    programs: Dict[str, Dict[str, Any]] = {}
    program_runs: Dict[str, Dict[str, Any]] = {}
    for raw, _, d in first["programs"]:
        for table, key in ((programs, program_name(raw)),
                           (program_runs, raw)):
            table.setdefault(key, {"durations": []})["durations"].append(d)
    for table in (programs, program_runs):
        for p in table.values():
            ds = p.pop("durations")
            p.update(count=len(ds), sum_s=sum(ds) / 1e9,
                     median_s=statistics.median(ds) / 1e9)
    prog_iv = sorted((s, s + d, program_name(n))
                     for n, s, d in first["programs"])
    prog_starts = [p[0] for p in prog_iv]
    ops: Dict[str, Dict[str, float]] = {}
    ops_by_program: Dict[str, Dict[str, Dict[str, float]]] = {}
    for name, s, _, self_ns, _ in timed:
        i = bisect_right(prog_starts, s) - 1
        inside = prog_iv[i][2] if i >= 0 and s < prog_iv[i][1] else "?"
        for table in (ops, ops_by_program.setdefault(inside, {})):
            row = table.setdefault(op_family(name),
                                   {"count": 0, "sum_s": 0.0})
            row["count"] += 1
            row["sum_s"] += self_ns / 1e9

    # idle gaps of the first device, by what ran around them and what the
    # host was doing meanwhile
    host = sorted(trace["host"], key=lambda e: e[1])
    span = t1 - t0
    host = [e for e in host if e[2] < 0.5 * span]  # not thread-long spans
    host_starts = [e[1] for e in host]
    def program_at(t: int, side: int) -> str:
        if not prog_iv:
            return "?"
        i = bisect_left(prog_starts, t)
        i = min(max(i - 1 if side < 0 else i, 0), len(prog_iv) - 1)
        return prog_iv[i][2]

    gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)]
    gaps.sort(key=lambda g: g[0] - g[1])
    by_label: Dict[str, int] = {}
    for gap in gaps[:_LABELLED_GAPS]:
        label = _label_gap(gap, program_at(gap[0], -1),
                           program_at(gap[1], +1), host, host_starts)
        by_label[label] = by_label.get(label, 0) + gap[1] - gap[0]
    rest = sum(e - s for s, e in gaps[_LABELLED_GAPS:])
    if rest:
        by_label["shorter gaps, not labelled"] = rest
    top = lambda d, n=10: sorted(d.items(), key=lambda kv: -kv[1])[:n]
    return {
        "devices": len(devices),
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
        "busy_s_per_device": [b / 1e9 for b in busy_ns],
        "collective_exposed_s": sum(exposed_ns) / len(exposed_ns) / 1e9,
        "programs": programs,
        "program_runs": program_runs,
        "ops": ops,
        "ops_by_program": ops_by_program,
        "breakdown": {
            "device_ops": [[n, sec] for n, sec in
                           top({n: r["sum_s"] for n, r in ops.items()})],
            "idle_gaps": [[n, ns / 1e9] for n, ns in top(by_label)],
        },
    }


def find(table: Dict[str, Any], needle: str) -> Optional[Dict[str, Any]]:
    """The entry of ``programs`` or ``ops`` whose name contains ``needle``;
    the counts and sums of several matches are added."""
    hits = [v for k, v in table.items() if needle in k]
    if not hits:
        return None
    out = {"count": sum(h["count"] for h in hits),
           "sum_s": sum(h["sum_s"] for h in hits)}
    if all("median_s" in h for h in hits):
        out["median_s"] = max(hits, key=lambda h: h["count"])["median_s"]
    return out
