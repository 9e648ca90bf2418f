"""Reading ``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one cell, one traffic mix or
one per-layer metric sits in a file of its own, found by the name the
manifest gives it:

    configs/<config>.json     sizes as run, keyed as the source's config.json
    families/<model_type>.json how those keys map onto the program's config
    reference/<name>.py       the family's plain reference
    cells/<workload>.json     deployment of the cell: kind, batch, slots, ...
    traffic/<traffic>.json    parameters the one general generator reads
    metrics/<metric>.py       reader of one per-layer metric

so a later PR adds a cell, a configuration, a mix or a per-layer metric by
adding files and manifest entries, and edits none that is there: an entry
goes to the END of its list, and a cell joins a metric's ``workloads`` at
the end. The tests hold it to that and leave it the room
(``tests/perfbench/held.py``): what an earlier PR listed is held by name and
index, a ``workloads`` list by its first entries, a count as ``>=``. A PR's
own tests hold its entries the same way, never by ``[-1]``, a total length
or a ``workloads`` list compared whole, or the next PR could add nothing.
"""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Dict, List, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load(path: Optional[str] = None) -> Dict[str, Any]:
    """The manifest, with ``_dir`` set to the directory its relative paths
    start from (the checkout's root, or a test's tiny tree)."""
    path = os.path.abspath(path or os.path.join(ROOT, "BENCHMARK.json"))
    with open(path) as f:
        manifest = json.load(f)
    manifest["_dir"] = os.path.dirname(path)
    return manifest


def _by_name(rows: List[Dict[str, Any]], name: str, what: str):
    for row in rows:
        if row["name"] == name:
            return row
    raise KeyError(f"BENCHMARK.json names no {what} {name!r}")


def workload(manifest, name: str) -> Dict[str, Any]:
    return _by_name(manifest["workloads"], name, "workload")


def bench_dir(manifest) -> str:
    """The benchmark's own data directory: the first of ``paths``."""
    return os.path.join(manifest["_dir"], manifest["paths"][0])


def read_json(manifest, kind: str, name: str) -> Dict[str, Any]:
    with open(os.path.join(bench_dir(manifest), kind, name + ".json")) as f:
        return json.load(f)


def read_json_from_bench(kind: str, name: str) -> Dict[str, Any]:
    """A data file of the yardstick itself (families), whatever tree the
    manifest came from."""
    with open(os.path.join(BENCH_DIR, kind, name + ".json")) as f:
        return json.load(f)


def config(manifest, name: str) -> Dict[str, Any]:
    entry = _by_name(manifest["configs"], name, "configuration")
    with open(os.path.join(manifest["_dir"], entry["file"])) as f:
        return json.load(f)


def metrics_for(manifest, workload_name: str, traced: bool):
    """The metrics the cell reports in this kind of run, manifest order."""
    rows = manifest["per_layer"] if traced else manifest["end_to_end"]
    return [m for m in rows
            if "workloads" not in m or workload_name in m["workloads"]]


def load_module(path: str, name: str):
    """Import a file by path (metric readers have dots in their names)."""
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_reader(metric: str):
    """``read(ctx)`` of ``metrics/<metric>.py``. Readers live with the
    yardstick, whatever tree the manifest came from."""
    path = os.path.join(BENCH_DIR, "metrics", metric + ".py")
    mod = load_module(path, "perfbench_metric_" + metric.replace(".", "_"))
    return mod.read
