"""Sets of runs of the benchmark's cells, in one call, with their spreads.

    python3 perfbench/sets.py --plan gpt2s_train:4,mistral7b_chat:2 \
        --seed-base 2147480000 --out chiprun_out/sets/a

For each cell of ``--plan`` (``name:n``): one run that compiles (reported
apart, as the driver does), then two interleaved sets of ``n`` runs, the
same seeds in both (set 1 seed i, set 2 seed i, set 1 seed i+1, ...), each
run the benchmark's own command in a process of its own. Every run's
output is kept under ``--out``; the last lines say, for each end-to-end
metric and set, the values, the median, the quartiles and the spread as the
driver takes them (``perfbench/lib/stats.py``), how far the second set's
median lies from the first's, and the median of each set-up phase the
run's ``checks`` note names. A builder's tool: the driver never runs it.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perfbench.lib import contract, stats  # noqa: E402
from perfbench.lib import manifest as manifest_lib  # noqa: E402


def one_run(manifest, cell: str, seed: int, seconds: float, trace: int,
            out_dir: str, tag: str) -> dict:
    """Run the command once; returns what the summary needs of it."""
    cmd = manifest["command"] + [
        "--workload", cell, "--seed", str(seed), "--seconds",
        str(seconds), "--trace", str(trace)]
    t0 = time.time()
    proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    path = os.path.join(out_dir, f"{cell}.{tag}.out")
    with open(path, "w") as f:
        f.write(proc.stdout)
        f.write("\n---- stderr (last 4000)\n" + proc.stderr[-4000:])
    run = {"cell": cell, "tag": tag, "seed": seed, "rc": proc.returncode,
           "took_s": round(time.time() - t0, 1), "correct": None,
           "values": {}, "phases": {}, "problems": []}
    if proc.returncode == 0:
        last = contract.last_line(proc.stdout)
        run["problems"] = contract.check_line(last, manifest, cell,
                                              bool(trace))
        line = json.loads(last)
        run["correct"], run["failed"] = line["correct"], line["failed"]
        run["values"] = {k: v["value"] for k, v in line["metrics"].items()}
        run["memory_peak_bytes"] = line["device"]["memory_peak_bytes"]
        for text in proc.stdout.splitlines():
            if text.startswith('{"note": "checks"'):
                run["phases"] = json.loads(text).get("setup_phases", {})
    print(json.dumps(run), flush=True)
    return run


def summarize(cell: str, sets: list) -> None:
    names = sorted({k for runs in sets for r in runs for k in r["values"]})
    for name in names:
        medians = []
        for i, runs in enumerate(sets):
            xs = [r["values"][name] for r in runs if name in r["values"]]
            if len(xs) < 2:
                continue
            q1, med, q3 = statistics.quantiles(xs, n=4)
            medians.append(statistics.median(xs))
            print(json.dumps({
                "cell": cell, "metric": name, "set": i + 1, "values": xs,
                "median": medians[-1], "q1": q1, "q3": q3,
                "spread": stats.spread(xs)}), flush=True)
        if len(medians) == 2:
            print(json.dumps({
                "cell": cell, "metric": name,
                "second_over_first": medians[1] / medians[0] - 1}),
                flush=True)
    every = [r for runs in sets for r in runs]
    for phase in sorted({k for r in every for k in r["phases"]}):
        xs = [r["phases"][phase] for r in every if phase in r["phases"]]
        print(json.dumps({
            "cell": cell, "phase": phase, "median_s": statistics.median(xs),
            "min_s": min(xs), "max_s": max(xs),
            "values": [round(x, 2) for x in xs]}), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--plan", required=True)
    ap.add_argument("--seed-base", type=int, default=2147480000)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default="chiprun_out/sets")
    args = ap.parse_args()
    manifest = manifest_lib.load()
    seconds = args.seconds or float(manifest["run_seconds"])
    out_dir = os.path.join(ROOT, args.out)
    os.makedirs(out_dir, exist_ok=True)
    bad = 0
    for k, item in enumerate(args.plan.split(",")):
        cell, n = item.split(":")
        base = args.seed_base + 100 * k
        first = one_run(manifest, cell, base + 99, seconds, args.trace,
                        out_dir, "first")
        sets = [[], []]
        for i in range(int(n)):
            for s in (0, 1):
                sets[s].append(one_run(manifest, cell, base + i, seconds,
                                       args.trace, out_dir, f"s{s + 1}r{i}"))
        summarize(cell, sets)
        bad += sum(1 for r in [first] + sets[0] + sets[1]
                   if r["rc"] != 0 or r["problems"] or not r["correct"])
    print(json.dumps({"runs_not_good": bad}), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
