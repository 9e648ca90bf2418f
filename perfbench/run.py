"""The benchmark's one command.

    python3 perfbench/run.py --workload NAME --seed N --seconds S --trace 0|1

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and
prints, as the last line of its standard output, the JSON object the
contract asks for (``perfbench/lib/contract.py`` builds, checks and prints
it; nothing else does). With ``--trace 0`` the metrics are the cell's
end-to-end metrics; with ``--trace 1`` its per-layer metrics, taken from a
short profiler trace made inside the worker that holds the chip.

This process never imports JAX: a chip belongs to one process at a time, and
the chips belong to the workers (the ``JaxTrainer`` worker, the serve
replica). Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench.lib import configs, contract  # noqa: E402
from perfbench.lib import manifest as manifest_lib  # noqa: E402


def say(**fields) -> None:
    """An earlier line of the output: notes for whoever reads the run."""
    print(json.dumps(fields, default=str), flush=True)


def prepare_environment() -> str:
    """Where compiled programs are kept, and how workers find this code.
    The compile cache is at ``JAX_COMPILATION_CACHE_DIR`` if the machine set
    it, else at a fixed path inside the checkout (the program's own helper
    decides); every program is cached, however quickly it compiled."""
    from ray_tpu._private import compile_cache

    cache_dir = compile_cache.enable()
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    paths = [ROOT] + [p for p in os.environ.get("PYTHONPATH", "").split(
        os.pathsep) if p and p != ROOT]
    os.environ["PYTHONPATH"] = os.pathsep.join(paths)
    return cache_dir


def wait_until_chips_are_free(timeout_s: float = 60.0) -> None:
    """Return once no process holds a TPU device file any more. A killed
    chip worker needs seconds to die (4 s with one chip, 11 s with four);
    until it has, its chip is not free, and a run started meanwhile loses a
    quarter of a minute of set-up to a worker that cannot open it. So this
    run ends only when what it started has ended."""
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if not chip_holders():
            return
        time.sleep(0.25)


def chip_holders() -> set:
    holders = set()
    for pid in os.listdir("/proc"):
        if not pid.isdigit() or int(pid) == os.getpid():
            continue
        try:
            for fd in os.listdir(f"/proc/{pid}/fd"):
                target = os.readlink(f"/proc/{pid}/fd/{fd}")
                if target.startswith(("/dev/vfio/", "/dev/accel")):
                    holders.add(int(pid))
                    break
        except OSError:
            continue  # the process ended, or is not ours to read
    return holders


def build_context(args, manifest, require_tpu: bool) -> dict:
    workload = manifest_lib.workload(manifest, args.workload)
    cfg = manifest_lib.config(manifest, workload["config"])
    fam = manifest_lib.read_json_from_bench("families", cfg["model_type"])
    preset, overrides = configs.program_overrides(cfg, fam)
    trace_dir = os.path.join(ROOT, ".perfbench_trace", workload["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    return {
        "workload": workload, "config": cfg,
        "cell": manifest_lib.read_json(manifest, "cells", workload["name"]),
        "traffic": manifest_lib.read_json(manifest, "traffic",
                                          workload["traffic"]),
        "preset": preset, "overrides": overrides,
        "reference_path": os.path.join(HERE, "reference",
                                       fam["reference"] + ".py"),
        "seed": args.seed, "seconds": args.seconds, "trace": args.trace,
        "require_tpu": require_tpu, "trace_dir": trace_dir,
        "t_process_start": T_PROCESS_START,
    }


def metrics_of(manifest, ctx: dict, result: dict) -> dict:
    """name -> {"value", "unit"} for the cell's metrics of this kind of
    run. A per-layer reader that finds nothing to read returns nothing, and
    the metric is then left out (and the contract check names it)."""
    out = {}
    for m in manifest_lib.metrics_for(manifest, ctx["workload"]["name"],
                                      bool(ctx["trace"])):
        if ctx["trace"]:
            value = manifest_lib.metric_reader(m["name"])(
                {**ctx, **result})
        else:
            value = result["e2e"].get(m["name"])
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None, *, manifest_path=None, require_tpu: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    manifest = manifest_lib.load(manifest_path)
    if args.seconds is None:
        args.seconds = float(manifest["run_seconds"])
    ctx = build_context(args, manifest, require_tpu)
    cache_dir = prepare_environment()

    import ray_tpu
    from ray_tpu._private import compile_cache

    # A kind's module has ``run(ctx)``, which returns ``correct``, ``checks``,
    # ``attempted``, ``failed``, ``e2e`` (name -> value), ``device``,
    # ``trace`` (the traced window's summary or None), ``clock``
    # (``worker_start_s``), ``sizes``, ``counters``, and may return
    # ``notes``, ``setup_phases`` (name -> seconds, in order) and
    # ``compared`` (name -> {"value", "limit"}: what ``correct`` was
    # decided from).
    kind = ctx["cell"]["kind"]
    if kind == "train":
        from perfbench.lib import train_cell as cell_module
    elif kind == "serve":
        from perfbench.lib import serve_cell as cell_module
    else:
        raise SystemExit(f"perfbench: cell kind {kind!r} is not known")

    chips = ctx["workload"]["chips"]
    say(note="start", workload=args.workload, seed=args.seed,
        seconds=args.seconds, trace=args.trace, cache_dir=cache_dir,
        cache_entries=compile_cache.entries(cache_dir))
    ctx["t_init"] = time.time()
    info = ray_tpu.init(log_to_driver=False)
    try:
        found = int(ray_tpu.cluster_resources().get("TPU", 0))
        if require_tpu and found < chips:
            print(f"perfbench: the cell needs {chips} TPU chip(s), "
                  f"ray_tpu.init() found {found}: nothing is measured",
                  file=sys.stderr)
            return 3
        result = cell_module.run(ctx)
    except Exception:
        traceback.print_exc()
        return 4
    finally:
        try:
            ray_tpu.shutdown()
            wait_until_chips_are_free()
        finally:
            keep = os.environ.get("PERFBENCH_KEEP_LOGS")
            if keep:
                shutil.copytree(os.path.join(info["session_dir"], "logs"),
                                keep, dirs_exist_ok=True)
    shutil.rmtree(ctx["trace_dir"], ignore_errors=True)

    device = dict(result["device"])
    if require_tpu and (device["platform"] != "tpu"
                        or device["count"] != chips):
        print(f"perfbench: the worker saw {device}", file=sys.stderr)
        return 3
    breakdown = None
    if args.trace:
        summary = result.get("trace")
        if summary is None:
            print("perfbench: the traced window saw no operation on the "
                  "device", file=sys.stderr)
            return 5
        device["window_s"] = summary["window_s"]
        device["busy_s"] = summary["busy_s"]
        breakdown = summary["breakdown"]
    # where set-up's seconds went: the command's two phases, then the
    # phases the process that holds the chip stamped, in order
    setup_phases = {"command_to_init": ctx["t_init"] - T_PROCESS_START,
                    "worker_start": result["clock"]["worker_start_s"],
                    **result.get("setup_phases", {})}
    say(note="checks", correct=result["correct"], checks=result["checks"],
        setup_phases=setup_phases, **result.get("notes", {}))
    say(note="end_to_end_in_this_run", **result["e2e"])
    say(note="cache", cache_entries=compile_cache.entries(cache_dir))
    line = contract.build_line(
        correct=result["correct"], attempted=result["attempted"],
        failed=result["failed"], metrics=metrics_of(manifest, ctx, result),
        device=device, breakdown=breakdown, compared=result.get("compared"))
    try:
        contract.emit(line, manifest, args.workload, bool(args.trace))
        # the contract wants them twice: stderr's last lines, and above
        for name, c in result.get("compared", {}).items():
            print(f"perfbench: compared {name} = {c['value']} "
                  f"(limit {c['limit']})", file=sys.stderr)
    except contract.ContractError as e:
        for p in e.problems:
            print(f"perfbench: contract: {p}", file=sys.stderr)
        say(note="refused_line", line=line)
        return 6
    return 0


if __name__ == "__main__":
    sys.exit(main())
