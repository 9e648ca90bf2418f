"""Owner watchdog + stale-session reaper.

Reference analog: raylet client-disconnect suicide
(`src/ray/raylet/node_manager.cc:1432`) and GCS node health checks
(`src/ray/gcs/gcs_server/gcs_health_check_manager.h:39`) — a SIGKILLed
driver must not orphan daemons whose workers go on holding the host's
chips (a chip belongs to one process at a time).
"""

import json
import os
import signal
import subprocess
import sys
import tempfile
import textwrap
import time

import pytest

from ray_tpu._private import reaper
from ray_tpu._private.watchdog import proc_start_time


def _pids_matching(marker: str):
    out = []
    for d in os.listdir("/proc"):
        if not d.isdigit():
            continue
        try:
            with open(f"/proc/{d}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
        except OSError:
            continue
        if marker in cmd:
            out.append(int(d))
    return out


def test_proc_start_time():
    me = proc_start_time(os.getpid())
    assert isinstance(me, int) and me > 0
    # a pid that can't exist
    assert proc_start_time(2 ** 22 + 12345) is None


def test_daemon_tree_collapses_on_driver_sigkill(tmp_path):
    """kill -9 the driver -> controller+supervisor+workers all exit."""
    script = textwrap.dedent("""
        import time
        import ray_tpu

        ray_tpu.init(num_cpus=1, object_store_memory=64 * 1024 * 1024)

        @ray_tpu.remote
        def f(x):
            return x + 1

        assert ray_tpu.get(f.remote(1)) == 2
        print("READY", flush=True)
        time.sleep(120)
    """)
    env = dict(os.environ)
    env["RAY_TPU_WATCHDOG_INTERVAL_S"] = "0.2"
    proc = subprocess.Popen([sys.executable, "-c", script], env=env,
                            stdout=subprocess.PIPE, text=True)
    try:
        line = proc.stdout.readline()
        assert "READY" in line, f"driver failed to start: {line!r}"
        # the daemon tree is alive while the driver lives
        session_pids = [
            p for p in _pids_matching("ray_tpu._private.")
            if reaper._read_env_var(p, "RAY_TPU_OWNER_PID") == str(proc.pid)
        ]
        assert session_pids, "driver spawned no daemons?"

        os.kill(proc.pid, signal.SIGKILL)
        proc.wait()

        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            alive = [p for p in session_pids
                     if proc_start_time(p) is not None]
            if not alive:
                return
            time.sleep(0.2)
        pytest.fail(f"daemons survived driver SIGKILL: {alive}")
    finally:
        if proc.poll() is None:
            proc.kill()


def test_reaper_removes_unmapped_arena():
    path = "/dev/shm/rtpu_arena_test_stale_deadbeef"
    with open(path, "wb") as f:
        f.write(b"\0" * 4096)
    try:
        removed = reaper.reap_stale_arenas()
        assert path in removed
        assert not os.path.exists(path)
    finally:
        if os.path.exists(path):
            os.unlink(path)


def test_reaper_keeps_mapped_arena():
    """An arena a live process holds open must survive the sweep."""
    import mmap

    path = "/dev/shm/rtpu_arena_test_live_cafef00d"
    with open(path, "wb") as f:
        f.write(b"\0" * 4096)
    fd = os.open(path, os.O_RDWR)
    try:
        mm = mmap.mmap(fd, 4096)
        removed = reaper.reap_stale_arenas()
        assert path not in removed
        assert os.path.exists(path)
        mm.close()
    finally:
        os.close(fd)
        if os.path.exists(path):
            os.unlink(path)


def test_reaper_kills_daemon_with_dead_owner(tmp_path):
    """A controller whose recorded owner is dead is reaped (watchdog
    disabled to isolate the reaper path)."""
    # a pid that is certainly dead: spawn-and-reap a trivial process
    dead = subprocess.Popen([sys.executable, "-c", "pass"])
    dead.wait()

    env = dict(os.environ)
    env["RAY_TPU_OWNER_WATCHDOG"] = "0"  # reaper, not watchdog, under test
    env["RAY_TPU_OWNER_PID"] = str(dead.pid)
    addr_file = str(tmp_path / "addr")
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.controller",
         "--port", "0", "--session-dir", str(tmp_path),
         "--address-file", addr_file],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and not os.path.exists(addr_file):
            time.sleep(0.05)
        assert os.path.exists(addr_file), "controller never came up"

        assert proc.pid in reaper.find_stale_daemons()
        reaped = reaper.reap_stale_daemons()
        assert proc.pid in reaped
        assert proc.wait(timeout=5) != 0 or True  # exited
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()


def test_reaper_spares_daemon_with_live_owner(tmp_path):
    """Daemons owned by a LIVE process (this one) are never listed."""
    env = dict(os.environ)
    env["RAY_TPU_OWNER_WATCHDOG"] = "0"
    env["RAY_TPU_OWNER_PID"] = str(os.getpid())
    addr_file = str(tmp_path / "addr")
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu._private.controller",
         "--port", "0", "--session-dir", str(tmp_path),
         "--address-file", addr_file],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and not os.path.exists(addr_file):
            time.sleep(0.05)
        assert proc.pid not in reaper.find_stale_daemons()
        assert proc.poll() is None
    finally:
        proc.kill()
        proc.wait()


@pytest.mark.parametrize("deaf", [False, True],
                         ids=["workers_heed_sigterm", "a_worker_ignores_it"])
def test_shutdown_returns_when_every_process_has_gone(deaf):
    """``shutdown()`` ends the session's processes, it does not merely
    signal them: when it returns no daemon and no worker is left, running
    or as a zombie handed to init. A chip worker needs seconds to die and
    holds its chip till then; a harness that starts its next run, or counts
    what a run left behind, right after ``shutdown()`` must find nothing.
    A worker deaf to SIGTERM is killed after the supervisor's grace."""
    script = textwrap.dedent(f"""
        import ctypes, json, os, time
        import ray_tpu

        info = ray_tpu.init(num_cpus=4, object_store_memory=64 * 1024 * 1024)

        @ray_tpu.remote
        class A:
            def pid(self, deaf):
                if deaf:  # SIG_IGN for the whole process, from any thread
                    ctypes.CDLL(None).signal(15, 1)
                return os.getpid()

        @ray_tpu.remote
        def f():
            return os.getpid()

        actors = [A.remote() for _ in range(2)]
        pids = ray_tpu.get([a.pid.remote({deaf}) for a in actors])
        pids += ray_tpu.get([f.remote() for _ in range(3)])
        t = time.monotonic()
        ray_tpu.shutdown()
        took = time.monotonic() - t
        left = [d for d in os.listdir("/proc") if d.isdigit() and
                info["session_dir"].encode() in
                open(f"/proc/{{d}}/cmdline", "rb").read()]
        states = [open(f"/proc/{{p}}/stat").read().split(")")[-1].split()[0]
                  for p in set(pids) if os.path.exists(f"/proc/{{p}}")]
        print(json.dumps({{"left": left, "states": states, "took": took}}),
              flush=True)
    """)
    out = subprocess.run([sys.executable, "-c", script], timeout=60,
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-2000:]
    seen = json.loads(out.stdout.splitlines()[-1])
    assert seen["left"] == [] and seen["states"] == [], seen
    assert (2.0 <= seen["took"] < 15.0) if deaf else seen["took"] < 5.0, seen
