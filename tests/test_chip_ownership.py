"""Who owns a chip (ISSUE 21): one process for each chip, decided by the
settings stock JAX and libtpu honour.

Unit level: the daemon / CPU-worker / chip-worker environments, chip
detection on a host that sets no TPU variable, the compile-cache directory
rule. Cluster level (fake ``TPU`` resources on the CPU backend): a worker
that leased chips is spawned pinned to them, never returns to the idle pool,
and its chips go back only when its process is gone.
"""

import os
import subprocess
import sys
import time

import pytest

from ray_tpu._private import accelerators, compile_cache
from ray_tpu._private.resources import detect_node_resources

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TPU_VARS = ("TPU_VISIBLE_CHIPS", "TPU_CHIPS_PER_HOST_BOUNDS",
            "TPU_HOST_BOUNDS", "TPU_ACCELERATOR_TYPE", "TPU_WORKER_ID",
            "TPU_TOPOLOGY", "RAY_TPU_FORCE_TPU_CHIPS")


@pytest.fixture
def bare_host(monkeypatch):
    """A host whose environment says nothing about TPUs."""
    for var in TPU_VARS:
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("RAY_TPU_DISABLE_METADATA", "1")


def _device_files(monkeypatch, files):
    import glob

    monkeypatch.setattr(
        glob, "glob", lambda pattern: [
            f for f in files
            if f.startswith(pattern.split("[")[0].rstrip("*"))])


# ------------------------------------------------------------ environments


class TestProcessEnvironments:
    BASE = {"PATH": "/bin", "JAX_PLATFORMS": "cpu",
            "TPU_CHIPS_PER_HOST_BOUNDS": "2,2,1", "TPU_HOST_BOUNDS": "1,1,1"}

    def test_daemon_pins_itself_to_cpu_and_remembers_its_launch(
            self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        assert accelerators.keep_off_accelerators() == "tpu,cpu"
        assert os.environ["JAX_PLATFORMS"] == "cpu"
        monkeypatch.delenv("JAX_PLATFORMS")
        assert accelerators.keep_off_accelerators() is None
        assert os.environ["JAX_PLATFORMS"] == "cpu"

    def test_daemon_env_carries_the_drivers_platform_untouched(
            self, monkeypatch):
        """The supervisor hands the launch platform to chip workers, so
        the spawn environment must not rewrite it (the daemon pins its
        own process instead) — and it sets nothing else of the kind."""
        from ray_tpu._private.node import _daemon_env

        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        env = _daemon_env()
        assert env["JAX_PLATFORMS"] == "tpu,cpu"
        added = set(env) - set(os.environ)
        assert all(k.startswith("RAY_TPU_") or k == "PYTHONPATH"
                   for k in added), added

    @pytest.mark.parametrize("launched_with", [None, "tpu", "tpu,cpu"])
    def test_cpu_worker_is_held_to_the_cpu_backend(self, launched_with):
        env = accelerators.worker_env(self.BASE, [], 4, launched_with)
        assert env["JAX_PLATFORMS"] == "cpu"
        assert "TPU_VISIBLE_CHIPS" not in env

    @pytest.mark.parametrize("chips,bounds", [([2], "1,1,1"),
                                              ([0, 1], "1,2,1")])
    def test_chip_worker_gets_launch_platform_and_its_pinning(
            self, chips, bounds):
        env = accelerators.worker_env(self.BASE, chips, 4, "tpu,cpu")
        assert env["JAX_PLATFORMS"] == "tpu,cpu"
        assert env["TPU_VISIBLE_CHIPS"] == ",".join(map(str, chips))
        assert env["TPU_CHIPS_PER_HOST_BOUNDS"] == bounds
        assert env["TPU_HOST_BOUNDS"] == "1,1,1"

    def test_chip_worker_without_launch_platform_lets_jax_choose(self):
        env = accelerators.worker_env(self.BASE, [0], 4, None)
        assert "JAX_PLATFORMS" not in env

    def test_whole_host_worker_is_not_pinned(self):
        env = accelerators.worker_env(self.BASE, [0, 1, 2, 3], 4, "tpu")
        assert "TPU_VISIBLE_CHIPS" not in env
        assert env["TPU_CHIPS_PER_HOST_BOUNDS"] == "2,2,1"  # the host's own

    def test_one_chip_host_needs_no_pinning(self):
        """The one-chip machine cut from a 2x2 host keeps the host's
        bounds in its environment; its only worker takes them as is."""
        env = accelerators.worker_env(self.BASE, [0], 1, "tpu,cpu")
        assert "TPU_VISIBLE_CHIPS" not in env

    def test_unsupported_chip_count_is_loud(self):
        with pytest.raises(ValueError, match="3"):
            accelerators.worker_env(self.BASE, [0, 1, 2], 4, "tpu")

    def test_test_suite_keeps_chip_workers_on_cpu(self):
        """conftest sets JAX_PLATFORMS=cpu: a node launched under it
        hands 'cpu' to the workers of fake-TPU tests."""
        env = accelerators.worker_env(self.BASE, [1], 4, "cpu")
        assert env["JAX_PLATFORMS"] == "cpu"


# --------------------------------------------------------------- detection


class TestChipDetection:
    def test_bare_vm_counts_vfio_groups(self, bare_host, monkeypatch):
        _device_files(monkeypatch, ["/dev/vfio/0", "/dev/vfio/1",
                                    "/dev/vfio/2", "/dev/vfio/3",
                                    "/dev/vfio/vfio"])
        assert accelerators.count_local_chips() == 4
        assert detect_node_resources()["TPU"] == 4.0

    def test_bare_vm_counts_accel_nodes(self, bare_host, monkeypatch):
        _device_files(monkeypatch, ["/dev/accel0", "/dev/accel1"])
        assert detect_node_resources()["TPU"] == 2.0

    def test_attached_chips_win_over_a_larger_described_host(
            self, bare_host, monkeypatch):
        """The chip machine: one VFIO group, environment of a 2x2 host."""
        monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
        monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v5litepod-4")
        _device_files(monkeypatch, ["/dev/vfio/3", "/dev/vfio/vfio"])
        assert detect_node_resources()["TPU"] == 1.0

    def test_no_device_files_falls_back_to_the_variables(
            self, bare_host, monkeypatch):
        _device_files(monkeypatch, [])
        assert "TPU" not in detect_node_resources()
        monkeypatch.setenv("TPU_CHIPS_PER_HOST_BOUNDS", "2,2,1")
        assert detect_node_resources()["TPU"] == 4.0

    def test_detection_never_imports_jax(self, bare_host):
        code = ("import sys; from ray_tpu._private.resources import "
                "detect_node_resources as d; d(); "
                "assert 'jax' not in sys.modules")
        subprocess.run([sys.executable, "-c", code], check=True, cwd=REPO)

    def test_host_chip_ids_follow_the_nodes_own_isolation(
            self, bare_host, monkeypatch):
        assert accelerators.host_chip_ids(4) == [0, 1, 2, 3]
        monkeypatch.setenv("TPU_VISIBLE_CHIPS", "2,3")
        assert accelerators.host_chip_ids(2) == [2, 3]
        assert accelerators.host_chip_ids(4) == [0, 1, 2, 3]  # fake count


def test_driver_side_serve_import_stays_off_jax():
    """build_app() is called by the driver, which must leave the chip to
    the replica's process: importing it may not drag jax in."""
    code = ("import sys; from ray_tpu.serve.llm import build_app; "
            "import ray_tpu.train; "
            "assert 'jax' not in sys.modules, 'jax imported'")
    subprocess.run([sys.executable, "-c", code], check=True, cwd=REPO)


# ------------------------------------------------------------ compile cache


class TestCompileCacheRule:
    def test_set_from_outside_is_left_alone(self, monkeypatch, tmp_path):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert compile_cache.enable() == str(tmp_path)
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == str(tmp_path)

    def test_unset_takes_one_fixed_directory_in_the_checkout(
            self, monkeypatch):
        # (setenv, so that teardown also drops what enable() exports)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
        import jax

        before = jax.config.jax_compilation_cache_dir
        try:
            first = compile_cache.enable()
            assert first == os.path.join(REPO, ".jax_cache")
            # children inherit it; an already-imported jax is told too
            assert os.environ["JAX_COMPILATION_CACHE_DIR"] == first
            assert jax.config.jax_compilation_cache_dir == first
            assert compile_cache.enable() == first
        finally:
            jax.config.update("jax_compilation_cache_dir", before)

    def test_default_is_never_a_temp_pid_session_or_time_path(self):
        import tempfile

        path = compile_cache.DEFAULT_DIR
        assert not path.startswith(tempfile.gettempdir())
        assert str(os.getpid()) not in path
        assert "session" not in path
        assert not any(ch.isdigit() for ch in os.path.basename(path))
        # a second interpreter computes the same directory
        out = subprocess.run(
            [sys.executable, "-c", "from ray_tpu._private import "
             "compile_cache as c; print(c.DEFAULT_DIR)"],
            check=True, cwd=REPO, capture_output=True, text=True)
        assert out.stdout.strip() == path

    def test_git_ignores_the_default_directory(self):
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()

    def test_entries_counts_programs_not_access_stamps(self, tmp_path):
        assert compile_cache.entries(str(tmp_path / "missing")) == 0
        for name in ("a-cache", "a-atime", "b-cache", "b-atime", "c"):
            (tmp_path / name).write_bytes(b"")
        assert compile_cache.entries(str(tmp_path)) == 3


# ------------------------------------------------------------ cluster level


@pytest.fixture
def chip_cluster():
    import ray_tpu

    ray_tpu.init(num_cpus=4, num_tpus=4,
                 object_store_memory=64 * 1024 * 1024)
    yield ray_tpu
    ray_tpu.shutdown()


def _where():
    return (os.getpid(), os.environ.get("TPU_VISIBLE_CHIPS"),
            os.environ.get("TPU_CHIPS_PER_HOST_BOUNDS"),
            os.environ["JAX_PLATFORMS"])


def _wait_for(predicate, timeout=20.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.1)
    return False


class TestChipLeases:
    def test_chip_task_worker_is_pinned_and_exits_with_its_lease(
            self, chip_cluster):
        ray_tpu = chip_cluster
        task = ray_tpu.remote(num_tpus=1)(_where)
        pid1, chip1, bounds1, platform1 = ray_tpu.get(task.remote())
        assert chip1 in {"0", "1", "2", "3"} and bounds1 == "1,1,1"
        assert platform1 == "cpu"  # the suite's own launch platform
        # the lease ends -> the process (which would hold the chip in its
        # JAX client) is gone before the chip is counted free again
        assert _wait_for(lambda: ray_tpu.available_resources()
                         .get("TPU") == 4.0)
        with pytest.raises(ProcessLookupError):
            os.kill(pid1, 0)
        pid2, *_ = ray_tpu.get(task.remote())
        assert pid2 != pid1

    def test_cpu_task_worker_has_no_chip(self, chip_cluster):
        ray_tpu = chip_cluster
        _, chip, _, platform = ray_tpu.get(ray_tpu.remote(_where).remote())
        assert chip is None and platform == "cpu"

    def test_four_actors_four_different_chips_then_all_four_to_one(
            self, chip_cluster):
        ray_tpu = chip_cluster

        @ray_tpu.remote(num_tpus=1)
        class Holder:
            def where(self):
                return _where()

        holders = [Holder.remote() for _ in range(4)]
        seen = ray_tpu.get([h.where.remote() for h in holders])
        assert sorted(s[1] for s in seen) == ["0", "1", "2", "3"]
        assert len({s[0] for s in seen}) == 4
        assert not ray_tpu.available_resources().get("TPU")
        for h in holders:
            ray_tpu.kill(h)
        assert _wait_for(lambda: ray_tpu.available_resources()
                         .get("TPU") == 4.0)
        # a whole-host lease takes the host's environment as it is
        _, chip, _, _ = ray_tpu.get(
            ray_tpu.remote(num_tpus=4)(_where).remote())
        assert chip is None
