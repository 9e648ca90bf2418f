"""Test harness configuration.

Forces JAX onto a virtual 8-device CPU backend (the TPU sharding tests run on
a CPU mesh, per the reference's pattern of hermetic single-host clusters,
SURVEY §4). ``JAX_PLATFORMS=cpu`` is what stock JAX honours, and every
daemon and worker the tests spawn inherits it — chip-leasing workers too,
since they get the platform choice their node was launched with.
"""

import os

# Must happen before any jax backend initialization, and is inherited by every
# daemon/worker subprocess the tests spawn.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("RAY_TPU_LOG_LEVEL", "WARNING")

# Fast failure detection for hermetic single-host clusters: production
# defaults (1s period x 3 misses x 3s timeout) make every node-death test
# wait ~6-10s. Supervisors also passively refresh liveness via their 0.2s
# sync, so short probe windows are safe here.
os.environ.setdefault("RAY_TPU_HEALTH_CHECK_PERIOD_MS", "200")
os.environ.setdefault("RAY_TPU_HEALTH_CHECK_TIMEOUT_MS", "1000")
os.environ.setdefault("RAY_TPU_HEALTH_CHECK_FAILURE_THRESHOLD", "3")

import sys  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(scope="module", autouse=True)
def _harness_memos_go_with_the_file():
    """What ``tests/model_harness.py`` compiled and seeded for one file's
    tests is dropped behind them: a worker holds one file's programs."""
    yield
    harness = sys.modules.get("tests.model_harness")
    if harness is not None:
        harness.forget()


@pytest.fixture(scope="module")
def ray_init():
    """A started single-node cluster with 4 CPUs (module-scoped for speed)."""
    import ray_tpu

    info = ray_tpu.init(
        num_cpus=32,  # virtual: plenty of headroom for long-lived test actors
        object_store_memory=256 * 1024 * 1024,
        ignore_reinit_error=True,
    )
    yield info
    ray_tpu.shutdown()


@pytest.fixture
def ray_cluster():
    """A multi-node cluster factory; nodes added by the test."""
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster()
    yield cluster
    import ray_tpu

    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    cluster.shutdown()
