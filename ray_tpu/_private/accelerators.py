"""TPU pod discovery: GKE env vars + GCE metadata server.

Analog of `python/ray/_private/accelerators/tpu.py:14-49`: figure out, from
inside a TPU VM, (a) the pod's accelerator type (e.g. "v5p-64"), (b) this
host's worker index within the pod, and (c) the chip count — then turn them
into scheduler resources: per-host "TPU" chips, an "accelerator_type:TPU-<gen>"
label, and the pod-wide `TPU-<type>-head` gang resource on worker 0 (the
reference's convention for multi-host gang scheduling; our STRICT_SPREAD
slice bundles in `parallel/slices.py` consume it).

Sources, in priority order:
  1. explicit env (TPU_ACCELERATOR_TYPE / TPU_WORKER_ID — set by the GKE
     TPU webhook and by tests),
  2. the GCE metadata server (guarded by a short timeout and the
     RAY_TPU_DISABLE_METADATA kill-switch; a zero-egress box just falls
     through in ~100ms).
"""

from __future__ import annotations

import logging
import os
from typing import Dict, Optional

logger = logging.getLogger(__name__)

# reference: accelerators/tpu.py GKE_TPU_* / GCE metadata keys
_GKE_ACCEL_ENV = "TPU_ACCELERATOR_TYPE"     # e.g. "v5p-64"
_GKE_WORKER_ID_ENV = "TPU_WORKER_ID"        # "0".."n_hosts-1"
_GKE_TOPOLOGY_ENV = "TPU_TOPOLOGY"          # e.g. "2x2x2"
_GCE_METADATA_URL = (
    "http://metadata.google.internal/computeMetadata/v1/instance/attributes")
_METADATA_HEADERS = {"Metadata-Flavor": "Google"}
_METADATA_TIMEOUT_S = 0.5


def _metadata_get(key: str) -> Optional[str]:
    """GCE metadata attribute, or None fast when unreachable/disabled."""
    if os.environ.get("RAY_TPU_DISABLE_METADATA"):
        return None
    base = os.environ.get("RAY_TPU_METADATA_URL", _GCE_METADATA_URL)
    try:
        import urllib.request

        req = urllib.request.Request(f"{base}/{key}",
                                     headers=_METADATA_HEADERS)
        with urllib.request.urlopen(req,
                                    timeout=_METADATA_TIMEOUT_S) as resp:
            return resp.read().decode().strip()
    except Exception:
        return None


def get_current_pod_accelerator_type() -> Optional[str]:
    """'v5p-64'-style type for the pod this host belongs to, or None off-TPU
    (reference `tpu.py` GKE env first, GCE `accelerator-type` second)."""
    accel = os.environ.get(_GKE_ACCEL_ENV)
    if accel:
        return accel
    return _metadata_get("accelerator-type")


def get_current_pod_worker_id() -> Optional[int]:
    """This host's index within the pod slice (0 == slice head)."""
    wid = os.environ.get(_GKE_WORKER_ID_ENV)
    if wid is None:
        wid = _metadata_get("agent-worker-number")
    if wid is None:
        return None
    try:
        return int(wid)
    except ValueError:
        return None


def get_current_pod_name() -> Optional[str]:
    """The TPU pod/instance name (detached-actor namespacing, logs)."""
    return os.environ.get("TPU_NAME") or _metadata_get("instance-id")


def tpu_pod_resources() -> Dict[str, float]:
    """Scheduler resources this host contributes on account of its TPU pod
    membership (empty off-TPU):

      - ``accelerator_type:TPU-<gen>``: node-affinity label,
      - ``TPU-<type>-head``: 1.0 on worker 0 only — the gang resource a
        pod-wide job leases to claim the slice (reference tpu.py:44-49).

    Per-host chip counts are detected separately (resources._detect_tpu_chips
    — `TPU_VISIBLE_CHIPS` isolation must win over pod math).
    """
    accel = get_current_pod_accelerator_type()
    if not accel:
        return {}
    out: Dict[str, float] = {}
    gen = accel.split("-")[0]
    out[f"accelerator_type:TPU-{gen}"] = 1.0
    # The resource NAME must be the chip-normalized one slice placement
    # groups demand (SliceTopology.head_resource) — the raw accelerator
    # string counts cores on v2-v4/v5p and would never match.
    from ray_tpu.parallel.slices import SliceTopology

    try:
        topo = SliceTopology.parse(accel)
        head, multi_host = topo.head_resource, topo.num_hosts > 1
    except ValueError:
        head, multi_host = f"TPU-{accel}-head", False
    worker_id = get_current_pod_worker_id()
    # Worker 0 is the head. A missing worker id only implies head-ness on a
    # single-host slice; on a multi-host pod where TPU_WORKER_ID is unset
    # and the metadata lookup failed, granting head on every host would let
    # slice placement groups gang-schedule multiple jobs onto one slice.
    if worker_id == 0 or (worker_id is None and not multi_host):
        out[head] = 1.0
    return out


# ---------------------------------------------------------------------------
# who owns the chips of this host
#
# A chip belongs to one process at a time: the first process whose JAX
# client opens it holds it until that process exits. Which process that is
# gets decided by the settings stock JAX and libtpu honour, and by nothing
# else — JAX_PLATFORMS for "may this process open an accelerator at all",
# and TPU_VISIBLE_CHIPS with its bounds for "which ones".

# chips in one process -> TPU_CHIPS_PER_HOST_BOUNDS (reference
# accelerators/tpu.py; libtpu refuses a second process on the host's
# default bounds, and takes these for 1 and 2 chips on a v5e 2x2 host)
_CHIP_BOUNDS = {1: "1,1,1", 2: "1,2,1"}


def count_local_chips() -> int:
    """TPU chips attached to this host, from the device files the TPU
    driver creates — no JAX backend is initialised, so counting claims
    nothing, and it works on a VM that sets no TPU variable at all.
    Older runtimes expose ``/dev/accel<N>``, newer ones one VFIO group per
    chip (``/dev/vfio/<N>`` next to the ``/dev/vfio/vfio`` control node)."""
    import glob

    chips = glob.glob("/dev/accel[0-9]*")
    if not chips:
        chips = [p for p in glob.glob("/dev/vfio/*")
                 if os.path.basename(p).isdigit()]
    return len(chips)


def host_chip_ids(num_chips: int) -> list:
    """The ids libtpu knows this node's ``num_chips`` chips by: the node's
    own ``TPU_VISIBLE_CHIPS`` where it was started on a subset of its
    host, else 0..n-1."""
    visible = [c.strip() for c in
               os.environ.get("TPU_VISIBLE_CHIPS", "").split(",") if c.strip()]
    if len(visible) == num_chips and all(c.isdigit() for c in visible):
        return [int(c) for c in visible]
    return list(range(num_chips))


def keep_off_accelerators() -> Optional[str]:
    """Pin THIS process, and whatever inherits its environment, to JAX's
    CPU backend; returns the ``JAX_PLATFORMS`` it was launched with (None
    if unset). Control daemons call it first thing: they must never open
    a chip that a worker will need."""
    launched_with = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    return launched_with


def worker_env(base, chips, host_chips: int,
               launch_platforms: Optional[str]) -> Dict[str, str]:
    """Environment of a worker process spawned from ``base``.

    Without ``chips`` the worker runs JAX on the CPU backend whatever the
    host has. With chips it gets back the ``JAX_PLATFORMS`` the node was
    launched with (so the driver's setting decides: unset or ``tpu`` on a
    TPU host, ``cpu`` in the test suite) and, unless it leases the whole
    host, is pinned to exactly its chips."""
    env = dict(base)
    if not chips:
        env["JAX_PLATFORMS"] = "cpu"
        return env
    if launch_platforms is None:
        env.pop("JAX_PLATFORMS", None)
    else:
        env["JAX_PLATFORMS"] = launch_platforms
    if len(chips) < host_chips:
        if len(chips) not in _CHIP_BOUNDS:
            raise ValueError(
                f"a worker can hold {sorted(_CHIP_BOUNDS)} chips or all "
                f"{host_chips} of its host, not {len(chips)}")
        env["TPU_VISIBLE_CHIPS"] = ",".join(str(c) for c in chips)
        env["TPU_CHIPS_PER_HOST_BOUNDS"] = _CHIP_BOUNDS[len(chips)]
        env["TPU_HOST_BOUNDS"] = "1,1,1"
    return env


def chips_from_accelerator_type(accel: str) -> int:
    """Per-host chip count implied by the pod type (fallback when the
    runtime env vars are absent)."""
    from ray_tpu.parallel.slices import SliceTopology

    try:
        topo = SliceTopology.parse(accel)
    except ValueError:
        return 0
    return topo.chips_per_host if topo.num_hosts > 1 else topo.num_chips
