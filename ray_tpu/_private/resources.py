"""Resource accounting.

Analog of the reference's scheduling resource model
(`src/ray/raylet/scheduling/cluster_resource_manager`, `NodeResources`):
a node advertises a map of resource name → float capacity; tasks/actors demand
resource maps; placement-group bundles reserve slices and re-expose them under
formatted names.

TPU-first: chips are a first-class resource ("TPU"), and a whole ICI slice is
gang-schedulable via the "TPU-<topology>-head" resource convention the
reference introduced for multi-host TPU pods
(`python/ray/_private/accelerators/tpu.py:44-49`) — a pod-slice job grabs the
head resource on host 0 and per-host "TPU" chips elsewhere.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

EPS = 1e-9

CPU = "CPU"
TPU = "TPU"
MEMORY = "memory"
OBJECT_STORE_MEMORY = "object_store_memory"


class ResourceSet(dict):
    """A {name: amount} map with arithmetic. Amounts are floats >= 0."""

    @classmethod
    def of(cls, mapping: Optional[Dict[str, float]]) -> "ResourceSet":
        rs = cls()
        if mapping:
            for k, v in mapping.items():
                if v < 0:
                    raise ValueError(f"negative resource {k}={v}")
                if v > 0:
                    rs[k] = float(v)
        return rs

    def fits(self, other: "ResourceSet") -> bool:
        """True if self has at least `other` of every resource."""
        return all(self.get(k, 0.0) + EPS >= v for k, v in other.items())

    def subtract(self, other: "ResourceSet") -> None:
        for k, v in other.items():
            cur = self.get(k, 0.0) - v
            if cur < -EPS:
                raise ValueError(f"resource {k} went negative ({cur})")
            if cur <= EPS:
                self.pop(k, None)
            else:
                self[k] = cur

    def add(self, other: "ResourceSet") -> None:
        for k, v in other.items():
            self[k] = self.get(k, 0.0) + v

    def copy(self) -> "ResourceSet":
        return ResourceSet.of(self)

    def utilization(self, total: "ResourceSet") -> float:
        """Max fractional utilization across resources present in `total`."""
        util = 0.0
        for k, cap in total.items():
            if cap > 0:
                used = cap - self.get(k, 0.0)
                util = max(util, used / cap)
        return util


def detect_node_resources(
    num_cpus: Optional[float] = None,
    num_tpus: Optional[int] = None,
    memory_bytes: Optional[int] = None,
    object_store_bytes: Optional[int] = None,
    custom: Optional[Dict[str, float]] = None,
) -> ResourceSet:
    """Detect this host's schedulable resources.

    TPU detection never initializes a jax backend (which would claim the
    chips): explicit args win, then TPU_VISIBLE_CHIPS isolation, then the
    device files of the chips really attached, and only where there are
    none the TPU topology env vars a TPU runtime sets. We only count.
    """
    rs = ResourceSet()
    rs[CPU] = float(num_cpus if num_cpus is not None else (os.cpu_count() or 1))
    if num_tpus is None:
        num_tpus = _detect_tpu_chips()
    if num_tpus:
        rs[TPU] = float(num_tpus)
    if memory_bytes is None:
        memory_bytes = _detect_memory()
    rs[MEMORY] = float(memory_bytes)
    if object_store_bytes:
        rs[OBJECT_STORE_MEMORY] = float(object_store_bytes)
    if custom:
        for k, v in custom.items():
            rs[k] = float(v)
    # TPU pod membership (GKE env / GCE metadata): accelerator-type label +
    # the slice-head gang resource on worker 0. Explicit custom resources win.
    if rs.get(TPU):
        from ray_tpu._private.accelerators import tpu_pod_resources

        for k, v in tpu_pod_resources().items():
            rs.setdefault(k, float(v))
    return rs


def _detect_tpu_chips() -> int:
    # TPU_VISIBLE_CHIPS-style isolation (reference accelerators/tpu.py:30).
    visible = os.environ.get("TPU_VISIBLE_CHIPS")
    if visible:
        return len([c for c in visible.split(",") if c.strip()])
    if os.environ.get("RAY_TPU_FORCE_TPU_CHIPS"):
        return int(os.environ["RAY_TPU_FORCE_TPU_CHIPS"])
    # the chips that are really attached: the environment may describe a
    # larger host than this VM was given (a one-chip machine cut from a
    # 2x2 host still says TPU_CHIPS_PER_HOST_BOUNDS=2,2,1), and a bare VM
    # may set nothing at all
    from ray_tpu._private.accelerators import (chips_from_accelerator_type,
                                               count_local_chips)

    attached = count_local_chips()
    if attached:
        return attached
    chips = os.environ.get("TPU_CHIPS_PER_HOST_BOUNDS")
    if chips:
        try:
            dims = [int(x) for x in chips.split(",")]
            n = 1
            for d in dims:
                n *= d
            return n
        except ValueError:
            pass
    # GKE sets the pod accelerator type but not per-host chip bounds:
    # derive chips/host from the topology (accelerators.py discovery)
    accel = os.environ.get("TPU_ACCELERATOR_TYPE")
    if accel:
        return chips_from_accelerator_type(accel)
    return 0


def _detect_memory() -> int:
    try:
        import psutil

        return int(psutil.virtual_memory().total)
    except Exception:
        return 8 * 1024**3


def pg_resource_name(pg_id_hex: str, bundle_index: int | None = None) -> str:
    """Formatted resource name for a placement-group bundle reservation.

    Mirrors the reference's `<name>_group_<index>_<pg_id>` convention so tasks
    scheduled into a bundle consume the reserved slice, not the free pool.
    """
    if bundle_index is None:
        return f"bundle_group_{pg_id_hex}"
    return f"bundle_group_{bundle_index}_{pg_id_hex}"
