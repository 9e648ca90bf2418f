"""One-copy-per-node shared model weights for serve replicas.

Every LLM replica on a node used to materialize its own full copy of the
(immutable) parameters, capping replicas-per-host and making scale-up
minutes of checkpoint staging. Here the FIRST replica on a node publishes
the host params into the node's shared-memory object arena (the PR-2
zero-copy put path — one memcpy per leaf buffer) and registers the
resulting ObjectRef in the controller KV under a (weights-key, node)
scoped entry; every LATER replica on that node ``get``s the ref and
deserializes pinned READ-ONLY numpy views over its own mmap of the same
arena range — zero additional arena bytes per replica, only pins.

The pins ride the PR-2 per-client pin accounting: a replica that dies
without unpinning has its pins reclaimed by the supervisor's dead-client
sweep, so replica churn can never leak the weights range (and the last
death lets the arena copy spill/free normally).

Cross-node delivery: replicas landing on a NEW node either pull the
global ref (chunked pipelined cross-node transfer into the local arena,
then publish locally) or — for seconds-scale scale-up without touching
the loader/checkpoint path at all — receive the tree over
``collective.broadcast`` from an existing replica
(:func:`broadcast_params`), then publish into their own node arena.
"""

from __future__ import annotations

import logging
import pickle
from typing import Any, Callable, Dict, Optional, Tuple

logger = logging.getLogger(__name__)

_KV_NS = "serve_weights"

# key -> (ObjectRef, views): holds the ref (so the owner never frees the
# object) and the views (so this process's pins persist) for the process
# lifetime. Replica death releases both through normal dead-client sweeps.
_HELD: Dict[str, Tuple[Any, Any]] = {}


def _tree_to_host(params):
    """Device pytree -> host numpy pytree (the arena-publishable form)."""
    import jax
    import numpy as np

    return jax.tree.map(np.asarray, params)


def tree_nbytes(params) -> int:
    import jax

    return sum(x.nbytes for x in jax.tree.leaves(params))


def _pack_ref(ref) -> Dict[str, Any]:
    return {"oid": ref._object_id.binary(), "owner": list(ref._owner_addr)}


def _unpack_ref(d):
    from ray_tpu._private.api import ObjectRef
    from ray_tpu._private.ids import ObjectID

    # skip_ref_counting: the publisher holds the canonical local ref in
    # _HELD; readers only pin via their views
    return ObjectRef(ObjectID(bytes(d["oid"])), tuple(d["owner"]),
                     skip_ref_counting=True)


def _cluster_ready() -> bool:
    from ray_tpu._private import api

    core = api._core
    return (core is not None and core.supervisor_addr is not None
            and core.arena is not None)


def get_or_publish(key: str, loader: Callable[[], Any], *,
                   timeout_s: float = 180.0) -> Tuple[Any, Dict[str, Any]]:
    """Return ``(host_params, info)`` with one arena copy per node.

    The first caller per node wins a KV claim, builds the params via
    ``loader()`` (or pulls another node's published copy through the
    chunked cross-node path), publishes them into the node arena, and
    registers the ref; every other caller on the node blocks on the ref
    key and attaches zero-copy. ``host_params`` is a pytree of READ-ONLY
    numpy views over the node arena for attached callers (callers
    typically ``jax.device_put`` it once into their own device memory).

    Falls back to a plain local ``loader()`` (``info["mode"] == "local"``)
    when no cluster/arena is reachable, so direct instantiation outside a
    cluster keeps working.
    """
    if not _cluster_ready():
        params = loader()
        return params, {"mode": "local", "shared": False}

    import ray_tpu
    from ray_tpu._private import api
    from ray_tpu._private import internal_kv as kv

    core = api._core
    node = core.node_id_hex or "local"
    me = core._store_client_id
    ref_key = f"ref:{key}@{node}"
    claim_key = f"claim:{key}@{node}"
    global_key = f"ref:{key}@global"

    for attempt in range(2):
        packed = kv.kv_get(ref_key, ns=_KV_NS)
        published = False
        source = "arena"
        if packed is None:
            if kv.kv_put(claim_key, me, ns=_KV_NS, overwrite=False):
                # we are this node's publisher
                try:
                    params, source = _materialize(global_key, loader,
                                                  timeout_s)
                    host = _tree_to_host(params)
                    del params
                    need = tree_nbytes(host)
                    if need > core.arena.size:
                        # loud, before the put: a store that cannot hold
                        # the weights must not be papered over by spills
                        raise RuntimeError(
                            f"shared weights {key!r} need {need} bytes but "
                            f"this node's object store holds "
                            f"{core.arena.size}; start the cluster with a "
                            f"larger object_store_memory or deploy with "
                            f"share_weights=False")
                    ref = ray_tpu.put(host)
                    del host  # the loader copy dies; the arena copy stays
                    packed = _pack_ref(ref)
                    kv.kv_put(ref_key, packed, ns=_KV_NS)
                    kv.kv_put(global_key, packed, ns=_KV_NS,
                              overwrite=False)
                    _HELD[ref_key] = (ref, None)
                    published = True
                except BaseException:
                    # release the claim so another replica can retry the
                    # election instead of deadlocking on kv_wait
                    try:
                        kv.kv_del(claim_key, ns=_KV_NS)
                    except Exception:
                        pass
                    raise
            else:
                try:
                    packed = kv.kv_wait(ref_key, timeout=timeout_s,
                                        ns=_KV_NS)
                except TimeoutError:
                    # claimed but never published (claimant died mid-load):
                    # clear the claim and re-run the election
                    try:
                        kv.kv_del(claim_key, ns=_KV_NS)
                    except Exception:
                        pass
                    continue
        ref = _unpack_ref(packed) if not published else _HELD[ref_key][0]
        try:
            views = ray_tpu.get(ref, timeout=timeout_s)
        except Exception:
            if published:
                raise
            # stale registration (the arena copy is gone — e.g. the whole
            # node restarted under the same KV): drop it and re-elect
            logger.warning("shared-weights ref %s is stale; re-electing",
                           ref_key, exc_info=True)
            for k in (ref_key, claim_key):
                try:
                    kv.kv_del(k, ns=_KV_NS)
                except Exception:
                    pass
            if attempt == 0:
                continue
            raise
        _HELD[ref_key] = (ref, views)
        info = {
            "mode": "published" if published else "attached",
            "shared": True,
            "source": source if published else "arena",
            "key": key,
            "node": node,
            "ref": ref.hex(),
            "nbytes": tree_nbytes(views),
        }
        return views, info
    raise RuntimeError(
        f"could not obtain shared weights for {key!r} within {timeout_s}s")


def _materialize(global_key: str, loader, timeout_s: float):
    """Publisher-side parameter source: prefer pulling another node's
    published copy (chunked cross-node arena transfer — no checkpoint /
    loader cost) over running the loader."""
    import ray_tpu
    from ray_tpu._private import internal_kv as kv

    packed = kv.kv_get(global_key, ns=_KV_NS)
    if packed is not None:
        try:
            return ray_tpu.get(_unpack_ref(packed),
                               timeout=timeout_s), "pull"
        except Exception:
            logger.warning("global weights ref is stale; running loader",
                           exc_info=True)
    return loader(), "loader"


def release(key: str) -> None:
    """Drop this process's hold (views + ref) on a shared-weights entry —
    for tests and explicit teardown; normal replica death releases through
    the dead-client pin sweep."""
    node = ""
    try:
        from ray_tpu._private import api

        node = api._core.node_id_hex if api._core is not None else ""
    except Exception:
        pass
    _HELD.pop(f"ref:{key}@{node or 'local'}", None)


# ------------------------------------------------------------- broadcast


def broadcast_params(params: Optional[Any], group_name: str,
                     world_size: int, rank: int, *, root: int = 0,
                     timeout_ms: int = 120_000):
    """Deliver a params pytree to new-node replicas over
    ``collective.broadcast`` (shm on one node, chunked p2p ring across
    nodes — never the controller). The root passes the tree; receivers
    pass ``None`` and get the identical tree back. The tree structure +
    leaf specs travel as a pickled uint8 header broadcast, then one
    broadcast per leaf (the transport frames carry dtype/shape, so
    receivers need no pre-sized template).

    Each participant runs in its own task/actor; the group is imperative
    and destroyed on exit, so repeated scale-ups with fresh group names
    never collide.
    """
    import jax
    import numpy as np

    from ray_tpu.util import collective as col

    col.init_collective_group(world_size, rank, backend="host",
                              group_name=group_name)
    try:
        if rank == root:
            if params is None:
                raise ValueError("broadcast root must pass the params tree")
            host = _tree_to_host(params)
            leaves, treedef = jax.tree.flatten(host)
            spec = pickle.dumps(treedef)
            col.broadcast(np.frombuffer(spec, np.uint8), src_rank=root,
                          group_name=group_name, timeout_ms=timeout_ms)
            for leaf in leaves:
                col.broadcast(np.ascontiguousarray(leaf), src_rank=root,
                              group_name=group_name, timeout_ms=timeout_ms)
            return host
        spec = col.broadcast(np.empty(0, np.uint8), src_rank=root,
                             group_name=group_name, timeout_ms=timeout_ms)
        treedef = pickle.loads(bytes(spec))
        leaves = [col.broadcast(np.empty(0, np.uint8), src_rank=root,
                                group_name=group_name,
                                timeout_ms=timeout_ms)
                  for _ in range(treedef.num_leaves)]
        return jax.tree.unflatten(treedef, leaves)
    finally:
        try:
            col.destroy_collective_group(group_name)
        except Exception:
            pass
