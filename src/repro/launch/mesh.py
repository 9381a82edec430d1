"""Production mesh construction + mesh-aware KVS shard placement.

Defined as functions (never module-level constants) so importing this module
never touches jax device state.  Single pod: 16×16 = 256 chips,
axes (data, model).  Multi-pod: 2×16×16 = 512 chips, axes (pod, data, model);
the pod axis folds into data-parallel/FSDP sharding via the default rules.
"""
from __future__ import annotations

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def _make_mesh(shape, axes) -> Mesh:
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 1, n_model: int = 1) -> Mesh:
    """Small mesh for local smoke runs (1 device by default)."""
    return _make_mesh((n_data, n_model), ("data", "model"))


def make_sharded_backend(n_shards: int = 4, mesh: Mesh | None = None,
                         slot_bytes: int = 1 << 16, n_slots: int = 1024,
                         replication_factor: int = 1,
                         write_quorum: int | None = None,
                         retry=None,
                         cache_bytes: int | None = None,
                         cache_kw: dict | None = None):
    """Mesh-aware shard placement for the store backend.

    Returns a :class:`repro.core.kvs.ShardedKVS` router over ``n_shards``
    :class:`repro.core.kvs.ShardedDeviceKVS` tables.  With a mesh, each
    shard's slot table is pinned to its own round-robin slice of the mesh's
    devices (a strided 1-axis sub-mesh), so a group commit's per-shard
    ``multiput`` and a session read's per-shard ``multiget`` land on
    disjoint device sets.  With fewer devices than shards (CPU smoke runs)
    slices wrap; with no mesh each shard is still a device-table KVS, just
    placed on the default device (use ``ShardedKVS([InMemoryKVS()] * n)``
    for a host-only backend).

    With ``replication_factor=R > 1`` each shard becomes a
    :class:`repro.core.replica.ReplicatedKVS` group of R device tables, each
    replica on its own device slice (n_shards × R disjoint slices), so a
    replica death takes out one device group, not the shard: reads fail
    over inside the group, writes keep landing with ``write_quorum`` acks
    (default 1 — availability-first), and
    :class:`repro.core.replica.RecoveryManager` rebuilds lost replicas from
    the survivors.  ``retry`` is the group's
    :class:`repro.core.replica.RetryPolicy` (default policy if None).

    With ``cache_bytes`` set, the router is topped with a
    :class:`repro.core.cache.CachingKVS` chunk cache of that byte budget
    (``cache_kw`` passes through tuning knobs like ``always_admit_bytes``):
    hot chunks are then served at memory speed and a fully warm session
    ``multiget`` costs 0 device round trips.
    """
    from repro.core.cache import CachingKVS
    from repro.core.kvs import ShardedDeviceKVS, ShardedKVS
    from repro.core.replica import ReplicatedKVS

    def finish(router):
        if cache_bytes:
            return CachingKVS(router, cache_bytes=cache_bytes,
                              **(cache_kw or {}))
        return router

    R = max(1, int(replication_factor))
    n_tables = n_shards * R
    devs = mesh.devices.reshape(-1) if mesh is not None else None

    def make_table(j: int):
        if devs is None:
            return ShardedDeviceKVS(slot_bytes, n_slots)
        group = devs[j::n_tables]
        if len(group) == 0:                    # more tables than devices
            group = devs[j % len(devs):j % len(devs) + 1]
        sub = Mesh(np.asarray(group), ("kv",))
        return ShardedDeviceKVS(slot_bytes, n_slots, mesh=sub)

    if R == 1:
        return finish(ShardedKVS([make_table(i) for i in range(n_shards)]))
    shards = []
    for i in range(n_shards):
        replicas = [make_table(i * R + r) for r in range(R)]
        shards.append(ReplicatedKVS(
            replicas, write_quorum=1 if write_quorum is None else write_quorum,
            retry=retry))
    return finish(ShardedKVS(shards))
