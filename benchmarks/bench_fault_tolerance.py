"""Fault tolerance: degraded-read overhead and shard recovery cost.

The availability workload the replication layer exists for: a sharded,
R=2-replicated backend serving a 64-query mixed batch when one replica of
every shard group is killed mid-workload.  Measures, healthy vs degraded vs
recovered: router read round trips, per-group failover hops, and the
simulated read seconds (§2.3 Cassandra-like model, plus the deterministic
retry backoff the group would have slept).

Asserts the acceptance criteria — the degraded batch returns byte-identical
results, at most ONE extra read round trip per failed-over shard batch
(and ZERO extra on the next batch: a hard-down replica is skipped, not
re-probed), writes keep landing at quorum 1 while degraded — and the
recovery contract: ``RecoveryManager.rebuild`` restores each lost replica
in O(1) round trips per surviving peer (one survivor scan + ≤3 ops on the
target), after which reads are served by the rebuilt replica again.
Running this under CI is the degraded-mode regression gate.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import (FaultInjectingKVS, InMemoryKVS, KVSStats, Q,
                        RecoveryManager, ReplicatedKVS, RStore, RStoreConfig,
                        ShardedKVS)

from .common import emit, main, save_json

N_SHARDS = 4
R = 2
PER_QUERY_S = 5e-4
BANDWIDTH = 200e6


def _make_backend():
    groups = [
        ReplicatedKVS([FaultInjectingKVS(InMemoryKVS(), seed=1000 + i * R + r)
                       for r in range(R)], write_quorum=1)
        for i in range(N_SHARDS)]
    return ShardedKVS(groups), groups


def _ingest_chain(rs, rng, n_versions, n_keys, rec_size):
    def pay():
        return rng.integers(0, 256, rec_size, dtype=np.uint8).tobytes()

    v = rs.init_root({k: pay() for k in range(n_keys)})
    vids = [v]
    for _ in range(n_versions - 1):
        ks = rng.choice(n_keys, size=2, replace=False)
        v = rs.commit([v], adds={int(k): pay() for k in ks})
        vids.append(v)
    rs.flush()
    return vids


def _mixed_queries(vids, n_keys, rng, n=64):
    qs = []
    for i in range(n):
        v = vids[i % len(vids)]
        kind = i % 4
        if kind == 0:
            qs.append(Q.version(v))
        elif kind == 1:
            qs.append(Q.record(v, int(rng.integers(0, n_keys))))
        elif kind == 2:
            lo = int(rng.integers(0, n_keys))
            qs.append(Q.range(v, lo, lo + n_keys // 8))
        else:
            qs.append(Q.evolution(int(rng.integers(0, n_keys))))
    return qs


def _timed_batch(kvs, groups, snap, queries):
    """Execute a batch; return (results, router_read_rts, group_failover
    hops this batch, simulated seconds incl. retry backoff)."""
    s0 = kvs.stats.snapshot()
    f0 = [g.stats.n_failovers for g in groups]
    b0 = sum(g.stats.simulated_backoff_seconds for g in groups)
    res = snap.execute(queries)
    d = KVSStats(n_queries=kvs.stats.n_queries - s0.n_queries,
                 bytes_fetched=kvs.stats.bytes_fetched - s0.bytes_fetched)
    hops = [g.stats.n_failovers - f for g, f in zip(groups, f0)]
    backoff = sum(g.stats.simulated_backoff_seconds for g in groups) - b0
    sim = (d.simulated_seconds(PER_QUERY_S, BANDWIDTH)
           + sum(hops) * PER_QUERY_S + backoff)
    return res, d.n_queries, hops, sim


def run(smoke: bool = False):
    n_versions = 24 if smoke else 256
    n_keys = 24 if smoke else 96
    rec_size = 128 if smoke else 512
    capacity = 1024 if smoke else 8192
    batch = 8 if smoke else 32

    kvs, groups = _make_backend()
    rs = RStore(RStoreConfig(algorithm="bottom_up", capacity=capacity,
                             batch_size=batch), kvs=kvs)
    rng = np.random.default_rng(41)
    vids = _ingest_chain(rs, rng, n_versions, n_keys, rec_size)
    queries = _mixed_queries(vids[-16:], n_keys, np.random.default_rng(42))
    snap = rs.snapshot()

    # ---- healthy baseline -------------------------------------------------
    res_healthy, rts_healthy, hops, sim_healthy = _timed_batch(
        kvs, groups, snap, queries)
    assert sum(hops) == 0, f"healthy run failed over: {hops}"

    # ---- kill one replica of every shard group mid-workload ---------------
    for g in groups:
        g.replicas[0].kill()
    res_degraded, rts_degraded, hops1, sim_degraded = _timed_batch(
        kvs, groups, snap, queries)

    for r0, r1 in zip(res_healthy, res_degraded):
        assert r0.value == r1.value, f"degraded result diverged for {r0.query}"
    # ≤ 1 extra read round trip per failed-over shard batch
    assert all(h <= 1 for h in hops1), f"failover hops per group: {hops1}"
    assert sum(hops1) >= 1, "nothing failed over despite the kill"
    assert rts_degraded == rts_healthy, (rts_degraded, rts_healthy)

    # next degraded batch: the dead replica is skipped at zero extra cost
    res_again, _, hops2, _ = _timed_batch(kvs, groups, snap, queries)
    assert sum(hops2) == 0, f"re-probed a known-down replica: {hops2}"
    for r0, r1 in zip(res_healthy, res_again):
        assert r0.value == r1.value

    # writes keep landing while degraded (quorum 1 of 2)
    v = vids[-1]
    with rs.writer() as w:
        for _ in range(4):
            k = int(rng.integers(0, n_keys))
            v = w.commit([v], adds={k: rng.integers(
                0, 256, rec_size, dtype=np.uint8).tobytes()})
            vids.append(v)
    got, _ = rs.get_version(v)
    assert len(got) == n_keys

    # ---- recovery ---------------------------------------------------------
    for g in groups:
        g.replicas[0].revive()
    rm = RecoveryManager(kvs)
    t0 = time.perf_counter()
    reports = [rm.rebuild(0, shard=i) for i in range(N_SHARDS)]
    recovery_wall = time.perf_counter() - t0
    # O(1) round trips per surviving peer: one survivor scan + ≤3 target ops
    assert all(r.read_round_trips == 2 for r in reports), reports
    assert all(r.round_trips <= 4 for r in reports), reports
    assert all(g.preferred == 0 for g in groups), "rebuilt replica not preferred"

    snap = rs.snapshot()
    r0q0 = [g.replicas[0].stats.n_queries for g in groups]
    res_rec, rts_rec, hops3, sim_rec = _timed_batch(kvs, groups, snap, queries)
    assert sum(hops3) == 0, f"failed over after recovery: {hops3}"
    served = sum(g.replicas[0].stats.n_queries - q for g, q in zip(groups, r0q0))
    assert served >= 1, "rebuilt replicas served no reads"
    # version contents are immutable, so every non-evolution query matches
    # the healthy run byte-for-byte (evolutions legitimately grew by the
    # degraded-mode commits)
    for r0, r1 in zip(res_healthy, res_rec):
        if r0.query.kind != "evolution":
            assert r0.value == r1.value, f"post-recovery diverged: {r0.query}"

    recovery_bytes = sum(r.bytes_copied for r in reports)
    out = {
        "n_versions": n_versions, "n_shards": N_SHARDS,
        "replication_factor": R,
        "mixed64_read_round_trips": {"healthy": rts_healthy,
                                     "degraded": rts_degraded},
        "failover_hops": {"first_degraded_batch": hops1,
                          "second_degraded_batch": hops2},
        "mixed64_simulated_s": {"healthy": sim_healthy,
                                "degraded": sim_degraded,
                                "recovered": sim_rec,
                                "overhead_frac":
                                    sim_degraded / sim_healthy - 1.0},
        "recovery": {"round_trips": [r.round_trips for r in reports],
                     "keys_copied": sum(r.keys_copied for r in reports),
                     "bytes_copied": recovery_bytes,
                     "stale_keys_deleted":
                         sum(r.stale_keys_deleted for r in reports),
                     "wall_s": recovery_wall},
    }
    emit("fault/degraded_read", 0.0,
         f"sim_ms {sim_healthy*1e3:.2f}->{sim_degraded*1e3:.2f} "
         f"(+{(sim_degraded/sim_healthy-1)*100:.1f}%) "
         f"hops={sum(hops1)}<=1/shard-batch then {sum(hops2)}")
    emit("fault/round_trips", 0.0,
         f"healthy={rts_healthy} degraded={rts_degraded} (router-level equal)")
    emit("fault/recovery", recovery_wall * 1e6,
         f"{N_SHARDS} replicas rebuilt, {recovery_bytes} B copied, "
         f"<=4 round trips each")
    save_json("bench_fault_tolerance", out)
    return out


if __name__ == "__main__":
    main(run)
