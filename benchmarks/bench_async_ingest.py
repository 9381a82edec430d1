"""Async ingest: cross-session background drains vs per-session sync flush.

The write-path bench showed one session's group commit costs O(shards)
round trips; this one shows the :class:`~repro.core.flusher.BackgroundFlusher`
extends that across sessions — K concurrent sessions staging at ZERO round
trips per commit and draining together in ≤S write round trips on S shards,
where per-session synchronous flushes pay ~K·S.  Latency compared under the
same Cassandra-like cost model (per-request overhead dominates — §2.3,
write-side).

Asserts the acceptance criteria (8 sessions × 64 versions on 4 shards: one
cross-session drain ≤ 4 write round trips, per-commit stage cost = 0 round
trips, ≥3x lower simulated write seconds than per-session sync flush), plus
the degraded-mode contract: the same workload on replicated shards with one
replica of every group killed mid-drain stays byte-identical to the
synchronous-flush oracle, and recover_all converges every replica.  Running
this under CI is the async-ingest regression gate.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import (FaultInjectingKVS, InMemoryKVS, RecoveryManager,
                        ReplicatedKVS, RStore, RStoreConfig, ShardedKVS)

from .common import emit, main, save_json

N_SHARDS = 4
N_SESSIONS = 8
PER_QUERY_S = 5e-4
BANDWIDTH = 200e6


def _cfg(capacity):
    return RStoreConfig(algorithm="bottom_up", capacity=capacity,
                        batch_size=10**9)


def _drive_async(rs, rng, n_versions, n_keys, rec_size):
    """Stage the canonical workload through N_SESSIONS concurrent sessions
    (round-robin interleaved), then barrier once.  Returns (heads, drain
    report, staging round trips observed)."""
    def pay():
        return rng.integers(0, 256, rec_size, dtype=np.uint8).tobytes()

    with rs.writer() as boot:
        root = boot.init_root({k: pay() for k in range(n_keys)})
    sessions = [rs.writer() for _ in range(N_SESSIONS)]
    heads = [root] * N_SESSIONS
    stage_rts = rs.kvs.stats.n_put_queries + rs.kvs.stats.n_queries
    for i in range(n_versions - 1):
        for j, w in enumerate(sessions):
            heads[j] = w.commit(
                [heads[j]], adds={int(rng.integers(0, n_keys)): pay(),
                                  n_keys + i * N_SESSIONS + j: pay()})
    stage_rts = (rs.kvs.stats.n_put_queries + rs.kvs.stats.n_queries
                 - stage_rts)
    rep = rs.barrier()
    for w in sessions:
        w.close()
    return heads, rep, stage_rts


def _drive_sync(rs, rng, n_versions, n_keys, rec_size):
    """Same total commit volume, but each session is its own synchronous
    group flush (the pre-flusher way to run K writers).  Cost baseline
    only — per-session vid order differs from the interleaved runs."""
    def pay():
        return rng.integers(0, 256, rec_size, dtype=np.uint8).tobytes()

    with rs.writer() as boot:
        root = boot.init_root({k: pay() for k in range(n_keys)})
    heads = [root] * N_SESSIONS
    for j in range(N_SESSIONS):
        with rs.writer() as w:
            for i in range(n_versions - 1):
                heads[j] = w.commit(
                    [heads[j]], adds={int(rng.integers(0, n_keys)): pay(),
                                      n_keys + i * N_SESSIONS + j: pay()})
    return heads


def _drive_oracle(rs, rng, n_versions, n_keys, rec_size):
    """Synchronous-flush oracle: the SAME round-robin commit sequence as
    :func:`_drive_async`, but every commit is its own flush
    (``batch_size=1``).  Same sequence -> same vids -> byte-identical
    contents, however the async runs buffer or fail over."""
    def pay():
        return rng.integers(0, 256, rec_size, dtype=np.uint8).tobytes()

    root = rs.init_root({k: pay() for k in range(n_keys)})
    heads = [root] * N_SESSIONS
    for i in range(n_versions - 1):
        for j in range(N_SESSIONS):
            heads[j] = rs.commit(
                [heads[j]], adds={int(rng.integers(0, n_keys)): pay(),
                                  n_keys + i * N_SESSIONS + j: pay()})
    return heads


def run(smoke: bool = False):
    n_versions = 8 if smoke else 64       # per session
    n_keys = 40 if smoke else 200
    rec_size = 128 if smoke else 256
    capacity = 1024 if smoke else 8 * 1024

    # ---- async: K sessions, one cross-session drain ----------------------
    kvs = ShardedKVS([InMemoryKVS() for _ in range(N_SHARDS)])
    rs = RStore(_cfg(capacity), kvs=kvs)
    rs.attach_flusher(max_staged_versions=10**9, max_staged_bytes=1 << 62)
    t0 = time.perf_counter()
    heads, rep, stage_rts = _drive_async(
        rs, np.random.default_rng(33), n_versions, n_keys, rec_size)
    wall_async = time.perf_counter() - t0
    assert stage_rts == 0, \
        f"per-commit stage cost must be 0 round trips, saw {stage_rts}"
    assert rep.write_round_trips <= N_SHARDS, \
        (f"cross-session drain must cost <= {N_SHARDS} write round trips, "
         f"got {rep.write_round_trips}")
    sim_async = kvs.stats.simulated_write_seconds(PER_QUERY_S, BANDWIDTH)
    async_rts = kvs.stats.n_put_queries

    # ---- baseline: per-session synchronous group flushes -----------------
    kvs0 = ShardedKVS([InMemoryKVS() for _ in range(N_SHARDS)])
    rs0 = RStore(_cfg(capacity), kvs=kvs0)
    t0 = time.perf_counter()
    heads0 = _drive_sync(rs0, np.random.default_rng(33), n_versions, n_keys,
                         rec_size)
    wall_sync = time.perf_counter() - t0
    sim_sync = kvs0.stats.simulated_write_seconds(PER_QUERY_S, BANDWIDTH)
    sync_rts = kvs0.stats.n_put_queries
    speedup = sim_sync / sim_async
    assert speedup >= 3.0, \
        f"async drain must be >=3x cheaper in simulated write seconds, got {speedup:.2f}x"

    # ---- synchronous-flush oracle (same round-robin sequence) ------------
    rs_or = RStore(RStoreConfig(algorithm="bottom_up", capacity=capacity,
                                batch_size=1), kvs=InMemoryKVS())
    heads_or = _drive_oracle(rs_or, np.random.default_rng(33), n_versions,
                             n_keys, rec_size)
    assert heads == heads_or
    for v in heads_or:
        assert rs.get_version(v)[0] == rs_or.get_version(v)[0], \
            "async run diverged from synchronous-flush oracle"

    # ---- degraded mode: replicated shards, one replica killed mid-drain --
    groups = [ReplicatedKVS(
        [FaultInjectingKVS(InMemoryKVS(), seed=70 + i * 2 + r)
         for r in range(2)], write_quorum=1) for i in range(N_SHARDS)]
    kvs2 = ShardedKVS(groups)
    rs2 = RStore(_cfg(capacity), kvs=kvs2)
    rs2.attach_flusher(max_staged_versions=10**9)
    rng2 = np.random.default_rng(33)

    def pay2():
        return rng2.integers(0, 256, rec_size, dtype=np.uint8).tobytes()

    with rs2.writer() as boot:
        root2 = boot.init_root({k: pay2() for k in range(n_keys)})
    sessions2 = [rs2.writer() for _ in range(N_SESSIONS)]
    heads2 = [root2] * N_SESSIONS
    killed = False
    for i in range(n_versions - 1):
        if not killed and i >= (n_versions - 1) // 2:
            # first buffer is durable; kill replica 0 of every group so the
            # NEXT drain discovers the dead replica and fails over mid-batch
            rs2.barrier()
            for g in groups:
                g.replicas[0].kill()
            killed = True
        for j, w in enumerate(sessions2):
            heads2[j] = w.commit(
                [heads2[j]], adds={int(rng2.integers(0, n_keys)): pay2(),
                                   n_keys + i * N_SESSIONS + j: pay2()})
    rs2.barrier()                          # drains through the failover
    for w in sessions2:
        w.close()
    assert heads2 == heads_or
    for v in heads_or:
        assert rs2.get_version(v)[0] == rs_or.get_version(v)[0], \
            "degraded async run diverged from synchronous-flush oracle"
    # recovery: every replica of every group converges byte-identically
    for g in groups:
        g.replicas[0].revive()
    RecoveryManager(kvs2).recover_all()
    for g in groups:
        want = dict(g.replicas[0].inner.scan())
        for idx, r in enumerate(g.replicas):
            assert dict(r.inner.scan()) == want
            assert g.pending_repairs(idx) == 0

    total_versions = 1 + N_SESSIONS * (n_versions - 1)
    out = {
        "n_sessions": N_SESSIONS,
        "n_versions_per_session": n_versions,
        "n_shards": N_SHARDS,
        "total_versions": total_versions,
        "async": {"stage_round_trips": stage_rts,
                  "drain_round_trips": rep.write_round_trips,
                  "total_write_round_trips": async_rts,
                  "wall_s": wall_async,
                  "simulated_s": sim_async},
        "sync_per_session": {"total_write_round_trips": sync_rts,
                             "wall_s": wall_sync,
                             "simulated_s": sim_sync},
        "speedup_simulated": speedup,
        "degraded_byte_identical": True,
    }
    emit("async_ingest/stage", 0.0,
         f"{total_versions} versions staged at {stage_rts} round trips")
    emit("async_ingest/drain", wall_async * 1e6 / total_versions,
         f"{N_SESSIONS} sessions -> {rep.write_round_trips} write round "
         f"trips (<= {N_SHARDS} shards), sim_ms={sim_async*1e3:.2f}")
    emit("async_ingest/sync_baseline", wall_sync * 1e6 / total_versions,
         f"round_trips={sync_rts} sim_ms={sim_sync*1e3:.2f}")
    emit("async_ingest/speedup", 0.0,
         f"simulated {speedup:.1f}x fewer backend write seconds "
         f"({sync_rts} -> {async_rts} round trips)")
    emit("async_ingest/degraded", 0.0,
         "replica killed mid-drain: byte-identical to sync oracle, "
         "recover_all converged")
    save_json("bench_async_ingest", out)
    return out


if __name__ == "__main__":
    main(run)
