"""Write-path group commits: one multiput per shard vs per-blob puts.

The §2.3 argument applied to ingest: the seed's flush issued one ``kvs.put``
per chunk and per chunk map (~2×n_chunks write round trips per flush, plus
one per rebuilt old map).  A :class:`WriteSession` stages a whole wave of
commits and group-flushes them through ONE ``multiput`` — the ShardedKVS
router splits it into exactly one write round trip per shard, so a
64-version flush costs O(shards) backend writes however many chunks it
produced.  Latency is compared under the same Cassandra-like cost model the
read benchmarks use (per-request overhead dominates — the §2.3 effect,
write-side).

Asserts the acceptance criterion (64 versions, 4 shards → exactly 4 write
round trips; reads still one round trip per shard touched), so running this
under CI is a round-trip regression gate.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import (InMemoryKVS, KVSStats, Q, RStore, RStoreConfig,
                        ShardedKVS)

from .common import emit, main, save_json

N_SHARDS = 4
PER_QUERY_S = 5e-4
BANDWIDTH = 200e6


def _ingest(rs, rng, n_versions, n_keys, rec_size):
    def pay():
        return rng.integers(0, 256, rec_size, dtype=np.uint8).tobytes()

    with rs.writer() as w:
        v = w.init_root({k: pay() for k in range(n_keys)})
        for i in range(n_versions - 1):
            v = w.commit([v], adds={int(rng.integers(0, n_keys)): pay(),
                                    n_keys + i: pay()})
    return v


def run(smoke: bool = False):
    n_versions = 16 if smoke else 64
    n_keys = 40 if smoke else 200
    rec_size = 128 if smoke else 512
    # smoke sizes must still produce enough chunks to touch every shard
    capacity = 1024 if smoke else 16 * 1024

    # ---- write session over the sharded router ---------------------------
    kvs = ShardedKVS([InMemoryKVS() for _ in range(N_SHARDS)])
    rs = RStore(RStoreConfig(algorithm="bottom_up", capacity=capacity,
                             batch_size=10**9), kvs=kvs)
    rng = np.random.default_rng(21)
    t0 = time.perf_counter()
    last = _ingest(rs, rng, n_versions, n_keys, rec_size)
    wall = time.perf_counter() - t0

    write_rts = kvs.stats.n_put_queries
    n_blobs = kvs.stats.n_values_put
    assert write_rts == N_SHARDS, \
        f"group flush must be one multiput per shard, got {write_rts}"
    per_shard = [s.stats.n_put_queries for s in kvs.shards]
    assert per_shard == [1] * N_SHARDS, per_shard

    # seed cost: one put per blob (chunks + maps + rebuilt maps), same bytes
    seed = KVSStats(n_put_queries=n_blobs, bytes_stored=kvs.stats.bytes_stored)
    sim_grouped = kvs.stats.simulated_write_seconds(PER_QUERY_S, BANDWIDTH)
    sim_seed = seed.simulated_write_seconds(PER_QUERY_S, BANDWIDTH)

    # ---- reads through the same router: one round trip per shard touched -
    snap = rs.snapshot()
    q0 = kvs.stats.n_queries
    res = snap.execute([Q.version(last)])
    read_rts = kvs.stats.n_queries - q0
    assert 1 <= read_rts <= N_SHARDS, read_rts

    out = {
        "n_versions": n_versions,
        "n_shards": N_SHARDS,
        "grouped": {"write_round_trips": write_rts,
                    "blobs": n_blobs,
                    "bytes": kvs.stats.bytes_stored,
                    "wall_s": wall,
                    "simulated_s": sim_grouped},
        "seed_per_blob": {"write_round_trips": seed.n_put_queries,
                          "simulated_s": sim_seed},
        "read_round_trips_full_version": read_rts,
        "speedup_simulated": sim_seed / sim_grouped,
    }
    emit("write_path/grouped", wall * 1e6 / n_versions,
         f"round_trips={write_rts} blobs={n_blobs} "
         f"sim_ms={sim_grouped*1e3:.2f}")
    emit("write_path/seed_per_blob", 0.0,
         f"round_trips={seed.n_put_queries} sim_ms={sim_seed*1e3:.2f}")
    emit("write_path/speedup", 0.0,
         f"simulated {out['speedup_simulated']:.1f}x fewer backend write "
         f"seconds ({n_blobs} blobs -> {write_rts} round trips)")
    emit("write_path/read_after_write", 0.0,
         f"Q1 round_trips={read_rts} (per shard touched), "
         f"records={len(res[0].value)}")
    save_json("bench_write_path", out)
    return out


if __name__ == "__main__":
    main(run)
