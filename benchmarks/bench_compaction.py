"""Compaction & retention GC: storage reclaimed and query seconds won back.

The degradation workload the maintenance path exists for: a long online
chain (a ``VersionedCheckpointer`` committing training steps — §4 appends
every batch as fresh chunks and never revisits old ones), then
``keep_last(k)`` retention and ONE compaction pass.  Measures, before vs
after: total stored bytes, the layout-health fragmentation score, and the
simulated read seconds (the §2.3 Cassandra-like model) of a 64-query mixed
batch over the retained window.

Asserts the acceptance criteria — ≥30% of stored bytes reclaimed, the mixed
batch measurably faster, retained versions byte-identical — and the
round-trip contract (one multiput round trip per shard the rewrite touches
plus one multidelete round trip per shard the GC touches), so running this
under CI is a maintenance-path regression gate.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import (InMemoryKVS, KVSStats, Q, RStore, RStoreConfig,
                        ShardedKVS, keep_last, measure_layout)

from .common import emit, main, save_json

N_SHARDS = 4
PER_QUERY_S = 5e-4
BANDWIDTH = 200e6


def _ingest_chain(rs, rng, n_versions, n_keys, rec_size):
    """Checkpointer-like churn: fixed keyspace, every commit overwrites a
    couple of blocks — the workload whose old copies all eventually die."""
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


def _simulated_read(kvs, snap, queries):
    s0 = kvs.stats.snapshot()
    res = snap.execute(queries)
    d = KVSStats(n_queries=kvs.stats.n_queries - s0.n_queries,
                 bytes_fetched=kvs.stats.bytes_fetched - s0.bytes_fetched)
    return d.simulated_seconds(PER_QUERY_S, BANDWIDTH), res


def run(smoke: bool = False):
    n_versions = 32 if smoke else 512
    keep = 8 if smoke else 64
    n_keys = 24 if smoke else 96
    rec_size = 128 if smoke else 512
    capacity = 1024 if smoke else 8192
    batch = 8 if smoke else 32

    kvs = ShardedKVS([InMemoryKVS() for _ in range(N_SHARDS)])
    rs = RStore(RStoreConfig(algorithm="bottom_up", capacity=capacity,
                             batch_size=batch), kvs=kvs)
    rng = np.random.default_rng(33)
    vids = _ingest_chain(rs, rng, n_versions, n_keys, rec_size)
    kept = vids[-keep:]

    queries = _mixed_queries(kept, n_keys, np.random.default_rng(34))
    stored_before = kvs.total_stored_bytes()

    # ---- retention, then measure the degraded layout ---------------------
    # (retention is the *logical* change — evolution queries legitimately
    # stop seeing dropped versions' copies — but it moves no bytes, so reads
    # here still price the degraded pre-compaction layout)
    rs.retain(keep_last(keep))
    h_before = measure_layout(rs)
    sim_before, res_before = _simulated_read(kvs, rs.snapshot(), queries)

    # ---- ONE compaction pass ---------------------------------------------
    puts0 = [s.stats.n_put_queries for s in kvs.shards]
    dels0 = [s.stats.n_delete_queries for s in kvs.shards]
    t0 = time.perf_counter()
    rep = rs.compact()
    wall = time.perf_counter() - t0
    assert rep.mode == "pass", rep.mode

    # round-trip contract: ONE multiput per shard the writes touch, ONE
    # multidelete per shard the deletes touch
    dput = [s.stats.n_put_queries - p for s, p in zip(kvs.shards, puts0)]
    ddel = [s.stats.n_delete_queries - d for s, d in zip(kvs.shards, dels0)]
    assert all(d <= 1 for d in dput), f"multiput split per shard: {dput}"
    assert all(d <= 1 for d in ddel), f"multidelete split per shard: {ddel}"
    assert rep.write_round_trips == sum(dput) >= 1, (rep.write_round_trips, dput)
    assert rep.delete_round_trips == sum(ddel) >= 1, (rep.delete_round_trips, ddel)

    stored_after = kvs.total_stored_bytes()
    h_after = measure_layout(rs)
    reclaimed = 1.0 - stored_after / stored_before
    sim_after, res_after = _simulated_read(kvs, rs.snapshot(), queries)

    # retained versions byte-identical through the rewritten layout
    for r0, r1 in zip(res_before, res_after):
        assert r0.value == r1.value, f"result diverged for {r0.query}"
    assert reclaimed >= 0.30, f"only {reclaimed:.1%} of stored bytes reclaimed"
    assert sim_after < sim_before, "compaction did not reduce read seconds"

    out = {
        "n_versions": n_versions, "keep_last": keep, "n_shards": N_SHARDS,
        "stored_bytes": {"before": stored_before, "after": stored_after,
                         "reclaimed_frac": reclaimed},
        "frag_score": {"before": h_before.frag_score,
                       "after": h_after.frag_score},
        "dead_frac_before_pass": h_before.dead_frac,
        "mixed64_simulated_s": {"before": sim_before, "after": sim_after,
                                "speedup": sim_before / sim_after},
        "pass": {"chunks_deleted": rep.chunks_deleted,
                 "chunks_written": rep.chunks_written,
                 "records_dropped": rep.records_dropped,
                 "write_round_trips": rep.write_round_trips,
                 "delete_round_trips": rep.delete_round_trips,
                 "wall_s": wall},
    }
    emit("compaction/storage", 0.0,
         f"reclaimed={reclaimed:.1%} ({stored_before}->{stored_after} B)")
    emit("compaction/frag_score", 0.0,
         f"{h_before.frag_score:.2f}->{h_after.frag_score:.2f}")
    emit("compaction/mixed64_read", 0.0,
         f"sim_ms {sim_before*1e3:.2f}->{sim_after*1e3:.2f} "
         f"({sim_before/sim_after:.2f}x)")
    emit("compaction/round_trips", wall * 1e6,
         f"multiput={rep.write_round_trips}/shard<=1 "
         f"multidelete={rep.delete_round_trips}/shard<=1 "
         f"({rep.chunks_deleted} chunks -> {rep.chunks_written})")
    save_json("bench_compaction", out)
    return out


if __name__ == "__main__":
    main(run)
