"""Chunk cache: warm-read round-trip elimination and epoch coherence.

The online counterpart of the paper's offline layout tuning: a
cost-model-driven read cache (:class:`repro.core.cache.CachingKVS`) over the
sharded backend, measured on the mixed-64 query batch (version / record /
range / evolution mix).

Asserts the acceptance criteria, which are also the CI smoke gates:

1. a FULLY WARM cache serves the mixed-64 batch with 0 backend read round
   trips and ≥5x lower simulated seconds (§2.3 Cassandra-like model);
2. a COLD cache costs exactly the same read round trips as an uncached run
   of the identical store — the cache layer adds no traffic of its own;
3. after a ``retain(keep_last(k))`` + ``compact()`` pass invalidates the
   touched chunks, reads through the (previously warm) cache stay
   byte-identical to fresh uncached reads.

Also reports ``prefetch_evolution``: after the VersionGraph-path warm-up, an
evolution query runs with 0 backend read round trips.
"""
from __future__ import annotations

import numpy as np

from repro.core import (CachingKVS, InMemoryKVS, KVSStats, Q, RStore,
                        RStoreConfig, ShardedKVS, keep_last)
from repro.core.costmodel import BANDWIDTH_BPS, PER_QUERY_S

from .common import emit, main, save_json

N_SHARDS = 4
CACHE_BYTES = 64 << 20


def _make_store(cached: bool, capacity: int, batch: int):
    inner = ShardedKVS([InMemoryKVS() for _ in range(N_SHARDS)])
    kvs = CachingKVS(inner, cache_bytes=CACHE_BYTES) if cached else inner
    rs = RStore(RStoreConfig(algorithm="bottom_up", capacity=capacity,
                             batch_size=batch), kvs=kvs)
    return rs, kvs


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


def _sim(batch) -> float:
    return KVSStats(n_queries=batch.kvs_queries,
                    bytes_fetched=batch.bytes_fetched).simulated_seconds(
                        PER_QUERY_S, BANDWIDTH_BPS)


def run(smoke: bool = False):
    n_versions = 24 if smoke else 256
    n_keys = 24 if smoke else 96
    rec_size = 128 if smoke else 512
    capacity = 1024 if smoke else 8192
    batch = 8 if smoke else 32

    # identically-driven stores: cached subject, uncached reference
    rs, kvs = _make_store(True, capacity, batch)
    rs0, _ = _make_store(False, capacity, batch)
    vids = _ingest_chain(rs, np.random.default_rng(41), n_versions, n_keys,
                         rec_size)
    vids0 = _ingest_chain(rs0, np.random.default_rng(41), n_versions, n_keys,
                          rec_size)
    assert vids == vids0
    queries = _mixed_queries(vids[-16:], n_keys, np.random.default_rng(42))
    snap, snap0 = rs.snapshot(), rs0.snapshot()

    # ---- gate 2: cold cache == uncached round trips -----------------------
    ref = snap0.execute(queries)
    cold = snap.execute(queries)
    assert cold.batch.kvs_queries == ref.batch.kvs_queries, \
        (cold.batch.kvs_queries, ref.batch.kvs_queries)
    for a, b in zip(cold, ref):
        assert a.value == b.value, f"cold result diverged for {a.query}"

    # ---- gate 1: warm cache = 0 read round trips, >=5x lower sim seconds --
    warm = snap.execute(queries)
    assert warm.batch.kvs_queries == 0, warm.batch.kvs_queries
    assert warm.batch.cache_hits > 0
    for a, b in zip(warm, ref):
        assert a.value == b.value, f"warm result diverged for {a.query}"
    sim_cold, sim_warm = _sim(cold.batch), _sim(warm.batch)
    assert sim_warm == 0.0                      # zero backend traffic
    # >=5x criterion: with 0 round trips and 0 bytes the warm batch costs 0
    # simulated seconds, so any 5x bound holds with infinite headroom
    assert sim_cold >= 5 * sim_warm and sim_cold > 0

    # ---- prefetch_evolution: graph-path warm-up -> 0-RT evolution ---------
    rs_p, _ = _make_store(True, capacity, batch)
    _ingest_chain(rs_p, np.random.default_rng(41), n_versions, n_keys,
                  rec_size)
    snap_p = rs_p.snapshot()
    pk = int(np.random.default_rng(43).integers(0, n_keys))
    pre = snap_p.prefetch_evolution(pk)
    evo = snap_p.execute([Q.evolution(pk)])
    assert evo.batch.kvs_queries == 0, evo.batch.kvs_queries
    assert evo[0].value == rs0.get_evolution(pk)[0]

    # ---- gate 3: retention + compaction invalidate; warm reads stay exact -
    keep = max(4, n_versions // 4)
    for store in (rs, rs0):
        store.retain(keep_last(keep))
        store.compact()
    inv_before = kvs.cache_report()["n_invalidations"]
    assert inv_before > 0, "compaction pass invalidated nothing"
    retained = vids[-keep:]
    post = rs.snapshot().execute([Q.version(v) for v in retained])
    post0 = rs0.snapshot().execute([Q.version(v) for v in retained])
    for a, b in zip(post, post0):
        assert a.value == b.value, "post-compaction cached read diverged"

    rep = rs.cache_stats()
    out = {
        "n_versions": n_versions, "n_shards": N_SHARDS,
        "cache_bytes": CACHE_BYTES,
        "mixed64_read_round_trips": {"uncached": ref.batch.kvs_queries,
                                     "cold": cold.batch.kvs_queries,
                                     "warm": warm.batch.kvs_queries},
        "mixed64_simulated_s": {"cold": sim_cold, "warm": sim_warm,
                                "speedup": "inf (0 backend traffic)"},
        "warm_batch": {"cache_hits": warm.batch.cache_hits,
                       "bytes_from_cache": warm.batch.bytes_from_cache},
        "prefetch_evolution": {**pre,
                               "query_round_trips": evo.batch.kvs_queries},
        "post_compaction": {"invalidations": rep["n_invalidations"],
                            "byte_identical": True},
        "cache_report": rep,
    }
    emit("cache/warm_round_trips", 0.0,
         f"uncached={ref.batch.kvs_queries} cold={cold.batch.kvs_queries} "
         f"warm=0 sim_ms {sim_cold*1e3:.2f}->0.00 (>=5x with inf headroom)")
    emit("cache/prefetch_evolution", 0.0,
         f"warmed_keys={pre['warmed_keys']} then evolution rts=0")
    emit("cache/compaction_coherence", 0.0,
         f"invalidations={rep['n_invalidations']} hit_rate="
         f"{rep['hit_rate']:.2f} post-compact byte-identical")
    save_json("bench_cache", out)
    return out


if __name__ == "__main__":
    main(run)
