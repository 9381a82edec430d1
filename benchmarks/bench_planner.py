"""Unified query planner: composite pushdown + index-only execution gates.

The planner's two headline promises, asserted as CI smoke gates:

1. COMPOSITE PUSHDOWN — ``Q.and_(Q.where(...), Q.where_range(...))`` runs
   as ONE ``and_popcount``-family kernel launch and ONE interleaved
   multiget, fetches FEWER chunks than either predicate alone, and is
   byte-identical to the client-side two-session intersection it replaces
   (which paid two launches and two multigets).
2. INDEX-ONLY AGGREGATES — ``Q.count`` / ``Q.distinct`` on an indexed
   attribute answer from postings + chunk maps with ZERO chunk-payload
   read round trips (``stats.payload_round_trips == 0``).

Also reports predicted (``snap.explain``) vs measured chunk fetches — the
costmodel's plan-time view against the lossy-projection reality.
"""
from __future__ import annotations

import numpy as np

from repro.core import (InMemoryKVS, KVSStats, Q, RStore, RStoreConfig,
                        ShardedKVS)
from repro.core.costmodel import BANDWIDTH_BPS, PER_QUERY_S
from repro.core.secondary import datagen_extractor
from repro.kernels import ops

from .common import emit, main, save_json

N_SHARDS = 2
A0, A1 = "f0", "f1"               # two uint32 attrs of the datagen layout


def _make_store(capacity: int):
    kvs = ShardedKVS([InMemoryKVS() for _ in range(N_SHARDS)])
    rs = RStore(RStoreConfig(algorithm="bottom_up", capacity=capacity,
                             batch_size=8), kvs=kvs)
    rs.create_index(A0, datagen_extractor(2))
    rs.create_index(A1, datagen_extractor(2))
    return rs


def _ingest(rs, rng, n_keys, n_versions, rec_size, card0, card1):
    def pay():
        t0 = int(rng.integers(0, card0))
        t1 = int(rng.integers(0, card1))
        return (t0.to_bytes(4, "little") + t1.to_bytes(4, "little")
                + rng.integers(0, 256, rec_size - 8, dtype=np.uint8).tobytes())

    with rs.writer() as w:
        v = w.init_root({pk: pay() for pk in range(n_keys)})
        vids = [v]
        for _ in range(n_versions - 1):
            ks = rng.choice(n_keys, size=max(2, n_keys // 64), replace=False)
            v = w.commit([v], adds={int(k): pay() for k in ks})
            vids.append(v)
    return vids


def _sim(batch) -> float:
    return KVSStats(n_queries=batch.kvs_queries,
                    bytes_fetched=batch.bytes_fetched).simulated_seconds(
                        PER_QUERY_S, BANDWIDTH_BPS)


def run(smoke: bool = False):
    n_keys = 3000 if smoke else 8000
    n_versions = 4 if smoke else 12
    rec_size = 256
    capacity = 16 << 10
    card0, card1 = 128, 4096

    rs = _make_store(capacity)
    vids = _ingest(rs, np.random.default_rng(11), n_keys, n_versions,
                   rec_size, card0, card1)
    snap = rs.snapshot()
    ext = datagen_extractor(2)
    v = vids[-1]
    full = snap.execute([Q.version(v)])[0].value

    # two predicates that are each selective at chunk granularity
    some = ext(next(iter(full.values())))
    t0, (lo, hi) = some[A0], (some[A1], some[A1] + 15)
    composite = Q.and_(Q.where(v, A0, t0), Q.where_range(v, A1, lo, hi))

    # ---- gate 1: ONE launch + ONE multiget, fewer chunks, byte-identical --
    launches0 = ops.BITMAP_LAUNCHES
    got = snap.execute([composite])
    launches = ops.BITMAP_LAUNCHES - launches0
    assert launches == 1, f"composite AND took {launches} kernel launches"
    # sharded stats count per-shard round trips: ONE multiget <= N_SHARDS
    assert got.batch.kvs_queries <= N_SHARDS, got.batch.kvs_queries

    a = snap.execute([Q.where(v, A0, t0)])
    b = snap.execute([Q.where_range(v, A1, lo, hi)])
    want = {pk: p for pk, p in a[0].value.items()
            if pk in b[0].value and b[0].value[pk] == p}
    assert got[0].value == want, "composite diverged from 2-session intersect"
    oracle = {pk: p for pk, p in full.items()
              if ext(p)[A0] == t0 and lo <= ext(p)[A1] <= hi}
    assert got[0].value == oracle, "composite diverged from brute-force scan"

    and_chunks = got[0].stats.chunks_fetched
    a_chunks = a[0].stats.chunks_fetched
    b_chunks = b[0].stats.chunks_fetched
    assert and_chunks < min(a_chunks, b_chunks), (
        f"AND fetched {and_chunks} chunks, predicates alone fetched "
        f"{a_chunks}/{b_chunks}")
    and_sim = _sim(got.batch)
    two_sim = _sim(a.batch) + _sim(b.batch)

    # ---- gate 2: index-only count/distinct = 0 payload round trips --------
    agg = snap.execute([Q.count(Q.where(v, A0, t0)),
                        Q.distinct(v, A0),
                        Q.exists(Q.where_range(v, A1, lo, hi))])
    assert agg[0].value == sum(1 for p in full.values() if ext(p)[A0] == t0)
    assert agg[1].value == sorted({ext(p)[A0] for p in full.values()})
    assert agg[2].value is True
    for r in agg:
        assert r.stats.payload_round_trips == 0, r.stats
        assert r.stats.payload_chunks_fetched == 0, r.stats
    assert agg.batch.payload_round_trips == 0, agg.batch

    # predicted vs measured chunk fetches (explain's costmodel view)
    ex = snap.explain([composite])[0]
    predicted, measured = ex["predicted_chunks"], and_chunks

    out = {
        "n_keys": n_keys, "n_versions": n_versions, "n_shards": N_SHARDS,
        "composite": {
            "kernel_launches": launches,
            "round_trips": got.batch.kvs_queries,
            "chunks": {"and": and_chunks, "where": a_chunks,
                       "where_range": b_chunks},
            "records": len(got[0].value),
            "simulated_s": {"and": and_sim, "two_sessions": two_sim},
        },
        "index_only": {
            "count": agg[0].value,
            "n_distinct": len(agg[1].value),
            "payload_round_trips": agg.batch.payload_round_trips,
            "map_round_trips": agg.batch.kvs_queries,
        },
        "explain": {"predicted_chunks": predicted,
                    "measured_chunks": measured,
                    "mode": ex["mode"]},
    }
    emit("planner/composite_and", 0.0,
         f"1 launch 1 multiget chunks {and_chunks}<min({a_chunks},{b_chunks}) "
         f"sim {two_sim*1e3:.2f}->{and_sim*1e3:.2f}ms")
    emit("planner/index_only", 0.0,
         f"count+distinct+exists payload_rts=0 "
         f"(map rts={agg.batch.kvs_queries})")
    emit("planner/explain", 0.0,
         f"predicted {predicted} vs measured {measured} chunks")
    save_json("bench_planner", out)
    return out


if __name__ == "__main__":
    main(run)
