"""Secondary indexes: filtered scans without full-version fetches.

The workload the subsystem exists for: "all records of version v where
field X = y" on a store whose payloads carry a structured attribute prefix
(the ``DatasetSpec.attr_fields`` layout, read by
``repro.core.secondary.datagen_extractor``).  Without a secondary index the
only plan is fetch-the-whole-version-and-filter; with one, the plan is
secondary-bitmap ∧ version-bitmap through the session kernel launch plus an
exact post-filter on the (few) fetched chunks.

Asserts the acceptance criteria, which are also the CI smoke gates:

1. SELECTIVITY — across a sweep of predicates, the filtered scan fetches
   ≤ 25% of the chunks the full-version baseline fetches for the same
   predicate, and its §2.3 simulated seconds are ≥ 4x lower;
2. EXACTNESS — every filtered result is byte-identical to the brute-force
   filter of the full fetch (lossy postings never leak);
3. WARM CACHE — with a ``CachingKVS`` on top, a repeated filtered scan runs
   with 0 backend read round trips.
"""
from __future__ import annotations

import numpy as np

from repro.core import (CachingKVS, InMemoryKVS, KVSStats, Q, RStore,
                        RStoreConfig, ShardedKVS)
from repro.core.costmodel import BANDWIDTH_BPS, PER_QUERY_S
from repro.core.secondary import datagen_extractor

from .common import emit, main, save_json

N_SHARDS = 2
ATTR = "f0"                       # first uint32 of the datagen attr layout


def _make_store(capacity: int, cache_bytes: int = 0):
    kvs = ShardedKVS([InMemoryKVS() for _ in range(N_SHARDS)])
    if cache_bytes:
        kvs = CachingKVS(kvs, cache_bytes=cache_bytes)
    rs = RStore(RStoreConfig(algorithm="bottom_up", capacity=capacity,
                             batch_size=8), kvs=kvs)
    rs.create_index(ATTR, datagen_extractor(1))
    return rs


def _ingest(rs, rng, n_keys, n_versions, rec_size, cardinality):
    def pay():
        tag = int(rng.integers(0, cardinality))
        return tag.to_bytes(4, "little") + rng.integers(
            0, 256, rec_size - 4, dtype=np.uint8).tobytes()

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
    n_versions = 6 if smoke else 16
    rec_size = 512
    capacity = 32 << 10
    cardinality = 1024 if smoke else 2048
    n_predicates = 8

    rs = _make_store(capacity)
    vids = _ingest(rs, np.random.default_rng(7), n_keys, n_versions,
                   rec_size, cardinality)
    snap = rs.snapshot()
    ext = datagen_extractor(1)

    # predicates: attribute values that actually occur in the newest version
    v = vids[-1]
    full = snap.execute([Q.version(v)])[0]
    seen = list({ext(p)[ATTR] for p in full.value.values()})
    tags = seen[:n_predicates]

    # ---- gates 1+2: per-predicate filtered session vs full-fetch session --
    flt_chunks = full_chunks = 0
    flt_sim = full_sim = 0.0
    for tag in tags:
        base = snap.execute([Q.version(v)])           # fetch-all baseline
        want = {pk: p for pk, p in base[0].value.items()
                if ext(p)[ATTR] == tag}
        got = snap.execute([Q.where(v, ATTR, tag)])   # indexed plan
        assert got[0].value == want, f"filtered scan diverged for tag {tag}"
        flt_chunks += got[0].stats.chunks_fetched
        full_chunks += base[0].stats.chunks_fetched
        flt_sim += _sim(got.batch)
        full_sim += _sim(base.batch)

    chunk_frac = flt_chunks / max(1, full_chunks)
    speedup = full_sim / max(flt_sim, 1e-12)
    assert chunk_frac <= 0.25, f"filtered scan fetched {chunk_frac:.0%} of chunks"
    assert speedup >= 4.0, f"simulated speedup only {speedup:.2f}x"

    # where_range exactness on the same store (a band of attribute values)
    lo, hi = min(tags), min(tags) + cardinality // 8
    want = {pk: p for pk, p in full.value.items()
            if lo <= ext(p)[ATTR] <= hi}
    got = snap.execute([Q.where_range(v, ATTR, lo, hi)])[0]
    assert got.value == want, "where_range diverged from brute-force filter"

    # ---- gate 3: warm cached filtered scans = 0 read round trips ----------
    rs_c = _make_store(capacity, cache_bytes=64 << 20)
    vids_c = _ingest(rs_c, np.random.default_rng(7), n_keys, n_versions,
                     rec_size, cardinality)
    assert vids_c == vids
    snap_c = rs_c.snapshot()
    queries = [Q.where(v, ATTR, tag) for tag in tags]
    cold = snap_c.execute(queries)
    assert cold.batch.kvs_queries >= 1
    warm = snap_c.execute(queries)
    assert warm.batch.kvs_queries == 0, warm.batch.kvs_queries
    for a, b in zip(warm, cold):
        assert a.value == b.value, "warm cached filtered scan diverged"

    st = rs.storage_stats()
    out = {
        "n_keys": n_keys, "n_versions": n_versions, "n_shards": N_SHARDS,
        "cardinality": cardinality, "n_predicates": len(tags),
        "chunks": {"filtered": flt_chunks, "full": full_chunks,
                   "fraction": chunk_frac},
        "simulated_s": {"filtered": flt_sim, "full": full_sim,
                        "speedup": speedup},
        "warm_cached_round_trips": warm.batch.kvs_queries,
        "secondary_index_bytes": st["secondary_index_bytes"],
        "index_report": st["secondary_indexes"][ATTR],
        "stored_chunk_bytes": st["stored_chunk_bytes"],
    }
    emit("secondary/filtered_scan", 0.0,
         f"chunks {flt_chunks}/{full_chunks} ({chunk_frac:.1%}<=25%) "
         f"sim {full_sim*1e3:.2f}->{flt_sim*1e3:.2f}ms ({speedup:.1f}x>=4x)")
    emit("secondary/warm_cached", 0.0,
         f"{len(tags)} filtered scans warm rts=0")
    emit("secondary/index_cost", 0.0,
         f"{st['secondary_index_bytes']}B postings vs "
         f"{st['stored_chunk_bytes']}B chunks")
    save_json("bench_secondary", out)
    return out


if __name__ == "__main__":
    main(run)
