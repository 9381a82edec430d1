"""Batched multi-query sessions vs sequential retrieval (§2.3 revisited).

The paper's core argument is that few large batched fetches beat many small
ones.  The plan/execute engine extends that from records-within-a-query to
queries-within-a-session: a server-side wave of 64 mixed queries (Q1 full
versions, point lookups, Q2 ranges, Q3 evolutions) is planned in one
vectorized projection pass, its candidate chunks deduped across queries, and
chunks + chunk maps fetched in ONE interleaved multiget.

Measured here against the same workload driven through the per-query
wrappers (1 round trip each) and the seed's two-phase cost (2 round trips
each: chunks, then maps), with latency under the Cassandra-like cost model
(per-request overhead dominates at this scale — exactly the §2.3 effect).
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import DatasetSpec, Q, RStore, RStoreConfig, generate
from repro.core.kvs import KVSStats

from .common import emit, main, save_json

SPEC = DatasetSpec(n_versions=120, n_base_records=600, pct_update=0.1,
                   record_size=512, payloads=True, p_d=0.05,
                   branch_prob=0.1, seed=17)
SMOKE_SPEC = DatasetSpec(n_versions=30, n_base_records=150, pct_update=0.1,
                         record_size=128, payloads=True, p_d=0.05,
                         branch_prob=0.1, seed=17)
CAPACITY = 32 * 1024
BATCH = 64


def _mixed_workload(rs, rng, n=BATCH):
    vids = rs.graph.versions
    keys = rs.graph.store.keys()
    qs = []
    for i in range(n):
        v = int(rng.choice(vids))
        kind = i % 4
        if kind == 0:
            qs.append(Q.version(v))
        elif kind == 1:
            qs.append(Q.record(v, int(rng.choice(keys))))
        elif kind == 2:
            lo = int(rng.integers(0, 500))
            qs.append(Q.range(v, lo, lo + 80))
        else:
            qs.append(Q.evolution(int(rng.choice(keys))))
    return qs


def _cost(stats: KVSStats) -> float:
    return stats.simulated_seconds()


def run(smoke: bool = False):
    rng = np.random.default_rng(7)
    g = generate(SMOKE_SPEC if smoke else SPEC)
    rs = RStore(RStoreConfig(algorithm="bottom_up", capacity=CAPACITY,
                             batch_size=10**9))
    rs.graph = g
    rs._grow_r2c()
    rs.build()
    qs = _mixed_workload(rs, rng, n=16 if smoke else BATCH)
    snap = rs.snapshot()

    # ---- batched session: one planned wave, one round trip ---------------
    before = rs.kvs.stats.snapshot()
    t0 = time.perf_counter()
    res = snap.execute(qs)
    wall_batched = time.perf_counter() - t0
    d_batched = rs.kvs.stats.snapshot()
    d_batched.n_queries -= before.n_queries
    d_batched.bytes_fetched -= before.bytes_fetched
    assert d_batched.n_queries == 1, \
        f"batched session must be 1 round trip, got {d_batched.n_queries}"

    # ---- sequential wrappers: one single-query session each --------------
    before = rs.kvs.stats.snapshot()
    t0 = time.perf_counter()
    seq_vals = [snap.execute([q])[0].value for q in qs]
    wall_seq = time.perf_counter() - t0
    d_seq = rs.kvs.stats.snapshot()
    d_seq.n_queries -= before.n_queries
    d_seq.bytes_fetched -= before.bytes_fetched

    for r, sv in zip(res, seq_vals):
        assert r.value == sv, "batched result diverged from sequential"

    # seed cost: two multigets per query (chunks, then maps), same bytes
    seed_stats = KVSStats(n_queries=2 * len(qs),
                          bytes_fetched=d_seq.bytes_fetched)

    out = {
        "n_queries": len(qs),
        "batched": {"round_trips": d_batched.n_queries,
                    "bytes": d_batched.bytes_fetched,
                    "chunks": res.batch.chunks_fetched,
                    "wall_s": wall_batched,
                    "simulated_s": _cost(d_batched)},
        "sequential": {"round_trips": d_seq.n_queries,
                       "bytes": d_seq.bytes_fetched,
                       "wall_s": wall_seq,
                       "simulated_s": _cost(d_seq)},
        "seed_two_phase": {"round_trips": seed_stats.n_queries,
                           "simulated_s": _cost(seed_stats)},
    }
    out["speedup_simulated"] = out["sequential"]["simulated_s"] / \
        out["batched"]["simulated_s"]
    emit("batched_query/batched", wall_batched * 1e6 / len(qs),
         f"round_trips=1 bytes={d_batched.bytes_fetched} "
         f"sim_ms={_cost(d_batched)*1e3:.2f}")
    emit("batched_query/sequential", wall_seq * 1e6 / len(qs),
         f"round_trips={d_seq.n_queries} sim_ms={_cost(d_seq)*1e3:.2f}")
    emit("batched_query/seed_two_phase", 0.0,
         f"round_trips={seed_stats.n_queries} "
         f"sim_ms={_cost(seed_stats)*1e3:.2f}")
    emit("batched_query/speedup", 0.0,
         f"simulated {out['speedup_simulated']:.1f}x fewer backend seconds")
    save_json("bench_batched_query", out)
    return out


if __name__ == "__main__":
    main(run)
