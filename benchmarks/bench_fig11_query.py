"""Fig. 11: end-to-end query latency — Q1 (full version), Q2 (range),
Q3 (record evolution) — across algorithms and sub-chunk sizes, against a
random query workload, with the DELTA and SUBCHUNK baselines.

Claims: BOTTOM-UP best for Q1/Q2; Q2 tracks Q1 (partial span ∝ full span);
DELTA's Q2 ≥ its Q1 (it reconstructs then filters); larger sub-chunks help
Q3; SUBCHUNK is best for Q3 and worst for Q1.

Each workload wave runs through the plan/execute session API — the whole
batch of N_QUERIES is planned together and fetched in one KVS round trip
(see bench_batched_query.py for the round-trip comparison itself).
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import DatasetSpec, Q, RStore, RStoreConfig, generate

from .common import emit, main, save_json

SPEC = DatasetSpec(n_versions=100, n_base_records=500, pct_update=0.1,
                   record_size=512, payloads=True, p_d=0.05,
                   branch_prob=0.1, seed=13)
CAPACITY = 32 * 1024
N_QUERIES = 12


def _rstore_for(algo: str, k: int) -> RStore:
    g = generate(SPEC)
    rs = RStore(RStoreConfig(algorithm=algo, capacity=CAPACITY, k=k,
                             batch_size=10**9))
    rs.graph = g
    rs._grow_r2c()
    rs.build()
    return rs


def _workload(rs, rng):
    vids = rng.choice(rs.graph.versions, N_QUERIES)
    keys = rng.choice(rs.graph.store.keys(), N_QUERIES)
    return vids, keys


def run():
    out = {}
    rng = np.random.default_rng(5)
    for algo in ("bottom_up", "depth_first", "shingle"):
        for k in (1, 5, 25):
            rs = _rstore_for(algo, k)
            vids, keys = _workload(rs, rng)
            snap = rs.snapshot()          # session API: plan+execute batches
            t0 = time.perf_counter()
            res1 = snap.execute([Q.version(int(v)) for v in vids])
            q1 = (time.perf_counter() - t0) / N_QUERIES
            spans = [r.stats.chunks_fetched for r in res1]
            t0 = time.perf_counter()
            snap.execute([Q.range(int(v), 100, 200) for v in vids])
            q2 = (time.perf_counter() - t0) / N_QUERIES
            t0 = time.perf_counter()
            res3 = snap.execute([Q.evolution(int(kk)) for kk in keys])
            q3 = (time.perf_counter() - t0) / N_QUERIES
            kspans = [r.stats.chunks_fetched for r in res3]
            out[f"{algo}_k{k}"] = {
                "q1_s": q1, "q2_s": q2, "q3_s": q3,
                "q1_round_trips": res1.batch.kvs_queries,
                "avg_version_span": float(np.mean(spans)),
                "avg_key_span": float(np.mean(kspans)),
            }
            emit(f"fig11/{algo}/k{k}", q1 * 1e6,
                 f"q2_us={q2*1e6:.0f} q3_us={q3*1e6:.0f} "
                 f"vspan={np.mean(spans):.1f} kspan={np.mean(kspans):.1f}")

    # DELTA baseline: reconstruct along the path, then filter
    g = generate(SPEC)
    from repro.core.partition import DeltaBaseline
    db = DeltaBaseline()
    part = db.partition(g, CAPACITY)
    spans = db.version_spans(g, part)
    vids, keys = np.array(g.versions), g.store.keys()
    sel = rng.choice(vids, N_QUERIES)
    avg_delta_span = float(np.mean([spans[int(v)] for v in sel]))
    out["delta"] = {"avg_version_span": avg_delta_span,
                    "q2_note": "Q2 >= Q1 (reconstruct then filter)",
                    "q3_note": "impractical (reconstruct all versions)"}
    emit("fig11/delta", 0.0, f"vspan={avg_delta_span:.1f} (Q3 impractical)")

    # SUBCHUNK baseline: perfect Q3, catastrophic Q1
    from repro.core.partition import SubChunkPartitioner, key_spans, version_spans
    part = SubChunkPartitioner().partition(g, CAPACITY)
    vs = version_spans(g, part)
    ks = key_spans(g, part)
    out["subchunk"] = {
        "avg_version_span": float(np.mean([vs[int(v)] for v in sel])),
        "avg_key_span": float(np.mean(list(ks.values()))),
    }
    emit("fig11/subchunk", 0.0,
         f"vspan={out['subchunk']['avg_version_span']:.1f} "
         f"kspan={out['subchunk']['avg_key_span']:.1f}")
    save_json("bench_fig11_query", out)
    return out


if __name__ == "__main__":
    main(run)
