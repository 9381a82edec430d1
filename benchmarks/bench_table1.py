"""Table 1: analytical cost model vs the instrumented system on a chain.

For a chain workload (n versions, m_v records, update fraction d) we compare
the closed-form storage / #queries / bytes predictions with measurements from
the built system for RStore-chunking, SINGLE-ADDRESS, SUBCHUNK and DELTA.
"""
from __future__ import annotations

import numpy as np

from repro.core import DatasetSpec, costmodel, generate
from repro.core.partition import (ALGORITHMS, DeltaBaseline,
                                  SingleAddressPartitioner,
                                  SubChunkPartitioner, total_version_span,
                                  version_spans)

from .common import emit, main, save_json

N, M, D, S = 60, 400, 0.10, 256
CAP = 8 * 1024


def run():
    spec = DatasetSpec(n_versions=N, n_base_records=M, pct_update=D,
                       frac_modify=1.0, frac_insert=0.0, frac_delete=0.0,
                       record_size=S, branch_prob=0.0, seed=23)
    g = generate(spec)
    w = costmodel.Workload(n=N, m_v=M, d=D, c=0.3, s=S, s_c=CAP)
    out = {}

    # --- storage: measured unique bytes vs single-address prediction -------
    measured_storage = int(g.store.sizes.sum())
    predicted = costmodel.single_address(w)["storage"]
    out["storage"] = {"measured": measured_storage, "predicted": predicted,
                      "rel_err": abs(measured_storage - predicted) / predicted}
    emit("table1/storage", 0.0,
         f"measured={measured_storage} predicted={predicted:.0f} "
         f"err={out['storage']['rel_err']:.2%}")

    # --- version query count: RStore chunking vs m_v·s/s_c -----------------
    part = ALGORITHMS["bottom_up"]().partition(g, CAP)
    spans = version_spans(g, part)
    avg_span = float(np.mean(list(spans.values())))
    pred_q = costmodel.rstore(w)["version_queries"]
    out["rstore_version_queries"] = {"measured": avg_span, "predicted_floor": pred_q}
    emit("table1/rstore_vq", 0.0,
         f"measured_span={avg_span:.1f} floor={pred_q:.1f} "
         f"span_factor={avg_span/pred_q:.2f}")

    # --- single-address: one query per record ------------------------------
    sa = SingleAddressPartitioner().partition(g, CAP)
    sa_span = float(np.mean(list(version_spans(g, sa).values())))
    out["single_address_vq"] = {"measured": sa_span,
                                "predicted": costmodel.single_address(w)["version_queries"]}
    emit("table1/single_address_vq", 0.0,
         f"measured={sa_span:.0f} predicted={M}")

    # --- delta: half-chain retrieval for a random version ------------------
    db = DeltaBaseline()
    dpart = db.partition(g, CAP)
    dspans = db.version_spans(g, dpart)
    avg_chain_chunks = float(np.mean(list(dspans.values())))
    pred_bytes = costmodel.delta(w)["version_bytes"]
    measured_bytes = avg_chain_chunks * CAP
    out["delta_version_bytes"] = {"measured": measured_bytes,
                                  "predicted": pred_bytes}
    emit("table1/delta_bytes", 0.0,
         f"measured≈{measured_bytes:.2e} predicted={pred_bytes:.2e} "
         f"(c≈{measured_bytes/ (w.m_v*w.s + w.d*(w.n-1)*w.m_v*w.s/2) :.2f})")

    # --- subchunk: key span = 1 ---------------------------------------------
    from repro.core.partition import key_spans
    sc = SubChunkPartitioner().partition(g, CAP)
    ks = key_spans(g, sc)
    out["subchunk_point"] = {"measured_key_span": float(np.mean(list(ks.values()))),
                             "predicted": 1.0}
    emit("table1/subchunk_kspan", 0.0,
         f"measured={out['subchunk_point']['measured_key_span']:.2f} predicted=1")

    save_json("bench_table1", out)
    return out


if __name__ == "__main__":
    main(run)
