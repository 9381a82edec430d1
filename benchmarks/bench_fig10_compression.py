"""Fig. 10: partitioning quality + compression ratio vs max sub-chunk size k,
at bounded per-record change P_d ∈ {10%, 5%, 1%}.

Claims: compression ratio grows with k and with smaller P_d; the total
version span balances Factor 1 (bigger sub-chunks → fewer relevant records
per fetched chunk → more chunks per version) against Factor 2 (compression →
fewer chunks overall); at small P_d Factor 2 wins.
"""
from __future__ import annotations

import numpy as np

from repro.core import DatasetSpec, generate
from repro.core.partition import BottomUpPartitioner
from repro.core.subchunk import (build_subchunks, build_transformed,
                                 compressed_subchunk_sizes)

from .common import emit, main, save_json

CAPACITY = 32 * 1024


def run():
    out = {}
    for p_d in (0.10, 0.05, 0.01):
        spec = DatasetSpec(n_versions=120, n_base_records=600, pct_update=0.2,
                           frac_modify=1.0, frac_insert=0.0, frac_delete=0.0,
                           record_size=1024, payloads=True, p_d=p_d,
                           branch_prob=0.1, seed=9)
        g = generate(spec)
        raw_total = int(g.store.sizes.sum())
        row = {}
        for k in (1, 2, 5, 10, 25, 50):
            groups = build_subchunks(g, k)
            sizes = compressed_subchunk_sizes(g, groups)
            tds = build_transformed(g, groups, sizes)
            part = BottomUpPartitioner().partition(tds.tgraph, CAPACITY)
            r2c = part.record_to_chunk[tds.rec_to_sub]
            span = int(sum(np.unique(r2c[m]).size
                           for m in g.memberships().values()))
            ratio = raw_total / float(sizes.sum())
            row[k] = {"span": span, "compression_ratio": ratio,
                      "chunks": part.num_chunks}
            emit(f"fig10/pd{int(p_d*100)}/k{k}", 0.0,
                 f"span={span} compression={ratio:.2f}x chunks={part.num_chunks}")
        out[f"pd_{p_d}"] = row
    save_json("bench_fig10_compression", out)
    return out


if __name__ == "__main__":
    main(run)
