"""Fig. 13: online partitioning quality vs batch size.

Quality metric (the paper's): total-version-span(online @ batch B) /
total-version-span(offline BOTTOM-UP on the same versions).  Claims: ratio
≥ 1, shrinking toward 1 as the batch grows; even small batches stay within a
reasonable penalty.
"""
from __future__ import annotations

import numpy as np

from repro.core import DatasetSpec, RStore, RStoreConfig, generate
from repro.core.partition import BottomUpPartitioner, total_version_span

from .common import emit, main, save_json

CAPACITY = 16 * 1024


def _replay_into(rs: RStore, g) -> None:
    """Re-ingest a generated graph through the RStore commit API."""
    keys = g.store.keys()
    store = g.store
    for v in g.versions:
        d = g.tree_delta[v]
        adds = {int(keys[r]): store.payload(int(r)) for r in d.adds}
        dels = []
        if v != g.root:
            # deletions = keys removed (not superseded by adds)
            del_keys = {int(keys[r]) for r in d.dels}
            dels = sorted(del_keys - set(adds))
            if v == g.root:
                dels = []
        if v == g.root:
            rs.init_root(adds)
        else:
            parent = g.tree_parent(v)
            rs.commit([parent], adds=adds, dels=dels)


def run():
    spec = DatasetSpec(n_versions=200, n_base_records=400, pct_update=0.1,
                       record_size=256, payloads=True, branch_prob=0.0,
                       seed=17)
    out = {}
    g_ref = generate(spec)
    offline = BottomUpPartitioner().partition(g_ref, CAPACITY)
    off_span = total_version_span(g_ref, offline)

    for batch in (10, 25, 50, 100, 200):
        rs = RStore(RStoreConfig(algorithm="bottom_up", capacity=CAPACITY,
                                 batch_size=batch))
        _replay_into(rs, generate(spec))
        rs.flush()
        spans = sum(int(np.unique(rs.r2c[rs.graph.members(v)]).size)
                    for v in rs.graph.versions)
        ratio = spans / off_span
        out[batch] = {"online_span": spans, "offline_span": off_span,
                      "ratio": ratio}
        emit(f"fig13/batch{batch}", 0.0, f"ratio={ratio:.3f}")
    save_json("bench_fig13_online", out)
    return out


if __name__ == "__main__":
    main(run)
