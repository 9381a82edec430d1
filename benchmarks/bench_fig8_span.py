"""Fig. 8: total version span, BOTTOM-UP vs SHINGLE vs DFS vs BFS vs DELTA,
across the Table-2 dataset families (scaled-down, structure-identical).

Claims validated (EXPERIMENTS.md §Fig8):
  - BOTTOM-UP/SHINGLE/DFS all beat DELTA on every dataset;
  - BOTTOM-UP outperforms DELTA by multiples (paper: up to 8.21×, avg 3.56×);
  - BREADTHFIRST ≥ DEPTHFIRST everywhere, equal on linear chains.
"""
from __future__ import annotations

import time

from repro.core import PAPER_DATASETS, generate
from repro.core.partition import (ALGORITHMS, DeltaBaseline,
                                  total_version_span)

from .common import emit, main, save_json

ALGOS = ["bottom_up", "shingle", "depth_first", "breadth_first"]
CAPACITY = 64 * 1024          # ~1 MB in the paper; scaled with record count


def run(datasets=None):
    out = {}
    ratios = []
    for name, spec in (datasets or PAPER_DATASETS).items():
        g = generate(spec)
        row = {}
        for algo in ALGOS:
            t0 = time.perf_counter()
            part = ALGORITHMS[algo]().partition(g, CAPACITY)
            dt = time.perf_counter() - t0
            span = total_version_span(g, part)
            row[algo] = {"span": span, "chunks": part.num_chunks,
                         "seconds": dt}
        db = DeltaBaseline()
        part = db.partition(g, CAPACITY)
        row["delta"] = {"span": db.total_version_span(g, part),
                        "chunks": part.num_chunks}
        out[name] = row
        ratio = row["delta"]["span"] / row["bottom_up"]["span"]
        ratios.append(ratio)
        emit(f"fig8/{name}/bottom_up", row["bottom_up"]["seconds"] * 1e6,
             f"span={row['bottom_up']['span']} delta_span={row['delta']['span']} "
             f"ratio={ratio:.2f}x")
    emit("fig8/avg_delta_over_bottomup", 0.0,
         f"{sum(ratios)/len(ratios):.2f}x (paper avg 3.56x, max 8.21x)")
    save_json("bench_fig8_span", out)
    return out


if __name__ == "__main__":
    main(run)
