"""Fig. 9: effect of the β subtree bound on BOTTOM-UP (dataset B0 analogue).

Claims: span grows as β shrinks; runtime first drops with smaller β (less
processing per node) then rises again for very small β (merge overhead).
"""
from __future__ import annotations

import time

from repro.core import PAPER_DATASETS, generate
from repro.core.partition import BottomUpPartitioner, total_version_span

from .common import emit, main, save_json

CAPACITY = 64 * 1024


def run():
    g = generate(PAPER_DATASETS["B0"])
    out = {}
    for beta in (2, 5, 10, 20, 50, 100, 1000):
        t0 = time.perf_counter()
        part = BottomUpPartitioner(beta=beta).partition(g, CAPACITY)
        dt = time.perf_counter() - t0
        span = total_version_span(g, part)
        out[beta] = {"span": span, "seconds": dt}
        emit(f"fig9/beta_{beta}", dt * 1e6, f"span={span}")
    save_json("bench_fig9_beta", out)
    return out


if __name__ == "__main__":
    main(run)
