#!/usr/bin/env python3
"""The store's benchmark: one run of one cell on the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs from the root of a checkout.  The cell, its configuration, traffic mix
and metrics are named in ``BENCHMARK.json``.  The run generates its data
from ``--seed``, loads the store, warms up, measures for ``--seconds``,
and compares every answer with a plain reference.  Its last line of
standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` also
``breakdown``, and ``checks`` last: each number compared and its limit);
the numbers compared are also the last lines of standard error.  With
``--trace 1`` the window runs under the profiler and the metrics are the
cell's per-layer ones.  ``--control <kind>`` puts a control in the
program's place on the timed path, one that breaks a guarantee, to show
that the comparison fails it: ``parent-version`` is the reference reading
each version's parent, ``pinned`` (writer cells) the program's own pinned
snapshots, one drain behind.

It exits non-zero and prints no result unless JAX finds a TPU with as many
chips as the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(HERE), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("parent-version", "pinned"))
    args = ap.parse_args(argv)

    import harness
    result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                              bool(args.trace), T_START, args.control)
    if result is None:
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
