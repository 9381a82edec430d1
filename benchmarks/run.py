"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks.common.emit) and
writes JSON payloads under benchmarks/results/.  An aggregate
``BENCH_SUMMARY.json`` — per-bench headline metrics keyed by suite name,
plus wall time and pass/fail status, stamped with the git SHA, a UTC
timestamp and a schema version so runs across PRs are directly diffable —
lands at the repo root so a single file answers "what did the last bench
run say".  The dry-run/roofline sweep (launch/dryrun.py) is separate — it
needs the 512-device platform flag.
"""
from __future__ import annotations

import datetime
import json
import pathlib
import subprocess
import sys
import time

SUMMARY_PATH = pathlib.Path(__file__).resolve().parent.parent / "BENCH_SUMMARY.json"

# bump when the summary layout changes (suites moved under "suites",
# metadata stamp added)
SCHEMA_VERSION = 2


def _git_sha() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=SUMMARY_PATH.parent, capture_output=True, text=True,
            timeout=10, check=True).stdout.strip()
    except Exception:  # noqa: BLE001 — not a repo / no git: still stamp
        return "unknown"


def _jsonable(obj):
    """Best-effort conversion of bench payloads (numpy scalars etc.)."""
    if obj is None:
        return None
    if isinstance(obj, dict):
        return {str(k): _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    for t in (bool, int, float, str):
        if isinstance(obj, t):
            return t(obj)
    if hasattr(obj, "item"):          # numpy scalar
        return obj.item()
    return repr(obj)


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (bench_async_ingest, bench_batched_query, bench_cache,
                   bench_chunksize, bench_compaction, bench_fault_tolerance,
                   bench_fig8_span, bench_fig9_beta, bench_fig10_compression,
                   bench_fig11_query, bench_fig12_scaling, bench_fig13_online,
                   bench_planner, bench_secondary, bench_table1,
                   bench_write_path)

    suites = [
        ("table1_costmodel", bench_table1.run),
        ("sec2.3_chunksize", bench_chunksize.run),
        ("fig8_span", bench_fig8_span.run),
        ("fig9_beta", bench_fig9_beta.run),
        ("fig10_compression", bench_fig10_compression.run),
        ("fig11_query", bench_fig11_query.run),
        ("batched_query", bench_batched_query.run),
        ("write_path", bench_write_path.run),
        ("async_ingest", bench_async_ingest.run),
        ("compaction", bench_compaction.run),
        ("fault_tolerance", bench_fault_tolerance.run),
        ("chunk_cache", bench_cache.run),
        ("secondary_index", bench_secondary.run),
        ("query_planner", bench_planner.run),
        ("fig12_scaling", bench_fig12_scaling.run),
        ("fig13_online", bench_fig13_online.run),
    ]
    print("name,us_per_call,derived")
    failures = 0
    suite_results = {}
    for name, fn in suites:
        t0 = time.time()
        try:
            headline = fn()
            wall = time.time() - t0
            print(f"suite/{name},{wall*1e6:.0f},ok")
            suite_results[name] = {"status": "ok", "wall_s": round(wall, 3),
                                   "headline": _jsonable(headline)}
        except Exception as e:  # noqa: BLE001
            failures += 1
            wall = time.time() - t0
            print(f"suite/{name},0,FAILED:{type(e).__name__}:{e}")
            suite_results[name] = {"status": f"FAILED:{type(e).__name__}:{e}",
                                   "wall_s": round(wall, 3), "headline": None}
    summary = {
        "schema_version": SCHEMA_VERSION,
        "git_sha": _git_sha(),
        "generated_at_utc": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "n_suites": len(suites),
        "n_failures": failures,
        "suites": suite_results,
    }
    SUMMARY_PATH.write_text(json.dumps(summary, indent=2) + "\n")
    print(f"# wrote {SUMMARY_PATH}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
