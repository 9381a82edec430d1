"""Shared helpers for the paper-figure benchmarks."""
from __future__ import annotations

import json
import pathlib
import time
from typing import Callable, Dict, List

RESULTS = pathlib.Path(__file__).resolve().parent / "results"
RESULTS.mkdir(exist_ok=True)

_rows: List[Dict] = []


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    """CSV row contract: name,us_per_call,derived."""
    _rows.append({"name": name, "us_per_call": us_per_call, "derived": derived})
    print(f"{name},{us_per_call:.3f},{derived}")


def timed(fn: Callable, *args, repeat: int = 1, **kw):
    t0 = time.perf_counter()
    out = None
    for _ in range(repeat):
        out = fn(*args, **kw)
    dt = (time.perf_counter() - t0) / repeat
    return out, dt


def main(run: Callable) -> None:
    """A benchmark module's entry point: place the compile cache, then run."""
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()


def save_json(name: str, payload) -> None:
    (RESULTS / f"{name}.json").write_text(json.dumps(payload, indent=2))


def rows():
    return list(_rows)
