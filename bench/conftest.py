"""Fixtures of the benchmark's CPU tests: a copy of the benchmark, with its
configurations shrunk, in a temporary checkout."""
import json
import pathlib
import shutil
import sys

import pytest

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (str(HERE), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY = {"n_base_records": 60, "n_versions": 30}
TINY_RATE_QPS = 30.0      # a one-second window still offers every kind

# The cells the tests drive, with the configurations and metrics only they
# use; each is added to a copy of the benchmark that lacks it.
CELLS = {
    "b1-k1.read-mix": ("b1-bottomup-k1", "read-mix.k1"),
    "b1-k3.read-mix": ("b1-shingle-k3", "read-mix.k3"),
    "b1-k1.ingest-read": ("b1-bottomup-k1", "ingest-read.k1"),
}
CELL_METRICS = {
    "end_to_end": [("durable_versions_per_s", "versions/s", "higher",
                    "b1-k1.ingest-read")],
    "per_layer": [("xor_delta_launches_per_query", "launches", "lower",
                   "b1-k3.read-mix"),
                  ("xor_delta_device_ms_per_query", "ms", "lower",
                   "b1-k3.read-mix"),
                  ("commit_ms", "ms", "lower", "b1-k1.ingest-read")],
}


def _with_test_cells(bench: dict) -> dict:
    have = {w["name"] for w in bench["workloads"]}
    configs = {c["name"] for c in bench["configs"]}
    for name, (config, traffic) in CELLS.items():
        if name in have:
            continue
        bench["workloads"].append({"name": name, "config": config,
                                   "traffic": traffic, "chips": 1,
                                   "why": "a test"})
        if config not in configs:
            configs.add(config)
            bench["configs"].append({
                "name": config, "source": "a test", "reduced": [],
                "file": f"bench/configs/{config}.json", "why": "a test"})
    for kind, metrics in CELL_METRICS.items():
        names = {m["name"]: m for m in bench[kind]}
        for name, unit, better, cell in metrics:
            m = names.get(name)
            if m is None:
                m = {"name": name, "unit": unit, "better": better,
                     "source": "host_clock", "workloads": []}
                bench[kind].append(m)
            if cell not in m.setdefault("workloads", [cell]):
                m["workloads"].append(cell)
    return bench


def copy_benchmark(dest: pathlib.Path, **sizes) -> pathlib.Path:
    """``BENCHMARK.json`` (with every cell of ``CELLS``) and ``bench/``
    (tests aside) under ``dest``, each configuration cut to ``sizes``
    (default ``TINY``) and each traffic mix offered at ``TINY_RATE_QPS`` at
    least."""
    bench = _with_test_cells(json.loads((ROOT / "BENCHMARK.json").read_text()))
    (dest / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    shutil.copytree(HERE, dest / "bench", ignore=shutil.ignore_patterns(
        "__pycache__", "test_*.py", "conftest.py"))
    for f in (dest / "bench" / "configs").glob("*.json"):
        cfg = json.loads(f.read_text())
        cfg.update(sizes or TINY)
        f.write_text(json.dumps(cfg, indent=1))
    for f in (dest / "bench" / "traffic").glob("*.json"):
        mix = json.loads(f.read_text())
        mix["rate_qps"] = max(mix["rate_qps"], TINY_RATE_QPS)
        f.write_text(json.dumps(mix, indent=1))
    return dest


@pytest.fixture
def tiny_root(tmp_path):
    return copy_benchmark(tmp_path)
