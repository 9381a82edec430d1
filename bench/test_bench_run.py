"""Whole runs of the benchmark on the CPU at a tiny size: the harness's look
for a chip is skipped, the rest of a run is driven, and the comparison is
shown to fail when the timed path is broken underneath."""
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

import harness
from conftest import CELLS, ROOT, copy_benchmark

OFF_CHIP = harness.Hooks(on_chip=False)


def run(root, workload, seed=2**33 + 1, seconds=1.0, trace=False,
        control=None, hooks=OFF_CHIP):
    return harness.run_cell(root, workload, seed, seconds, trace,
                            time.perf_counter(), control, hooks)


def cpu_env():
    return dict(os.environ, JAX_PLATFORMS="cpu",
                PYTHONPATH=os.environ.get("PYTHONPATH", ""))


def test_no_tpu_exits_nonzero_without_a_result():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "b1-k1.read-mix",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=cpu_env(), capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout
    assert "no TPU" in proc.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = cpu_env()
    env.pop("PYTHONPATH")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "b1-k1.read-mix",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_correct_at_a_tiny_size(tiny_root, workload):
    r = run(tiny_root, workload)
    assert r["correct"], r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    want = {m["name"] for m in bench["end_to_end"]
            if workload in m.get("workloads", [workload])}
    # the device reports no memory on the CPU, so that reader is silent
    assert set(r["metrics"]) == want - {"device_bytes_per_user_byte"}
    assert list(r)[-1] == "checks"
    assert r["device"]["platform"] == "cpu"


def test_traced_run_reports_per_layer_metrics(tiny_root):
    r = run(tiny_root, "b1-k1.ingest-read", trace=True)
    assert r["correct"]
    assert {"serve_ms_per_query", "fetched_bytes_per_query",
            "compiles_in_window", "commit_ms"} <= set(r["metrics"])
    assert r["device"]["window_s"] > 0
    names = {n for n, _ in r["breakdown"]["idle_gaps"]}
    assert {"bench.serve", "bench.commit"} <= names


def test_a_cell_is_added_by_data_alone(tmp_path):
    """A throwaway deployment and mix, added as files and entries only."""
    root = copy_benchmark(tmp_path)
    before = {p: p.read_bytes() for p in (root / "bench").rglob("*")
              if p.is_file()}
    cfg = json.loads((root / "bench/configs/b1-bottomup-k1.json").read_text())
    cfg.update(name="throwaway", n_base_records=40, n_versions=12,
               branch_prob=0.3, record_size=512)
    (root / "bench/configs/throwaway.json").write_text(json.dumps(cfg))
    (root / "bench/traffic/points.json").write_text(json.dumps({
        "rate_qps": 30.0, "wave_max": 16, "mix": {"record": 3, "records": 1},
        "record_miss_share": 0.5, "records_keys": 4, "range_keys": [4, 8],
        "and_f1_halfwidth": 8, "warmup_waves": [16, 1]}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "throwaway", "source": "a test",
                             "file": "bench/configs/throwaway.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "throwaway.points",
                               "config": "throwaway", "traffic": "points",
                               "chips": 1, "why": "a test"})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    r = run(root, "throwaway.points")
    assert r["correct"] and r["attempted"] == 30
    assert {"query_p50_ms", "query_p95_ms", "setup_s"} <= set(r["metrics"])
    assert all(p.read_bytes() == b for p, b in before.items())


def alter_one_answer(serve):
    def broken(wave):
        out = list(serve(wave))
        for i, a in enumerate(out):
            if isinstance(a, dict) and a:
                pk = next(iter(a))
                a = dict(a)
                a[pk] = bytes([a[pk][0] ^ 1]) + a[pk][1:]
                out[i] = a
                break
        return out
    return broken


def drop_half_the_wave(serve):
    return lambda wave: list(serve(wave))[:len(wave) // 2]


def commit_without_change(commit):
    """A write acknowledged with a version id whose contents are its
    parent's: the step returns the state unchanged."""
    return lambda parent, adds, dels: commit(parent, {}, [])


@pytest.mark.parametrize("workload,hooks,check", [
    ("b1-k1.read-mix", harness.Hooks(wrap_serve=alter_one_answer,
                                     on_chip=False), "wrong_answers"),
    ("b1-k3.read-mix", harness.Hooks(wrap_serve=alter_one_answer,
                                     on_chip=False), "wrong_answers"),
    ("b1-k1.read-mix", harness.Hooks(wrap_serve=drop_half_the_wave,
                                     on_chip=False), "missing_answers"),
    ("b1-k1.ingest-read", harness.Hooks(wrap_commit=commit_without_change,
                                        on_chip=False),
     "versions_not_read_back"),
])
def test_a_broken_timed_path_is_not_correct(tiny_root, workload, hooks,
                                            check):
    r = run(tiny_root, workload, hooks=hooks)
    assert not r["correct"]
    assert r["checks"][check]["value"] > r["checks"][check]["limit"]
    assert r["failed"] > 0


@pytest.mark.parametrize("workload,control", [
    ("b1-k1.read-mix", "parent-version"),
    ("b1-k3.read-mix", "parent-version"),
    ("b1-k1.ingest-read", "pinned"),
])
def test_control_fails_the_comparison(tiny_root, workload, control):
    r = run(tiny_root, workload, control=control)
    assert not r["correct"]
    assert r["failed"] > 0
    assert any(c["value"] > c["limit"] for c in r["checks"].values())
