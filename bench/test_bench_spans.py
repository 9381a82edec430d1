"""The per-layer metrics read from the program's spans: traced tiny runs on
the CPU report them, they split ``rstore.serve`` exactly, and they read only
the window's waves."""
import dataclasses
import json
import math
import time
from types import SimpleNamespace

import pytest

import harness
import spans
from conftest import ROOT
from repro.core import trace

OFF_CHIP = harness.Hooks(on_chip=False)
PARTS = ("plan_ms_per_query", "gather_ms_per_query", "decode_ms_per_query",
         "answer_ms_per_query", "serve_self_ms_per_query")
SPAN_METRICS = PARTS + ("gather_new_length_ms_per_query",)


@pytest.fixture
def captured(monkeypatch):
    """The last run's ``RunRecord`` and ``Session``, kept for the test."""
    got = SimpleNamespace(record=None, session=None)

    @dataclasses.dataclass
    class Record(harness.RunRecord):
        def __post_init__(self):
            got.record = self

    class Session(harness.Session):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            got.session = self
    monkeypatch.setattr(harness, "RunRecord", Record)
    monkeypatch.setattr(harness, "Session", Session)
    return got


def run(root, workload, control=None):
    return harness.run_cell(root, workload, 2**33 + 7, 1.0, True,
                            time.perf_counter(), control, OFF_CHIP)


def values(result) -> dict:
    return {k: v["value"] for k, v in result["metrics"].items()}


def test_the_benchmark_lists_the_span_metrics():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    got = {m["name"]: m for m in bench["per_layer"]}
    for name in SPAN_METRICS:
        assert got[name]["source"] == "program_span"
        assert got[name]["unit"] == "ms"
        assert got[name]["moves"] == "query_p95_ms"
        assert "workloads" not in got[name]


@pytest.mark.parametrize("workload", ["b1-k1.read-mix", "b1-k1.ingest-read"])
def test_traced_runs_report_the_span_metrics(tiny_root, captured, workload):
    r = run(tiny_root, workload)
    assert r["correct"]
    m = values(r)
    assert set(SPAN_METRICS) <= set(m)
    assert all(math.isfinite(m[k]) and m[k] >= 0 for k in SPAN_METRICS)
    assert 0 < m["gather_new_length_ms_per_query"] <= m["gather_ms_per_query"]
    # the parts split the roots' total exactly; the roots lie inside the
    # benchmark's timer around serve
    rec = captured.record
    waves = spans.window_waves(rec)
    root_ms = sum(w[0].duration_ns for w in waves) * spans.MS / rec.n_queries
    assert sum(m[k] for k in PARTS) == pytest.approx(root_ms, rel=1e-9)
    assert root_ms <= m["serve_ms_per_query"]


def test_new_lengths_are_the_windows_gather_compiles(tiny_root, captured,
                                                     monkeypatch):
    compiled = []
    counter = harness.CompileCounter

    class Counter(counter):
        def between(self, lo, hi):
            out = super().between(lo, hi)
            compiled.append(out[2])
            return out
    monkeypatch.setattr(harness, "CompileCounter", Counter)
    run(tiny_root, "b1-k1.read-mix")
    waves = spans.window_waves(captured.record)
    new = sum(s.counts["new_length"] for w in waves for s in w
              if s.name == "rstore.gather")
    assert new == compiled[0]["jit(gather_rows)"] > 0


def test_the_read_back_after_a_writer_window_is_left_out(tiny_root,
                                                         captured):
    run(tiny_root, "b1-k1.ingest-read")
    rec, ses = captured.record, captured.session
    waves = spans.window_waves(rec)
    assert [w[0].counts["queries"] for w in waves] == \
        [size for _, _, size in rec.window.waves]
    # after the window's waves the log holds the check's read-back waves
    log = list(trace.WAVES)
    end = next(i for i, w in enumerate(log) if w is waves[-1]) + 1
    step, n = ses.cell.traffic["wave_max"], len(ses.writer.committed)
    assert [w[0].counts["queries"] for w in log[end:]] == \
        [min(step, n - i) for i in range(0, n, step)]


def test_no_matching_waves_read_nothing(tiny_root):
    for waves in ([], [(0.0, 1.0, 10**9)]):
        fake = SimpleNamespace(window=SimpleNamespace(waves=waves),
                               n_queries=1)
        assert spans.window_waves(fake) is None
        for name in SPAN_METRICS:
            assert harness.metric_reader(tiny_root / "bench", name)(fake) \
                is None


def test_a_control_in_the_programs_place_reports_no_span_metrics(tiny_root):
    trace.WAVES.clear()
    r = run(tiny_root, "b1-k1.read-mix", control="parent-version")
    assert not set(SPAN_METRICS) & set(r["metrics"])
    assert "serve_ms_per_query" in r["metrics"]
