"""The read path's spans (``repro.core.trace``): nesting, self time, the
bounded wave log, and the spans one ``StoreQueryEngine.serve`` records."""
import threading

import jax
import pytest

from repro.core import (Q, RStore, RStoreConfig, ShardedDeviceKVS, ShardedKVS,
                        kvs, struct_extractor, trace)
from repro.serve.engine import StoreQueryEngine

EXT = struct_extractor({"color": (0, 1), "size": (1, 1)})


def _mk(pk: int, color: int, size: int = 0) -> bytes:
    return bytes([color, size % 251]) + bytes([pk % 251]) * 24


@pytest.fixture()
def store():
    kvs = ShardedKVS([ShardedDeviceKVS(slot_bytes=1 << 10, n_slots=64)
                      for _ in range(4)])
    rs = RStore(RStoreConfig(capacity=1 << 9, batch_size=4), kvs=kvs)
    rs.create_index("color", EXT)
    rs.create_index("size", EXT)
    vids = []
    with rs.writer() as w:
        v = w.init_root({pk: _mk(pk, pk % 5, pk % 11) for pk in range(60)})
        vids.append(v)
        for i in range(6):
            v = w.commit([v], adds={pk: _mk(pk, (pk + i) % 5, (pk + i) % 11)
                                    for pk in range(i, 60, 7)})
            vids.append(v)
    return rs, vids


def _mixed_wave(vids):
    v = vids[-1]
    return [Q.version(vids[2]), Q.record(v, 7), Q.records(v, [1, 2, 30]),
            Q.range(v, 10, 19), Q.evolution(7), Q.where(v, "color", 2),
            Q.and_(Q.where(v, "color", 1), Q.where_range(v, "size", 2, 9)),
            Q.count(Q.where(v, "size", 3))]


# ------------------------------------------------------------- the recorder
def test_spans_nest_with_parent_and_wave_ids():
    n0 = len(trace.WAVES)
    with trace.wave("rstore.serve", queries=3) as root:
        with trace.span("a") as a:
            with trace.span("b", rows=2) as b:
                b.counts["bytes"] = 8
        with trace.span("c") as c:
            pass
    assert len(trace.WAVES) == min(n0 + 1, trace.WAVE_LOG_MAX)
    got = trace.WAVES[-1]
    assert [s.name for s in got] == ["rstore.serve", "a", "b", "c"]
    assert got == [root, a, b, c]
    assert root.parent is None and a.parent == root.id
    assert b.parent == a.id and c.parent == root.id
    assert {s.wave for s in got} == {root.id}
    assert root.counts == {"queries": 3}
    assert b.counts == {"rows": 2, "bytes": 8}
    assert root.start_ns <= a.start_ns <= b.start_ns <= b.end_ns \
        <= a.end_ns <= c.start_ns <= c.end_ns <= root.end_ns


def test_self_time_is_duration_less_the_childrens_union():
    S = trace.Span
    root = S("r", 1, None, 1, 0, 100)
    spans = [root,
             S("k", 2, 1, 1, 10, 30), S("k", 3, 1, 1, 20, 40),   # overlap
             S("k", 4, 1, 1, 50, 60),
             S("g", 5, 4, 1, 52, 58),                   # a grandchild
             S("k", 6, 1, 1, 90, 120)]                  # runs past the end
    assert trace.self_ns(root, spans) == 100 - (30 + 10 + 10)
    assert trace.self_ns(spans[3], spans) == 10 - 6
    assert trace.self_ns(spans[1], spans) == 20


def test_the_wave_log_is_bounded():
    for i in range(trace.WAVE_LOG_MAX + 5):
        with trace.wave("w", queries=i):
            pass
    assert len(trace.WAVES) == trace.WAVE_LOG_MAX
    assert trace.WAVES[-1][0].counts["queries"] == trace.WAVE_LOG_MAX + 4
    assert trace.WAVES[0][0].counts["queries"] == 5


def test_spans_outside_a_wave_leave_the_log_untouched():
    with trace.wave("w"):
        pass
    before = list(trace.WAVES)
    with trace.span("rstore.gather", rows=1) as s:
        with trace.span("inner"):
            pass
    assert s.end_ns >= s.start_ns
    assert list(trace.WAVES) == before


def test_other_threads_stay_out_of_an_open_wave():
    def elsewhere():
        with trace.span("other"):
            pass
    with trace.wave("w"):
        t = threading.Thread(target=elsewhere)
        t.start()
        t.join()
    assert [s.name for s in trace.WAVES[-1]] == ["w"]


def test_a_wave_that_raises_is_logged():
    with pytest.raises(KeyError):
        with trace.wave("w", queries=2):
            with trace.span("x"):
                raise KeyError("missing")
    assert [s.name for s in trace.WAVES[-1]] == ["w", "x"]
    assert all(s.end_ns >= s.start_ns for s in trace.WAVES[-1])


def test_spans_are_profiler_annotations(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.wave("rstore.serve"):
            with trace.span("rstore.plan"):
                pass
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = {e.name for p in ProfileData.from_file(str(path)).planes
             if p.name.startswith("/host:") for line in p.lines
             for e in line.events}
    assert {"rstore.serve", "rstore.plan"} <= names


# ------------------------------------------------------------- the read path
def test_one_serve_records_one_wave_split_by_layer(store):
    rs, vids = store
    engine = StoreQueryEngine(rs)
    wave = _mixed_wave(vids)
    tables = rs.kvs.shards
    q0 = [t.stats.n_queries for t in tables]
    n0 = len(trace.WAVES)
    got = engine.serve(wave)
    assert len(trace.WAVES) == min(n0 + 1, trace.WAVE_LOG_MAX)
    spans = trace.WAVES[-1]
    root = spans[0]
    assert root.name == "rstore.serve"
    assert root.counts == {"queries": len(wave)}
    assert {s.name for s in spans} == {"rstore.serve", "rstore.plan",
                                       "rstore.gather", "rstore.decode",
                                       "rstore.answer"}
    by = {}
    for s in spans:
        by.setdefault(s.name, []).append(s)
    touched = sum(t.stats.n_queries > q for t, q in zip(tables, q0))
    assert len(by["rstore.gather"]) == touched > 0
    assert all(set(s.counts) == {"new_length", "pad_bytes"}
               and s.counts["new_length"] in (0, 1)
               and s.counts["pad_bytes"] >= 0
               for s in by["rstore.gather"])
    (plan,) = by["rstore.plan"]
    (answer,) = by["rstore.answer"]
    # one decode of the fetched blobs under the root, and one per chunk
    # whose payloads the answer step read, nested in it
    top = [s for s in by["rstore.decode"] if s.parent == root.id]
    nested = [s for s in by["rstore.decode"] if s.parent == answer.id]
    assert len(top) == 1
    assert len(nested) == got.batch.payload_chunks_fetched > 0
    assert len(top) + len(nested) == len(by["rstore.decode"])
    assert {s.parent for s in spans[1:]} <= {root.id, answer.id}
    assert plan.counts == answer.counts == {}
    assert all(s.counts == {} for s in by["rstore.decode"])
    assert trace.self_ns(answer, spans) == answer.duration_ns - sum(
        s.duration_ns for s in nested)


def test_self_times_add_up_to_the_wave(store):
    rs, vids = store
    StoreQueryEngine(rs).serve(_mixed_wave(vids))
    spans = trace.WAVES[-1]
    assert sum(trace.self_ns(s, spans) for s in spans) == \
        spans[0].duration_ns


def test_a_repinning_wave_is_one_wave(store):
    # the freshness check, and the re-pin after a build, lie inside the root
    rs, vids = store
    engine = StoreQueryEngine(rs)
    want = engine.serve([Q.version(vids[-1])])[0].value
    rs.build()                       # repartitions: the pin is stale
    n0 = len(trace.WAVES)
    got = engine.serve([Q.version(vids[-1]), Q.record(vids[-1], 7)])
    assert got[0].value == want
    assert len(trace.WAVES) == min(n0 + 1, trace.WAVE_LOG_MAX)
    spans = trace.WAVES[-1]
    assert spans[0].counts == {"queries": 2}
    assert all(spans[0].start_ns <= s.start_ns <= s.end_ns
               <= spans[0].end_ns for s in spans[1:])
    assert sum(s.name == "rstore.plan" for s in spans) == 1


# ------------------------------------------------------------- the gather
class _Compiles:
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.names = []

    def __call__(self, event, duration, fun_name="?", **_):
        if event == self.EVENT:
            self.names.append(fun_name)


def test_tables_of_one_shape_share_each_buckets_gather():
    a = ShardedDeviceKVS(slot_bytes=64, n_slots=16)
    b = ShardedDeviceKVS(slot_bytes=64, n_slots=16)
    for t in (a, b):
        t.multiput([("x", b"1" * 100), ("y", b"2" * 10), ("z", b"3")])
    jax.clear_caches()                   # no bucket compiled yet
    compiles = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        with trace.wave("w"):
            a.multiget(["x", "y", "z"])       # uploads: the ladder compiles
            b.multiget(["x", "y", "z"])       # the same shape: shared
            a.multiget(["x"])
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
    assert compiles.names.count("jit(gather_rows)") == len(kvs.GATHER_LADDER)
    gathers = trace.WAVES[-1][1:]
    assert [s.counts["new_length"] for s in gathers] == [1, 0, 0]


def test_a_cleared_jit_cache_makes_the_next_gather_new():
    t = ShardedDeviceKVS(slot_bytes=64, n_slots=16)
    t.multiput([("x", b"1" * 100), ("y", b"2")])
    jax.clear_caches()                   # another test may have compiled it
    with trace.wave("w"):
        t.multiget(["x", "y"])
        t.multiget(["y", "x"])
        jax.clear_caches()               # drops the compiled programs
        t.multiget(["x", "y"])
    gathers = trace.WAVES[-1][1:]
    assert [s.counts["new_length"] for s in gathers] == [1, 0, 1]
