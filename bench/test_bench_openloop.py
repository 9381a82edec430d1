"""The open-loop schedule, the wave former, the writer and the percentile
arithmetic, on the CPU with a simulated clock."""
from collections import Counter

import numpy as np
import pytest

import bgen
import openloop
from reference import Reference
from test_bench_gen import B1

MIX = {"version": 2, "record": 12, "records": 8, "range": 8, "evolution": 6,
       "where": 12, "and": 8, "count": 8}
TRAFFIC = {"rate_qps": 20.0, "wave_max": 64, "mix": MIX,
           "record_miss_share": 0.25, "records_keys": 8,
           "range_keys": [16, 255], "and_f1_halfwidth": 64,
           "warmup_waves": [64, 2]}


def test_schedule_offers_the_same_work_on_every_seed():
    a = openloop.make_schedule(TRAFFIC, 10.0, 1)
    b = openloop.make_schedule(TRAFFIC, 10.0, 2**40 + 1)
    again = openloop.make_schedule(TRAFFIC, 10.0, 1)
    assert len(a.arrivals) == len(b.arrivals) == 200
    assert Counter(a.kinds) == Counter(b.kinds) == Counter(
        openloop.kind_list(MIX, 200))
    assert a.kinds != b.kinds
    assert np.all(np.diff(a.arrivals) >= 0)
    assert 0 <= a.arrivals[0] and a.arrivals[-1] < 10.0
    assert np.array_equal(a.arrivals, again.arrivals)
    assert a.kinds == again.kinds


def test_kind_list_keeps_the_mix_proportions():
    kinds = openloop.kind_list(MIX, 640)
    assert Counter(kinds) == Counter({k: 10 * w for k, w in MIX.items()})
    # a short window still gets every kind in proportion
    assert Counter(openloop.kind_list(MIX, 32)) == Counter(
        {k: w // 2 for k, w in MIX.items()})
    few = Counter(openloop.kind_list(MIX, 22))
    assert sum(few.values()) == 22 and set(few) == set(MIX) - {"version"}
    with pytest.raises(ValueError):
        openloop.kind_list({"scan": 1}, 4)


def test_fresh_share_is_exact():
    t = dict(TRAFFIC, writer={"fresh_share": 0.5})
    s = openloop.make_schedule(t, 10.0, 3)
    assert s.fresh.sum() == 100


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


def test_wave_former_serves_every_due_query_in_waves():
    clock = FakeClock()
    sched = openloop.Schedule(np.array([0.0, 0.1, 0.15, 0.2, 5.0]),
                              ["record"] * 5, np.zeros(5, bool),
                              np.arange(5))
    waves = []

    def serve(wave):
        waves.append(list(wave))
        clock.t += 0.5                 # each wave takes half a second
        return [q * 10 for q in wave]

    log = openloop.run_window(serve, sched, lambda k: k, 2, 6.0,
                              clock=clock, sleep=clock.sleep)
    assert waves == [[0], [1, 2], [3], [4]]
    assert log.answers == [0, 10, 20, 30, 40]
    # due at 0.1 and 0.15, served in the wave that started at 0.5
    assert log.latency_s == pytest.approx([0.5, 0.9, 0.85, 1.3, 0.5])
    assert log.late_s == pytest.approx([0.0, 0.4, 0.8, 0.0])
    assert log.serve_s == pytest.approx(2.0)
    assert log.elapsed_s == pytest.approx(5.5)


def test_writer_commits_only_when_no_read_is_due():
    clock = FakeClock()
    sched = openloop.Schedule(np.array([1.0, 2.0]), ["record"] * 2,
                              np.zeros(2, bool), np.arange(2))
    steps = []

    class W:
        def step(self):
            steps.append(clock.t)
            clock.t += 0.4

    def serve(wave):
        clock.t += 0.3
        return list(wave)

    log = openloop.run_window(serve, sched, lambda k: k, 64, 3.0,
                              writer=W(), durable=lambda: len(steps),
                              clock=clock, sleep=clock.sleep)
    assert log.latency_s == pytest.approx([0.5, 0.6])
    assert all(t < 3.0 for t in steps)
    assert log.close_s >= 3.0 and log.durable_at_close == len(steps)


def test_percentile_interpolates_between_ranks():
    assert openloop.percentile([1, 2, 3, 4], 50) == 2.5
    assert openloop.percentile(list(range(101)), 95) == 95.0
    assert openloop.percentile([0.0, 10.0], 95) == pytest.approx(9.5)
    with pytest.raises(ValueError):
        openloop.percentile([], 50)


@pytest.fixture(scope="module")
def ref():
    commits, states = bgen.generate(B1, 9)
    r = Reference(["f0", "f1"])
    for (vid, parent, adds, dels), st in zip(commits, states):
        r.commit(vid, parent, adds, dels, st)
    return r


def test_resolve_makes_every_kind_against_live_keys(ref):
    sched = openloop.make_schedule(TRAFFIC, 16.0, 5)
    qs = [openloop.resolve(k, s, ref.versions, ref, TRAFFIC)
          for k, s in zip(sched.kinds, sched.qseeds)]
    assert {q.kind for q in qs} == set(MIX)
    again = [openloop.resolve(k, s, ref.versions, ref, TRAFFIC)
             for k, s in zip(sched.kinds, sched.qseeds)]
    assert qs == again
    records = [q for q in qs if q.kind == "record"]
    misses = sum(ref.expected(q) is None for q in records)
    assert 0 < misses < len(records)
    for q in qs:
        if q.kind in ("where", "and", "records", "version"):
            assert ref.expected(q)


def test_writer_commits_b1_shaped_versions(ref):
    got = []

    def commit(parent, adds, dels):
        got.append((parent, adds, dels))
        return 1000 + len(got)

    p = {"pct_update": 0.05, "frac_modify": 0.9, "frac_insert": 0.05,
         "frac_delete": 0.05, "branch_prob": 0.0}
    w = openloop.Writer(commit, ref, p,
                        bgen.payload_maker(np.random.default_rng(1), 1024, 2,
                                           256),
                        seed=1, head=ref.versions[-1], next_key=10**6)
    head = w.head
    v1, v2 = w.step(), w.step()
    assert got[0][0] == head and got[1][0] == v1
    parent, adds, dels = got[0]
    n = len(ref.state(parent))
    sel = max(1, int(n * 0.05))
    assert len(adds) + len(dels) == sel
    assert ref.state(v1) == {**{k: p for k, p in ref.state(parent).items()
                               if k not in dels}, **adds}
    assert w.committed == [v1, v2] and len(w.commit_s) == 2
