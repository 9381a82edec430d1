"""The benchmark's generator and reference, on the CPU at tiny sizes."""
import dataclasses

import numpy as np
import pytest

import bgen
from reference import Reference

B1 = dict(n_versions=40, n_base_records=80, pct_update=0.05,
          update_dist="random", zipf_a=1.2, branch_prob=0.02,
          frac_modify=0.9, frac_insert=0.05, frac_delete=0.05,
          record_size=1024, attr_fields=2, attr_cardinality=256)


def datagen_commits(ds, seed):
    """The commits ``repro.core.datagen.generate`` makes for ``ds``."""
    from repro.core import DatasetSpec, generate
    spec = DatasetSpec(**{k: ds[k] for k in (
        "n_versions", "n_base_records", "pct_update", "update_dist",
        "zipf_a", "branch_prob", "frac_modify", "frac_insert",
        "frac_delete", "record_size", "attr_fields", "attr_cardinality")},
        payloads=True, seed=seed)
    graph = generate(spec)
    keys = graph.store.keys()
    out = []
    for v in graph.versions:
        delta = graph.tree_delta[v]
        adds = {int(keys[r]): graph.store.payload(int(r)) for r in delta.adds}
        dels = sorted({int(keys[r]) for r in delta.dels} - adds.keys())
        parents = graph.parents[v]
        out.append((v, parents[0] if parents else None, adds, dels))
    return out, graph


@pytest.mark.parametrize("seed,changes", [
    (3, {}),
    (2**33 + 5, {"n_base_records": 200, "n_versions": 60}),
    (7, {"update_dist": "zipf", "branch_prob": 0.2}),
    (11, {"attr_cardinality": 100}),          # the one-draw-per-record path
    (13, {"record_size": 10, "attr_fields": 3}),
])
def test_generator_matches_datagen(seed, changes):
    ds = dict(B1, **changes)
    want, graph = datagen_commits(ds, seed)
    got, states = bgen.generate(ds, seed)
    assert got == want
    keys = graph.store.keys()
    for v in graph.versions:
        assert states[v] == {int(keys[r]): graph.store.payload(int(r))
                             for r in graph.members(v)}


def test_batched_payloads_equal_one_draw_per_record():
    fast = bgen.payload_maker(np.random.default_rng(5), 1024, 2, 256)
    slow_rng = np.random.default_rng(5)
    slow = bgen.payload_maker(slow_rng, 1024, 2, 255)   # forces the slow path
    a = fast(7)
    b = []
    for _ in range(7):
        raw = slow_rng.integers(0, 256, size=1024, dtype=np.uint8)
        vals = slow_rng.integers(0, 256, size=2, dtype=np.uint32)
        raw[:8] = np.frombuffer(vals.astype("<u4").tobytes(), np.uint8)
        b.append(raw.tobytes())
    assert a == b
    assert len(slow(3)) == 3


def test_reference_replays_states_and_answers():
    from repro.core import Q
    commits, states = bgen.generate(B1, 4)
    ref = Reference(["f0", "f1"])
    for vid, parent, adds, dels in commits:
        ref.commit(vid, parent, adds, dels)    # replayed, not handed over
    for v in (0, 5, 39):
        assert ref.state(v) == states[v]
    v = 20
    state = states[v]
    pk = sorted(state)[3]
    f0 = ref.attr(state[pk], "f0")
    assert ref.expected(Q.record(v, pk)) == state[pk]
    assert ref.expected(Q.record(v, 10**9)) is None
    assert ref.expected(Q.where(v, "f0", f0)) == {
        k: p for k, p in state.items() if ref.attr(p, "f0") == f0}
    assert ref.expected(Q.count(Q.range(v, 0, 9))) == sum(
        1 for k in state if k <= 9)
    evo = ref.expected(Q.evolution(pk))
    assert [vid for vid, _ in evo] == sorted(vid for vid, _ in evo)
    assert ref.parent(v) == commits[v][1]
    with pytest.raises(ValueError):
        ref.commit(v, 0, {}, [])


def test_generator_refuses_what_it_does_not_copy():
    with pytest.raises(ValueError):
        bgen.generate(dict(B1, merge_prob=0.1), 0)
