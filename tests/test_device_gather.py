"""The device table's gather (``ShardedDeviceKVS.multiget``): index lengths
padded up a ladder of powers of two, run whole when a table shape is first
uploaded, blocks of ``GATHER_BLOCK_ROWS`` rows, and one jitted
``gather_rows`` shared by every table of one shape."""
import importlib.util
import pathlib
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ShardedDeviceKVS, kvs, trace

B = kvs.GATHER_BLOCK_ROWS
SLOT = 16                                   # bytes: four uint32 words


class _Compiles:
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.names = []

    def __call__(self, event, duration, fun_name="?", **_):
        if event == self.EVENT:
            self.names.append(fun_name)


def _compiles_of(fn, cold=True):
    """The gather programs compiled while ``fn()`` ran, from a cold cache
    unless ``cold`` is false."""
    if cold:
        jax.clear_caches()
    compiles = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    try:
        fn()
    finally:
        jax.monitoring.unregister_event_duration_listener(compiles)
    return compiles.names.count("jit(gather_rows)")


def _one_slot_table(n_keys, n_slots=16, seed=0):
    """A table of ``n_keys`` one-slot values of random bytes."""
    rng = np.random.default_rng(seed)
    t = ShardedDeviceKVS(slot_bytes=SLOT, n_slots=n_slots)
    t.multiput([(f"k{i}", rng.bytes(SLOT)) for i in range(n_keys)])
    return t


def test_every_length_returns_the_rows_of_an_exact_gather():
    n = 3 * B + 5
    t = _one_slot_table(n)
    keys = [f"k{i}" for i in np.random.default_rng(1).permutation(n)]
    idx = np.array([t._dir[k][0] for k in keys], dtype=np.int32)
    # row i of a take depends on idx[i] alone: the first m rows are the
    # take at length m
    want = np.asarray(jnp.take(t._sync(), jnp.asarray(idx), axis=0))
    for m in range(1, n + 1):
        assert b"".join(t.multiget(keys[:m])) == want[:m].tobytes(), m


def test_a_new_table_shape_runs_the_ladder_at_upload():
    t = _one_slot_table(3 * B + 5)
    keys = [f"k{i}" for i in range(3 * B + 5)]
    assert _compiles_of(t._sync) == len(kvs.GATHER_LADDER)

    def read_every_length():
        with trace.wave("w"):
            for m in range(1, len(keys) + 1):
                t.multiget(keys[:m])
    assert _compiles_of(read_every_length, cold=False) == 0
    # and no read adds a program to the jit's cache either
    assert {s.counts["new_length"] for s in trace.WAVES[-1][1:]} == {0}


def test_lengths_in_one_bucket_compile_once():
    # once the ladder is dropped, a read compiles the bucket it meets
    t = _one_slot_table(40)
    t._sync()
    keys = [f"k{i}" for i in range(40)]
    assert _compiles_of(lambda: [t.multiget(keys[:m])
                                 for m in range(17, 33)]) == 1
    assert _compiles_of(lambda: [t.multiget(keys[:m])
                                 for m in range(1, 9)]) == 1


def test_a_table_of_another_shape_compiles_its_own_programs():
    # two tables of one shape share its ladder: two ladders compile, not 3
    tables = [_one_slot_table(12, n_slots=n, seed=n) for n in (16, 16, 32)]
    keys = [f"k{i}" for i in range(12)]
    assert _compiles_of(lambda: [t.multiget(keys) for t in tables]) == \
        2 * len(kvs.GATHER_LADDER)


class _Spy:
    """Stands in for ``gather_rows``: records each block's index length,
    and whether an earlier block's output was still alive when it ran."""

    def __init__(self):
        self.lengths, self.outputs, self.overlapped = [], [], False

    def __call__(self, t, idx):
        self.overlapped |= any(r() is not None for r in self.outputs)
        out = kvs.gather_rows(t, idx)
        self.lengths.append(len(idx))
        self.outputs.append(weakref.ref(out))
        return out

    def _cache_size(self):
        return kvs.gather_rows._cache_size()


def test_a_long_gather_keeps_one_block_of_rows_on_the_device():
    t = _one_slot_table(2 * B + 88)
    t._sync()                            # the ladder runs before the spy
    t._gather = spy = _Spy()
    keys = [f"k{i}" for i in range(2 * B + 88)]
    got = t.multiget(keys)
    assert spy.lengths == [B, B, 128]
    assert not spy.overlapped
    assert got == [t._host[t._dir[k][0]].tobytes() for k in keys]


@pytest.mark.parametrize("rows,pad_rows", [
    (1, 7), (8, 0), (9, 7), (B, 0), (B + 44, 20), (2 * B + 1, 7)])
def test_fetched_bytes_count_the_keys_rows_and_pad_bytes_the_rest(rows,
                                                                  pad_rows):
    # values of 1, 2 and 3 slots in turn, cut to ``rows`` slot rows in all
    t = ShardedDeviceKVS(slot_bytes=SLOT, n_slots=16)
    sizes, left = [], rows
    while left:
        n = min(1 + len(sizes) % 3, left)
        sizes.append(n)
        left -= n
    t.multiput([(f"k{i}", b"v" * (n * SLOT - 1))
                for i, n in enumerate(sizes)])
    with trace.wave("w"):
        t.multiget([f"k{i}" for i in range(len(sizes))])
    (gather,) = trace.WAVES[-1][1:]
    assert t.stats.bytes_fetched == rows * SLOT
    assert gather.counts["pad_bytes"] == pad_rows * SLOT


def test_the_pad_bytes_reader_sums_the_windows_gathers(monkeypatch):
    from types import SimpleNamespace
    root = pathlib.Path(__file__).resolve().parent.parent / "bench"
    monkeypatch.syspath_prepend(str(root))
    spec = importlib.util.spec_from_file_location(
        "gather_pad_bytes_per_query",
        root / "metrics" / "gather_pad_bytes_per_query.py")
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    run = SimpleNamespace(window=SimpleNamespace(waves=[(0.0, 60.0, 2)]),
                          n_queries=4)
    t = _one_slot_table(12)
    with trace.wave("rstore.serve", queries=2):
        t.multiget(["k0", "k1", "k2"])            # 3 rows: 5 padded
        t.multiget([f"k{i}" for i in range(12)])  # 12 rows: 4 padded
    assert reader.read(run) == (5 + 4) * SLOT / 4
    # a program whose gathers count no padding gives nothing to read
    with trace.wave("rstore.serve", queries=2):
        with trace.span("rstore.gather", new_length=0):
            pass
    assert reader.read(run) is None
