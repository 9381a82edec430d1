#!/usr/bin/env python3
"""Smoke run of the store's load-and-serve path on a TPU.

Generates stores from ``--seed`` with ``repro.core.datagen`` (the paper's B1
shape: a mostly-deep version tree, 2% branching, 5% random updates per
version, 1 KiB records with two indexed attribute fields), loads them
through the public API onto device tables, serves waves of mixed queries
through ``StoreQueryEngine``, commits versions through the background
flusher and reads them back.  Every answer is checked against a plain
dict-of-versions reference replayed from the generated deltas.

    python chip_smoke.py              # one chip: a ~1 GB bottom-up store
                                      # and a tenth-size shingle k=3 store
    python chip_smoke.py --chips 4    # only the 4-shard x 2-replica store
                                      # placed across four chips

It exits non-zero, and prints no result line, unless JAX finds a TPU.  Its
last line of standard output is one JSON object, ``{"ok": true, "device":
{"platform": ..., "kind": ..., "count": ...}}``.  The times it prints are
smoke timings of one run, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import struct
import sys
import time
import traceback
from typing import Dict, List, Optional, Sequence, Tuple

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

# The smoke's sizes.  The phase functions take them as arguments, so tests
# can rehearse the phases on the CPU at a tiny size.
BASE_RECORDS = 20_000         # the B1 store; the shingle store gets a tenth
N_VERSIONS = 1001
RECORD_BYTES = 1024
ATTRS = ("f0", "f1")
N_WAVES = 4
WAVE_SIZE = 64
N_COMMITS = 4                 # versions committed under load
MIN_STORED_BYTES = 1 << 30    # chunk data both one-chip stores must hold


def log(msg: str) -> None:
    print(msg, flush=True)


# ----------------------------------------------------------------- the data
def b1_spec(seed: int, n_base_records: int, n_versions: int = N_VERSIONS):
    """The paper's B1 dataset shape at ``n_base_records`` base records."""
    from repro.core import DatasetSpec
    return DatasetSpec(n_versions=n_versions, n_base_records=n_base_records,
                       pct_update=0.05, update_dist="random",
                       branch_prob=0.02, record_size=RECORD_BYTES,
                       payloads=True, attr_fields=len(ATTRS), seed=seed)


Commit = Tuple[int, Optional[int], Dict[int, bytes], List[int]]


def generated_commits(graph) -> List[Commit]:
    """``(vid, parent, adds {pk: payload}, deleted pks)`` per generated
    version, in commit order (the root has no parent)."""
    keys = graph.store.keys()
    out: List[Commit] = []
    for v in graph.versions:
        delta = graph.tree_delta[v]
        adds = {int(keys[r]): graph.store.payload(int(r)) for r in delta.adds}
        dels = sorted({int(keys[r]) for r in delta.dels} - adds.keys())
        parents = graph.parents[v]
        out.append((v, parents[0] if parents else None, adds, dels))
    return out


class Reference:
    """Plain reference semantics: each version is a dict ``pk -> payload``
    obtained by replaying its adds and deletes on its parent's dict."""

    def __init__(self, commits: Sequence[Commit]) -> None:
        self._delta: Dict[int, Tuple[Optional[int], Dict[int, bytes],
                                     List[int]]] = {}
        self._history: Dict[int, List[Tuple[int, bytes]]] = {}
        self._states: Dict[int, Dict[int, bytes]] = {}
        for vid, parent, adds, dels in commits:
            self.commit(vid, parent, adds, dels)

    def commit(self, vid: int, parent: Optional[int], adds: Dict[int, bytes],
               dels: Sequence[int]) -> None:
        self._delta[vid] = (parent, adds, list(dels))
        for pk, payload in adds.items():
            self._history.setdefault(pk, []).append((vid, payload))

    @property
    def versions(self) -> List[int]:
        return list(self._delta)

    @property
    def max_pk(self) -> int:
        return max(self._history)

    def state(self, vid: int) -> Dict[int, bytes]:
        if vid in self._states:
            return self._states[vid]
        path = []
        v: Optional[int] = vid
        while v is not None and v not in self._states:
            path.append(v)
            v = self._delta[v][0]
        out = dict(self._states[v]) if v is not None else {}
        for u in reversed(path):
            _, adds, dels = self._delta[u]
            for pk in dels:
                del out[pk]
            out.update(adds)
        self._states[vid] = out
        return out

    @staticmethod
    def attr(payload: bytes, name: str) -> int:
        return struct.unpack_from("<I", payload, 4 * ATTRS.index(name))[0]

    def matches(self, q, pk: int, payload: bytes) -> bool:
        k = q.kind
        if k == "version":
            return True
        if k == "record":
            return pk == q.pk
        if k == "records":
            return pk in q.pks
        if k == "range":
            return q.key_lo <= pk <= q.key_hi
        if k == "where":
            return self.attr(payload, q.attr) == q.value
        if k == "where_range":
            return q.key_lo <= self.attr(payload, q.attr) <= q.key_hi
        if k == "and":
            return all(self.matches(c, pk, payload) for c in q.children)
        raise ValueError(f"no reference for query kind {k!r}")

    def expected(self, q):
        if q.kind == "evolution":
            return sorted(self._history.get(q.pk, []), key=lambda t: t[0])
        if q.kind == "count":
            return len(self.expected(q.children[0]))
        state = self.state(q.vid)
        if q.kind == "record":
            return state.get(q.pk)
        return {pk: p for pk, p in state.items() if self.matches(q, pk, p)}


# ---------------------------------------------------------------- the store
def load(spec, kvs, config) -> Tuple[object, Reference, Dict[str, float]]:
    """Generate ``spec``, stage every version in a write session, build,
    and index both attribute fields.  Returns the store, its reference and
    the phase times."""
    from repro.core import RStore, datagen_extractor, generate
    t = {}
    t0 = time.perf_counter()
    commits = generated_commits(generate(spec))
    ref = Reference(commits)
    t["generate_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    rs = RStore(config, kvs=kvs)
    with rs.writer(flush_on_close=False) as w:
        for vid, parent, adds, dels in commits:
            got = (w.init_root(adds) if parent is None
                   else w.commit([parent], adds, dels))
            if got != vid:
                raise AssertionError(f"store numbered version {vid} as {got}")
    t["stage_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    rs.build()
    t["build_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    for a in ATTRS:
        rs.create_index(a, datagen_extractor(len(ATTRS)))
    t["index_s"] = time.perf_counter() - t0
    return rs, ref, t


def device_tables(kvs) -> List[object]:
    """The device slot tables under a backend stack, in shard order."""
    inner = getattr(kvs, "shards", None) or getattr(kvs, "replicas", None)
    if inner is None:
        return [kvs]
    return [t for s in inner for t in device_tables(s)]


def check_placement(kvs, platform: str) -> None:
    """Every slot table of the backend is an array on ``platform``."""
    for t in device_tables(kvs):
        plats = {d.platform for d in t.synced_devices()}
        if plats != {platform}:
            raise AssertionError(f"device table placed on {plats}, "
                                 f"not {platform}")


# ---------------------------------------------------------------- the waves
def make_wave(rng, ref: Reference, vids: Sequence[int], size: int = 64):
    """``size`` mixed queries over ``vids``: versions, point and multi-point
    lookups, key ranges, evolutions, indexed filters, a composite filter
    and counts."""
    from repro.core import Q
    mix = ["version"] * 2 + ["record"] * 12 + ["records"] * 8 + \
        ["range"] * 8 + ["evolution"] * 6 + ["where"] * 12 + \
        ["and"] * 8 + ["count"] * 8
    kinds = [mix[i % len(mix)] for i in range(size)]
    out = []
    for kind in kinds:
        v = int(rng.choice(vids))
        state = ref.state(v)
        pks = sorted(state)
        pk = int(rng.choice(pks))
        probe = state[pk]
        if kind == "version":
            out.append(Q.version(v))
        elif kind == "record":
            # one in four looks up a key the version does not hold
            out.append(Q.record(v, pk if rng.random() < 0.75
                                else pks[-1] + 1 + int(rng.integers(100))))
        elif kind == "records":
            out.append(Q.records(v, rng.choice(pks, min(8, len(pks)),
                                               replace=False)))
        elif kind == "range":
            out.append(Q.range(v, pk, pk + int(rng.integers(16, 256))))
        elif kind == "evolution":
            out.append(Q.evolution(pk))
        elif kind == "where":
            out.append(Q.where(v, "f0", ref.attr(probe, "f0")))
        elif kind == "and":
            f1 = ref.attr(probe, "f1")
            out.append(Q.and_(Q.where(v, "f0", ref.attr(probe, "f0")),
                              Q.where_range(v, "f1", max(0, f1 - 64),
                                            f1 + 64)))
        else:
            inner = (Q.where(v, "f1", ref.attr(probe, "f1"))
                     if rng.random() < 0.5 else
                     Q.range(v, pk, pk + int(rng.integers(16, 256))))
            out.append(Q.count(inner))
    return out


def serve_checked(engine, ref: Reference, queries) -> float:
    """Serve one wave and compare every answer with the reference; returns
    the wave's wall time."""
    t0 = time.perf_counter()
    batch = engine.serve(queries)
    dt = time.perf_counter() - t0
    if len(batch) != len(queries):
        raise AssertionError(f"{len(batch)} answers for {len(queries)} queries")
    for i, (q, r) in enumerate(zip(queries, batch)):
        want = ref.expected(q)
        if r.value != want:
            raise AssertionError(
                f"query {i} {q.kind} vid={q.vid} pk={q.pk}: answer differs "
                f"from the reference ({_size(r.value)} vs {_size(want)})")
    return dt


def _size(value) -> str:
    return (f"{len(value)} items" if isinstance(value, (dict, list))
            else repr(value)[:40])


def serve_waves(rs, ref: Reference, rng, n_waves: int, wave_size: int,
                label: str) -> None:
    from repro.serve.engine import StoreQueryEngine
    engine = StoreQueryEngine(rs)
    vids = ref.versions
    for w in range(n_waves):
        wave = make_wave(rng, ref, rng.choice(vids, 8), wave_size)
        dt = serve_checked(engine, ref, wave)
        log(f"{label}: wave {w}: {len(wave)} queries match the reference "
            f"(smoke timing, not a metric: {dt:.3f} s)")


def writes_under_load(rs, ref: Reference, rng, n_commits: int,
                      wave_size: int, label: str) -> None:
    """Commit through the background flusher, barrier, read them back."""
    import numpy as np
    from repro.core import Q
    from repro.serve.engine import StoreQueryEngine

    def payload() -> bytes:
        raw = rng.integers(0, 256, RECORD_BYTES, dtype=np.uint8)
        raw[:4 * len(ATTRS)] = np.frombuffer(
            rng.integers(0, 256, len(ATTRS), dtype=np.uint32)
            .astype("<u4").tobytes(), dtype=np.uint8)
        return raw.tobytes()

    flusher = rs.attach_flusher()
    next_pk = ref.max_pk + 1
    new = []
    t0 = time.perf_counter()
    for _ in range(n_commits):
        parent = int(rng.choice(ref.versions))
        pks = sorted(ref.state(parent))
        # rewrite 50 records, delete 10, insert 5
        touched = rng.choice(pks, min(60, len(pks)), replace=False)
        n_mod = len(touched) * 5 // 6
        adds = {int(pk): payload() for pk in touched[:n_mod]}
        adds.update({next_pk + i: payload() for i in range(5)})
        next_pk += 5
        dels = [int(pk) for pk in touched[n_mod:]]
        with rs.writer() as w:
            vid = w.commit([parent], adds, dels)
        ref.commit(vid, parent, adds, dels)
        new.append((vid, sorted(adds), dels))
    rs.barrier()
    log(f"{label}: {n_commits} versions committed through the flusher and "
        f"made durable (smoke timing, not a metric: "
        f"{time.perf_counter() - t0:.3f} s)")

    wave = []
    for vid, added, dels in new:
        wave += [Q.version(vid), Q.records(vid, added[:16]),
                 Q.record(vid, dels[0]), Q.evolution(added[0]),
                 Q.where(vid, "f0", ref.attr(ref.state(vid)[added[-1]], "f0"))]
    wave += make_wave(rng, ref, [v for v, _, _ in new],
                      max(0, wave_size - len(wave)))
    dt = serve_checked(StoreQueryEngine(rs), ref, wave)
    log(f"{label}: read-back wave: {len(wave)} queries match the reference "
        f"(smoke timing, not a metric: {dt:.3f} s)")
    flusher.close()


def store_report(rs, label: str, times: Dict[str, float]) -> int:
    st = rs.storage_stats()
    tables = device_tables(rs.kvs)
    table_bytes = sum(t.high_water_slots * t.slot_bytes for t in tables)
    log(f"{label}: {st['n_chunks']} chunks, {st['stored_chunk_bytes']} "
        f"stored chunk bytes, {st['raw_unique_bytes']} raw record bytes, "
        f"{table_bytes} device-table bytes in {len(tables)} tables")
    devices = sorted({d for t in tables for d in t.synced_devices()},
                     key=lambda d: d.id)
    log(f"{label}: device bytes in use with the store loaded: " + ", ".join(
        f"chip {d.id} {(d.memory_stats() or {}).get('bytes_in_use')}"
        for d in devices))
    log(f"{label}: load (smoke timing, not a metric): " + ", ".join(
        f"{k} {v:.1f}" for k, v in times.items()))
    return st["stored_chunk_bytes"]


# ------------------------------------------------------------------- phases
def one_chip(platform: str, seed: int, base_records: int = BASE_RECORDS,
             n_versions: int = N_VERSIONS, n_waves: int = N_WAVES,
             wave_size: int = WAVE_SIZE, n_commits: int = N_COMMITS) -> int:
    """The ~1 GB bottom-up store (with writes under load) and the
    tenth-size shingle k=3 store, each on 4 device-table shards.  Returns
    the stored chunk bytes of both."""
    import numpy as np
    from repro.core import RStoreConfig
    from repro.launch.mesh import make_sharded_backend
    rng = np.random.default_rng(seed)
    stored = 0
    for label, n, cfg in (
            ("b1", base_records,
             RStoreConfig(algorithm="bottom_up", batch_size=1 << 30)),
            ("b1-shingle-k3", max(1, base_records // 10),
             RStoreConfig(algorithm="shingle", k=3, batch_size=1 << 30))):
        t0 = time.perf_counter()
        kvs = make_sharded_backend(n_shards=4)
        rs, ref, times = load(b1_spec(seed, n, n_versions), kvs, cfg)
        check_placement(kvs, platform)
        times["total_s"] = time.perf_counter() - t0
        stored += store_report(rs, label, times)
        serve_waves(rs, ref, rng, n_waves, wave_size, label)
        if cfg.k == 1:                # the flusher ingests k = 1 stores
            writes_under_load(rs, ref, rng, n_commits, wave_size, label)
    return stored


def four_chips(devices, seed: int, base_records: int = BASE_RECORDS,
               n_versions: int = N_VERSIONS, n_waves: int = N_WAVES,
               wave_size: int = WAVE_SIZE) -> int:
    """The bottom-up store on 4 shards x 2 replicas over a mesh of four
    chips: tables must span every chip, and the replicas of each shard
    must sit on different chips."""
    import numpy as np
    from jax.sharding import Mesh
    from repro.core import RStoreConfig
    from repro.launch.mesh import make_sharded_backend
    mesh = Mesh(np.asarray(devices[:4]), ("chips",))
    kvs = make_sharded_backend(n_shards=4, replication_factor=2, mesh=mesh)
    t0 = time.perf_counter()
    rs, ref, times = load(
        b1_spec(seed, base_records, n_versions), kvs,
        RStoreConfig(algorithm="bottom_up", batch_size=1 << 30))
    times["total_s"] = time.perf_counter() - t0
    check_placement(kvs, devices[0].platform)
    used = set()
    for i, group in enumerate(kvs.shards):
        chips = [t.synced_devices() for t in group.replicas]
        used.update(d for c in chips for d in c)
        if any(a & b for j, a in enumerate(chips) for b in chips[j + 1:]):
            raise AssertionError(f"shard {i} has replicas sharing a chip")
    if used != set(devices[:4]):
        raise AssertionError(f"tables span {len(used)} of 4 chips")
    log(f"placement: 8 tables span {len(used)} chips; every shard's 2 "
        f"replicas sit on different chips")
    stored = store_report(rs, "b1-4x2", times)
    serve_waves(rs, ref, np.random.default_rng(seed), n_waves, wave_size,
                "b1-4x2")
    return stored


def kernel_report() -> None:
    """Each kernel on the path: its lowered TPU program holds a Mosaic
    ``tpu_custom_call``, and the run launched it at least once."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    sds = jax.ShapeDtypeStruct
    args = {
        "bitmap_vm": (sds((256, 8192), jnp.uint32), sds((128, 4), jnp.int32)),
        "xor_delta": (sds((128, 16384), jnp.uint32),) * 2,
        "minhash": (sds((8192, 256), jnp.int32), sds((8,), jnp.uint32),
                    sds((8,), jnp.uint32)),
    }
    for name, args in args.items():
        fn = ops.KERNELS[name]
        custom = "tpu_custom_call" in fn.lower(*args).as_text()
        used = ops.KERNEL_LAUNCHES[name]
        log(f"kernel {name}: lowered program holds tpu_custom_call: "
            f"{custom}; launches in this run: {used}")
        if not custom or used == 0:
            raise AssertionError(f"kernel {name} did not run on the chip")


def peak_memory_report(devices) -> None:
    log("peak device bytes in use: " + ", ".join(
        f"chip {d.id} {(d.memory_stats() or {}).get('peak_bytes_in_use')}"
        for d in devices))


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        print("chip_smoke: JAX found no TPU; this smoke runs on the chip "
              "only", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            stored = four_chips(devices, args.seed)
        else:
            stored = one_chip(dev.platform, args.seed)
            if stored < MIN_STORED_BYTES:
                raise AssertionError(f"the stores hold {stored} bytes of "
                                     f"chunk data, under {MIN_STORED_BYTES}")
            kernel_report()
        peak_memory_report(devices[:args.chips])
    except Exception:  # noqa: BLE001 - any failed phase fails the smoke
        traceback.print_exc()
        return 1
    log(f"stored chunk bytes in all stores: {stored}; wall time (smoke "
        f"timing, not a metric): {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
