"""One run of one benchmark cell, driven by data.

A cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix.  The configuration is the file its
``configs`` entry names; the traffic mix is ``<bench>/traffic/<name>.json``;
each metric is read by ``<bench>/metrics/<name>.py``, whose ``read(run)``
returns a number or ``None`` when it finds nothing to read.  ``<bench>`` is
the first directory under ``paths``.  Adding a cell, a mix or a metric is
adding files and entries.

A run: generate the deployment's data from the seed, load it through the
store's public API onto device tables, warm up the cell's shapes, open the
window (reads through ``StoreQueryEngine.serve``, writes through
``IngestGateway.commit``), then compare every answer, and every version
committed, with the plain reference.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import pathlib
import shutil
import sys
import tempfile
import time
from collections import Counter
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np

import bgen
import openloop
import tracefold
import warm
from reference import Reference

MISSING = object()           # an answer that never came


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ the cell
@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: pathlib.Path


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(root: pathlib.Path, workload: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = [w for w in bench["workloads"] if w["name"] == workload]
    if len(cells) != 1:
        raise SystemExit(f"no workload named {workload!r} in BENCHMARK.json")
    w = cells[0]
    (cfg,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    bench_dir = root / bench["paths"][0]
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=json.loads((root / cfg["file"]).read_text()),
        traffic=json.loads(
            (bench_dir / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        bench_dir=bench_dir)


def metric_reader(bench_dir: pathlib.Path, name: str) -> Callable:
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def device_peaks(bench_dir: pathlib.Path, kind: str) -> dict:
    table = json.loads((bench_dir / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise SystemExit(f"device kind {kind!r} has no entry in peaks.json")
    return table["devices"][kind]


# ------------------------------------------------------------ what readers see
@dataclass
class RunRecord:
    """Everything a metric reader may read of one run."""
    setup_s: float
    window: openloop.WindowLog
    n_queries: int
    raw_bytes: int                       # raw bytes of every record version
    device_peak_bytes: int
    fetched_bytes: int                   # padded rows gathered in the window
    compiles: int                        # programs compiled in the window
    launches: Counter                    # compiled kernel launches
    commit_s: List[float] = field(default_factory=list)
    writer: bool = False
    trace: Optional[dict] = None         # tracefold.reduce of the window
    events: Optional[list] = None        # the trace's events


# ------------------------------------------------------------------ the store
def device_tables(kvs) -> List[object]:
    inner = getattr(kvs, "shards", None) or getattr(kvs, "replicas", None)
    if inner is None:
        return [kvs]
    return [t for s in inner for t in device_tables(s)]


def load_store(config: dict, commits):
    """Stage every version in one write session, build, and index the
    attribute fields, through the store's public API."""
    from repro.core import RStore, RStoreConfig, datagen_extractor
    from repro.launch.mesh import make_sharded_backend
    st = config["store"]
    kvs = make_sharded_backend(n_shards=st["n_shards"],
                               slot_bytes=st["slot_bytes"],
                               n_slots=st["n_slots"])
    rs = RStore(RStoreConfig(algorithm=st["algorithm"], k=st["k"],
                             capacity=st["chunk_bytes"],
                             batch_size=st["batch_size"]), kvs=kvs)
    times = {}
    t0 = time.perf_counter()
    with rs.writer(flush_on_close=False) as w:
        for vid, parent, adds, dels in commits:
            got = (w.init_root(adds) if parent is None
                   else w.commit([parent], adds, dels))
            if got != vid:
                raise RuntimeError(f"store numbered version {vid} as {got}")
    times["stage_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    rs.build()
    times["build_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    n_fields = config["attr_fields"]
    for attr in config["indexes"]:
        rs.create_index(attr, datagen_extractor(n_fields))
    times["index_s"] = time.perf_counter() - t0
    return rs, times


# ----------------------------------------------------------------- the check
def _values(batch) -> list:
    return [r.value for r in batch]


def compare(ref: Reference, wl: openloop.WindowLog) -> Dict[str, int]:
    """Wrong and missing answers of a window, each against the reference
    as of the versions committed when its wave began."""
    wrong = missing = 0
    for q, a, upto in zip(wl.queries, wl.answers, wl.horizons):
        if a is MISSING:
            missing += 1
        elif a != ref.expected(q, upto):
            wrong += 1
    return {"wrong_answers": wrong, "missing_answers": missing}


def parent_version_control(ref: Reference) -> Callable[[list], list]:
    """A control: the reference in the program's place, answering each
    query from the version's parent instead of the version asked (a read
    one version stale)."""
    def older(q):
        if q.vid is not None:
            parent = ref.parent(q.vid)
            q = dataclasses.replace(q, vid=q.vid if parent is None
                                    else parent)
        if q.children:
            q = dataclasses.replace(q, children=tuple(older(c)
                                                      for c in q.children))
        return q
    return lambda wave: [ref.expected(older(q)) for q in wave]


# ------------------------------------------------------------------- the run
class CompileCounter:
    """Programs JAX compiled (or loaded from its persistent cache), with
    when each ended, how long it took and the program's name."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.events: List[tuple] = []

    def __call__(self, event: str, duration: float, fun_name: str = "?",
                 **_) -> None:
        if event == self.EVENT:
            self.events.append((time.perf_counter(), duration, fun_name))

    def between(self, lo: float, hi: float) -> tuple:
        """Count, seconds, and count by program, of those in [lo, hi]."""
        sel = [(d, f) for t, d, f in self.events if lo <= t <= hi]
        return (len(sel), float(sum(d for d, _ in sel)),
                Counter(f for _, f in sel))


class GcTimer:
    """The host's garbage-collection pauses: (start, end) of each."""

    def __init__(self) -> None:
        self.pauses: List[tuple] = []
        self._t0 = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append((self._t0, time.perf_counter()))


@dataclass
class Hooks:
    """Where a test breaks the timed path underneath a run."""
    wrap_serve: Optional[Callable] = None    # serve -> serve
    wrap_commit: Optional[Callable] = None   # commit -> commit
    on_chip: bool = True       # False: skip the look for a chip and the cache


def _no_span(name):
    return contextlib.nullcontext()


class Session:
    """The cell's store, loaded from the seed, with its reference and its
    clients: the read engine and, where the mix has one, the writer."""

    def __init__(self, cell: Cell, seed: int, hooks: Hooks,
                 annotate: Callable = _no_span,
                 control: Optional[str] = None) -> None:
        from repro.serve.engine import StoreQueryEngine
        from repro.serve.ingest_gateway import IngestGateway
        self.cell, self.seed, self.annotate = cell, seed, annotate
        cfg, traffic = cell.config, cell.traffic
        self.phases: Dict[str, float] = {}
        t0 = time.perf_counter()
        commits, states = bgen.generate(cfg, seed)
        self.ref = ref = Reference(
            [f"f{i}" for i in range(cfg["attr_fields"])])
        for (vid, parent, adds, dels), state in zip(commits, states):
            ref.commit(vid, parent, adds, dels, state)
        self.base_vids = ref.versions
        next_key = 1 + max(k for _, _, adds, _ in commits for k in adds)
        self.phases["generate_s"] = time.perf_counter() - t0
        self.rs, load_times = load_store(cfg, commits)
        self.phases.update(load_times)
        del commits, states
        self.tables = device_tables(self.rs.kvs)

        wcfg = traffic.get("writer")
        self.writer = self.gateway = None
        if wcfg is not None:
            self.gateway = gw = IngestGateway(self.rs,
                                              **(wcfg.get("flush") or {}))

            def commit(parent, adds, dels):
                return gw.commit(wcfg["client"], [parent], adds, dels)
            if hooks.wrap_commit:
                commit = hooks.wrap_commit(commit)
            payloads = bgen.payload_maker(
                np.random.default_rng([seed, 4]), cfg["record_size"],
                cfg["attr_fields"], cfg["attr_cardinality"])
            self.writer = openloop.Writer(commit, ref, wcfg, payloads, seed,
                                          head=self.base_vids[-1],
                                          next_key=next_key)

        engine = StoreQueryEngine(self.rs)

        def serve(wave):
            nonlocal engine
            if self.writer is not None:   # a fresh snapshot: read-your-writes
                engine = StoreQueryEngine(self.rs)
                with self.annotate("bench.snapshot"):
                    engine.snapshot()
            return _values(engine.serve(wave))
        if control == "parent-version":
            serve = parent_version_control(self.ref)
        elif control == "pinned" and self.writer is not None:
            def serve(wave):
                return _values(self.rs.snapshot(mode="pinned").execute(wave))
        elif control is not None:
            raise SystemExit(f"no control {control!r} for this cell")
        self._serve = hooks.wrap_serve(serve) if hooks.wrap_serve else serve

    def placement(self) -> set:
        return {d.platform for t in self.tables for d in t.synced_devices()}

    def serve(self, wave) -> list:
        """The wave's answers; an answer that never comes is ``MISSING``."""
        try:
            out = list(self._serve(wave))
        except Exception as e:  # noqa: BLE001 - a failed wave's answers never come
            log(f"bench: a wave of {len(wave)} failed: {e!r}")
            return [MISSING] * len(wave)
        return (out + [MISSING] * len(wave))[:len(wave)]

    def resolver(self, sched: openloop.Schedule) -> Callable[[int], object]:
        """Query ``k`` of ``sched``; with no writer, every query is
        resolved now, before any window opens."""
        writer, traffic = self.writer, self.cell.traffic

        def resolve(k):
            pool = (writer.committed if sched.fresh[k] and writer is not None
                    and writer.committed else self.base_vids)
            return openloop.resolve(sched.kinds[k], sched.qseeds[k], pool,
                                    self.ref, traffic)
        if writer is None:
            return [resolve(k) for k in range(len(sched.kinds))].__getitem__
        return resolve

    def compile_shapes(self) -> None:
        """Every bitmap-VM shape the cell's waves can use (``warm.py``)."""
        n = int(self.rs.storage_stats()["n_chunks"])
        grows = [n, n + 32 * warm.LANE] if self.writer is not None else [n]
        t0 = time.perf_counter()
        done = warm.warm_bitmap(grows, self.cell.traffic["wave_max"])
        self.phases["compile_shapes_s"] = time.perf_counter() - t0
        log(f"bitmap-VM shapes run before the window: {done}")

    def warm_up(self) -> None:
        """The cell's own wave sizes, and its writes, once."""
        t0 = time.perf_counter()
        traffic = self.cell.traffic
        for ws in openloop.warmup_schedule(traffic, self.seed):
            if self.writer is not None:
                for _ in range(traffic["writer"]["warmup_commits"]):
                    self.writer.step()
            resolve = self.resolver(ws)
            self.serve([resolve(k) for k in range(len(ws.kinds))])
        self.phases["warmup_s"] = time.perf_counter() - t0

    def window(self, sched: openloop.Schedule, resolve_at,
               seconds: float) -> openloop.WindowLog:
        writer = self.writer
        c0 = len(writer.committed) if writer else 0

        def durable() -> int:
            return (len(writer.committed) - c0
                    - self.gateway.flusher.staleness_lag)
        return openloop.run_window(
            self.serve, sched, resolve_at, self.cell.traffic["wave_max"],
            seconds, writer, durable=durable if writer else (lambda: 0),
            horizon=self.horizon, annotate=self.annotate)

    def horizon(self) -> Optional[int]:
        """The newest version committed so far (None: only the loaded
        ones)."""
        w = self.writer
        return w.committed[-1] if w is not None and w.committed else None

    def check(self, wl: openloop.WindowLog) -> Dict[str, int]:
        """Every answer against the reference; with a writer, every version
        it committed, read back whole once the writes are durable."""
        checks = compare(self.ref, wl)
        if self.writer is not None:
            self.gateway.barrier()
            lost, vids = 0, self.writer.committed
            step = self.cell.traffic["wave_max"]
            for i in range(0, len(vids), step):
                wave = [_q_version(v) for v in vids[i:i + step]]
                got = self.serve(wave)
                lost += sum(1 for q, a in zip(wave, got)
                            if a is MISSING or a != self.ref.state(q.vid))
            checks["versions_not_read_back"] = lost
        return checks

    def close(self) -> None:
        if self.gateway is not None:
            self.gateway.close()


def open_devices(cell: Cell, hooks: Hooks) -> Optional[list]:
    """The devices to run on, or None where there is no TPU with as many
    chips as the cell asks for; on the chip, places the compile cache."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if not hooks.on_chip:
        return devices
    if dev.platform != "tpu":
        log("bench: JAX found no TPU; the benchmark runs on the chip only")
        return None
    if len(devices) < cell.chips:
        log(f"bench: {cell.name} needs {cell.chips} chips, JAX found "
            f"{len(devices)}")
        return None
    log(f"device peaks: {device_peaks(cell.bench_dir, dev.device_kind)}")
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    return devices


def run_cell(root: pathlib.Path, workload: str, seed: int, seconds: float,
             trace: bool, t_start: float, control: Optional[str] = None,
             hooks: Hooks = Hooks()) -> Optional[dict]:
    """One run of ``workload``; returns the result line's object, or None
    when no accelerator of the kind the cell needs is there."""
    import jax
    cell = find_cell(root, workload)
    devices = open_devices(cell, hooks)
    if devices is None:
        return None
    compiles, gcs = CompileCounter(), GcTimer()
    jax.monitoring.register_event_duration_secs_listener(compiles)
    gc.callbacks.append(gcs)
    try:
        return _run(cell, seed, seconds, trace, t_start, control, hooks,
                    devices, compiles, gcs)
    finally:
        gc.callbacks.remove(gcs)
        jax.monitoring.unregister_event_duration_listener(compiles)


def _run(cell, seed, seconds, trace, t_start, control, hooks, devices,
         compiles, gcs) -> dict:
    import jax
    from repro.kernels import ops
    annotate = jax.profiler.TraceAnnotation if trace else _no_span
    ses = Session(cell, seed, hooks, annotate, control)
    if ses.placement() != {devices[0].platform}:
        raise RuntimeError(f"device tables placed on {ses.placement()}")
    if hooks.on_chip:
        ses.compile_shapes()
    ses.warm_up()

    sched = openloop.make_schedule(cell.traffic, seconds, seed)
    resolve_at = ses.resolver(sched)
    fetched0 = sum(t.stats.bytes_fetched for t in ses.tables)
    launches0 = Counter(ops.KERNEL_LAUNCHES)
    commits0 = len(ses.writer.commit_s) if ses.writer else 0
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        po = jax.profiler.ProfileOptions()
        po.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=po)

    # the reference and the set-up's objects stay out of the window's
    # garbage collections
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start
    w0 = time.perf_counter()
    with warm.cache_off(), annotate("bench.window"):
        wl = ses.window(sched, resolve_at, seconds)
    w1 = time.perf_counter()
    gc.unfreeze()
    events = summary = None
    if trace:
        jax.profiler.stop_trace()
        events = tracefold.events_from_dir(trace_dir)
        summary = tracefold.reduce(events)
        shutil.rmtree(trace_dir, ignore_errors=True)

    n_compiles, compile_s, by_program = compiles.between(w0, w1)
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices[:cell.chips])
    run = RunRecord(
        setup_s=setup_s, window=wl,
        n_queries=len(sched.kinds),
        raw_bytes=int(ses.rs.storage_stats()["raw_unique_bytes"]),
        device_peak_bytes=peak,
        fetched_bytes=sum(t.stats.bytes_fetched for t in ses.tables)
        - fetched0,
        compiles=n_compiles,
        launches=Counter(ops.KERNEL_LAUNCHES) - launches0,
        commit_s=ses.writer.commit_s[commits0:] if ses.writer else [],
        writer=ses.writer is not None, trace=summary, events=events)
    log("phases: " + ", ".join(f"{k} {v:.3f}" for k, v in ses.phases.items())
        + f", setup_s {setup_s:.3f}")
    log(window_line(wl, n_compiles, compile_s, run.commit_s))
    log(f"compiles in the window by program: {dict(by_program)}")
    log(slow_waves(wl, w0, compiles, gcs))
    log(f"device memory: peak {peak} bytes, raw record bytes "
        f"{run.raw_bytes}, launches {dict(run.launches)}, table slots in "
        f"use {[t.high_water_slots for t in ses.tables]}")

    # the check, once the window has closed and the peak has been read
    checks = ses.check(wl)
    attempted = len(sched.kinds) + (len(ses.writer.committed)
                                    if ses.writer else 0)
    limits = {k: 0 for k in checks}
    result = {
        "correct": all(checks[k] <= limits[k] for k in checks),
        "attempted": attempted,
        "failed": sum(checks.values()),
    }
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = metric_reader(cell.bench_dir, m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
    result.update(metrics=metrics, device=device)
    if trace:
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                        for k in checks}
    ses.close()
    return result


def window_line(wl, n_compiles, compile_s, commit_s) -> str:
    return (f"window: {len(wl.latency_s)} queries in {len(wl.waves)} waves, "
            f"elapsed {wl.elapsed_s:.3f} s, serve {wl.serve_s:.3f} s, "
            f"compiles {n_compiles} ({compile_s:.3f} s), wave start late "
            f"p50 {_p(wl.late_s, 50):.4f} s max {max(wl.late_s or [0]):.4f} s"
            + (f", commits {len(commit_s)}, durable at close "
               f"{wl.durable_at_close}" if commit_s else ""))


def slow_waves(wl, w0: float, compiles: CompileCounter, gcs: GcTimer,
               n: int = 3) -> str:
    """The ``n`` longest waves: when each began, its size, its serve time,
    and the compiles and garbage-collection pauses inside it."""
    gc_s = sum(e - s for s, e in gcs.pauses if w0 <= s)
    out = []
    for s, e, size in sorted(wl.waves, key=lambda w: w[0] - w[1])[:n]:
        c, cs, _ = compiles.between(w0 + s, w0 + e)
        g = sum(min(b, w0 + e) - max(a, w0 + s) for a, b in gcs.pauses
                if a < w0 + e and b > w0 + s)
        out.append(f"at {s:.2f} s: {size} queries, {e - s:.3f} s, "
                   f"{c} compiles {cs:.3f} s, gc {g:.3f} s")
    return (f"gc in the window: {gc_s:.3f} s; longest waves: "
            + "; ".join(out))


def _q_version(vid: int):
    from repro.core import Q
    return Q.version(vid)


def _p(values, q) -> float:
    return openloop.percentile(values, q) if len(values) else 0.0
