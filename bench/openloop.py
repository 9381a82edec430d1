"""Open-loop traffic: the query schedule, the wave former and the writer.

One general generator reads a traffic file (``bench/traffic/<name>.json``):

- ``rate_qps``: Poisson arrivals at this fixed rate.  A window of ``s``
  seconds holds exactly ``round(rate_qps * s)`` queries at uniform random
  times (a Poisson process given its count), so every seed offers the same
  amount of work, in another order.
- ``mix``: weights of the query kinds; each kind gets its share of the
  window's queries exactly, in an order shuffled from the seed.
- ``wave_max``: the wave former serves every query that is due, up to this
  many, whenever the engine is free.  Each query is timed from when it was
  due until its wave returns.
- ``writer`` (optional): a client that commits B1-shaped versions back to
  back whenever no read is due; reads then run on fresh snapshots and
  ``fresh_share`` of them ask for versions committed during the run.

Versions and keys are drawn uniformly.  A query's random draws come from a
seed of its own, so a query asked of versions committed in the run resolves
the same way whenever the wave that carries it is formed.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

KINDS = ("version", "record", "records", "range", "evolution", "where",
         "and", "count")


# ------------------------------------------------------------- the schedule
@dataclass
class Schedule:
    arrivals: np.ndarray          # seconds after the window opens, ascending
    kinds: List[str]
    fresh: np.ndarray             # bool: ask a version committed in the run
    qseeds: np.ndarray            # one seed per query for its own draws


def kind_list(mix: Dict[str, int], n: int) -> List[str]:
    """``n`` kinds in the proportions of ``mix`` (weights), in mix order:
    each kind gets the whole part of its share, and the largest remainders
    (the earlier kind on a tie) take the queries left over."""
    unknown = set(mix) - set(KINDS)
    if unknown:
        raise ValueError(f"unknown query kinds in the mix: {sorted(unknown)}")
    total = sum(mix.values())
    exact = {k: n * w / total for k, w in mix.items()}
    counts = {k: int(x) for k, x in exact.items()}
    order = sorted(mix, key=lambda k: counts[k] - exact[k])
    for k in order[:n - sum(counts.values())]:
        counts[k] += 1
    return [k for k in mix for _ in range(counts[k])]


def make_schedule(traffic: dict, seconds: float, seed: int,
                  stream: int = 1) -> Schedule:
    rng = np.random.default_rng([seed, stream])
    n = int(round(traffic["rate_qps"] * seconds))
    arrivals = np.sort(rng.uniform(0.0, seconds, n))
    kinds = kind_list(traffic["mix"], n)
    rng.shuffle(kinds)
    fresh = np.zeros(n, dtype=bool)
    share = (traffic.get("writer") or {}).get("fresh_share", 0.0)
    fresh[:int(round(share * n))] = True
    rng.shuffle(fresh)
    qseeds = rng.integers(0, 1 << 62, size=n)
    return Schedule(arrivals, kinds, fresh, qseeds)


def warmup_schedule(traffic: dict, seed: int) -> List[Schedule]:
    """The warm-up waves' queries: one schedule per wave size listed under
    ``warmup_waves``, from a stream of their own."""
    out = []
    for i, size in enumerate(traffic["warmup_waves"]):
        rng = np.random.default_rng([seed, 2, i])
        kinds = kind_list(traffic["mix"], size)
        rng.shuffle(kinds)
        out.append(Schedule(np.zeros(size), kinds, np.zeros(size, bool),
                            rng.integers(0, 1 << 62, size=size)))
    return out


def resolve(kind: str, qseed: int, vids: Sequence[int], ref, traffic: dict):
    """The concrete query: a version drawn from ``vids``, a key drawn from
    that version's live keys, and the kind's own draws."""
    from repro.core import Q
    rng = np.random.default_rng(int(qseed))
    v = int(vids[int(rng.integers(len(vids)))])
    keys = ref.sorted_keys(v)
    pk = int(keys[int(rng.integers(len(keys)))])
    probe = ref.state(v)[pk]
    lo, hi = traffic["range_keys"]
    if kind == "version":
        return Q.version(v)
    if kind == "record":
        if rng.random() < traffic["record_miss_share"]:
            pk = int(keys[-1]) + 1 + int(rng.integers(100))
        return Q.record(v, pk)
    if kind == "records":
        n = min(traffic["records_keys"], len(keys))
        return Q.records(v, rng.choice(keys, n, replace=False))
    if kind == "range":
        return Q.range(v, pk, pk + int(rng.integers(lo, hi + 1)))
    if kind == "evolution":
        return Q.evolution(pk)
    if kind == "where":
        return Q.where(v, "f0", ref.attr(probe, "f0"))
    if kind == "and":
        f1, w = ref.attr(probe, "f1"), traffic["and_f1_halfwidth"]
        return Q.and_(Q.where(v, "f0", ref.attr(probe, "f0")),
                      Q.where_range(v, "f1", max(0, f1 - w), f1 + w))
    if kind == "count":
        if rng.random() < 0.5:
            return Q.count(Q.where(v, "f1", ref.attr(probe, "f1")))
        return Q.count(Q.range(v, pk, pk + int(rng.integers(lo, hi + 1))))
    raise ValueError(f"unknown query kind {kind!r}")


# ----------------------------------------------------------------- the writer
class Writer:
    """Commits B1-shaped versions: ``pct_update`` of the parent's records
    changed (``frac_modify`` rewritten, ``frac_delete`` deleted,
    ``frac_insert`` new keys).  The parent is the writer's newest version,
    or with ``branch_prob`` a random earlier one."""

    def __init__(self, commit: Callable, ref, params: dict, payloads,
                 seed: int, head: int, next_key: int) -> None:
        self._commit = commit
        self.ref = ref
        self.p = params
        self.payloads = payloads
        self.rng = np.random.default_rng([seed, 3])
        self.head = head
        self.next_key = next_key
        self.committed: List[int] = []
        self.commit_s: List[float] = []

    def step(self) -> int:
        p, rng, ref = self.p, self.rng, self.ref
        parent = self.head
        if rng.random() < p["branch_prob"]:
            vids = ref.versions
            parent = int(vids[int(rng.integers(len(vids)))])
        keys = ref.sorted_keys(parent)
        sel = rng.choice(keys, max(1, int(len(keys) * p["pct_update"])),
                         replace=False).tolist()
        tot = p["frac_modify"] + p["frac_insert"] + p["frac_delete"]
        n_mod = int(len(sel) * p["frac_modify"] / tot)
        n_del = int(len(sel) * p["frac_delete"] / tot)
        n_ins = max(0, len(sel) - n_mod - n_del)
        new = list(range(self.next_key, self.next_key + n_ins))
        self.next_key += n_ins
        add_keys = sel[:n_mod] + new
        adds = dict(zip(add_keys, self.payloads(len(add_keys))))
        dels = sorted(sel[n_mod:n_mod + n_del])
        t0 = time.perf_counter()
        vid = self._commit(parent, adds, dels)
        self.commit_s.append(time.perf_counter() - t0)
        state = dict(ref.state(parent))
        for k in dels:
            del state[k]
        state.update(adds)
        ref.commit(vid, parent, adds, dels, state)
        self.committed.append(vid)
        self.head = vid
        return vid


# ------------------------------------------------------------ the wave former
@dataclass
class WindowLog:
    latency_s: np.ndarray                  # per query, due -> wave returned
    answers: List[object]                  # per query, what serve returned
    queries: List[object]                  # per query, the resolved query
    horizons: List[object] = field(default_factory=list)  # newest version
    #                                        committed when its wave began
    waves: List[tuple] = field(default_factory=list)  # (start, end, size)
    late_s: List[float] = field(default_factory=list)  # wave start - due
    serve_s: float = 0.0                   # sum of the serve timers
    elapsed_s: float = 0.0                 # open to the last answer
    close_s: float = 0.0                   # when the writer stopped
    durable_at_close: int = 0              # versions durable at close


def run_window(serve: Callable[[list], list], sched: Schedule,
               resolve_at: Callable[[int], object], wave_max: int,
               seconds: float, writer: Optional[Writer] = None,
               durable: Callable[[], int] = lambda: 0,
               horizon: Callable[[], object] = lambda: None,
               annotate: Callable = lambda name: contextlib.nullcontext(),
               clock: Callable[[], float] = time.perf_counter,
               sleep: Callable[[float], None] = time.sleep) -> WindowLog:
    """Drive one window: whenever the engine is free, serve every due
    query (up to ``wave_max``); when none is due, commit with the writer
    (until the window closes) or wait for the next arrival.  Every query
    due in the window is awaited."""
    n = len(sched.arrivals)
    log = WindowLog(np.zeros(n), [None] * n, [None] * n, [None] * n)
    t0 = clock()
    i = 0
    closed = False
    while i < n or (writer is not None and not closed):
        now = clock() - t0
        if writer is not None and not closed and now >= seconds:
            closed = True
            log.close_s, log.durable_at_close = now, durable()
        if i < n and sched.arrivals[i] <= now:
            j = int(np.searchsorted(sched.arrivals, now, side="right"))
            j = min(j, i + wave_max)
            with annotate("bench.form"):
                wave = [resolve_at(k) for k in range(i, j)]
            log.horizons[i:j] = [horizon()] * (j - i)
            ws = clock()
            with annotate("bench.serve"):
                answers = serve(wave)
            we = clock()
            if len(answers) != len(wave):
                raise RuntimeError(f"{len(answers)} answers for a wave of "
                                   f"{len(wave)} queries")
            log.serve_s += we - ws
            log.waves.append((ws - t0, we - t0, len(wave)))
            log.late_s.append(ws - t0 - float(sched.arrivals[i]))
            log.latency_s[i:j] = (we - t0) - sched.arrivals[i:j]
            log.answers[i:j] = answers
            log.queries[i:j] = wave
            i = j
        elif writer is not None and not closed:
            with annotate("bench.commit"):
                writer.step()
        elif i < n:
            with annotate("bench.wait"):
                sleep(max(0.0, float(sched.arrivals[i]) - now))
    log.elapsed_s = clock() - t0
    if writer is None:
        log.close_s = max(seconds, 0.0)
    return log


def percentile(values: Sequence[float], q: float) -> float:
    """The ``q``-th percentile (0-100) by linear interpolation between the
    closest ranks (numpy's default method)."""
    if len(values) == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))
