"""Spans of the store's read path, kept in memory and on the profiler's clock.

A *wave* is one call of ``StoreQueryEngine.serve``: its root span
(``rstore.serve``) is opened with :func:`wave`, and every :func:`span`
opened inside it, on the same thread, is recorded under it.  A finished
wave goes into :data:`WAVES` as a list of :class:`Span`, root first, in the
order the spans were opened.  The log is process-wide and bounded: a reader
that wants a window of waves finds them there after the window closes.

Each span also enters ``jax.profiler.TraceAnnotation(name)``, so that while
a profiler session is open it lands on the host plane of the trace, on the
device ops' clock.  There is no switch: with no profiler session open a
span costs two clock reads, an annotation that records nothing, and an
append.  A span opened while no wave is open on its thread records nothing
in the log.

Span names, and the counts a metric reads (integers, set at entry or
before exit):

- ``rstore.serve``: ``queries``, the wave's size: the wave's root;
- ``rstore.plan``: the planner, register file staging and the bitmap-VM
  launch;
- ``rstore.gather``: ``new_length``, ``pad_bytes``: one device table's
  multiget; ``new_length`` is 1 where the call grew the process's
  ``gather_rows`` jit cache, i.e. compiled (or loaded) a program for a new
  (table shape, bucketed index length); ``pad_bytes`` counts the rows
  gathered only to pad the index up to its bucket;
- ``rstore.decode``: chunk and chunk-map decoding (zlib and XOR delta);
- ``rstore.answer``: the answer step over the fetched chunks.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from dataclasses import dataclass, field
from typing import Deque, Dict, Iterator, List, Optional

import jax

WAVE_LOG_MAX = 4096           # finished waves kept, newest last


@dataclass
class Span:
    name: str
    id: int
    parent: Optional[int]      # id of the enclosing span; None for a root
    wave: Optional[int]        # id of the wave's root span
    start_ns: int
    end_ns: int = 0
    counts: Dict[str, int] = field(default_factory=dict)

    @property
    def duration_ns(self) -> int:
        return self.end_ns - self.start_ns


WAVES: Deque[List[Span]] = collections.deque(maxlen=WAVE_LOG_MAX)

_ids = itertools.count(1)


class _Thread(threading.local):
    def __init__(self) -> None:
        self.stack: List[Span] = []          # open spans, innermost last
        self.wave: Optional[List[Span]] = None  # the open wave's spans


_local = _Thread()


@contextlib.contextmanager
def span(name: str, **counts: int) -> Iterator[Span]:
    """Time the block as ``name``; the yielded span's ``counts`` may be set
    before the block ends."""
    st = _local
    parent = st.stack[-1] if st.stack else None
    s = Span(name, next(_ids), parent.id if parent else None,
             parent.wave if parent else None, 0, counts=counts)
    if st.wave is not None:
        st.wave.append(s)
    st.stack.append(s)
    with jax.profiler.TraceAnnotation(name):
        s.start_ns = time.perf_counter_ns()
        try:
            yield s
        finally:
            s.end_ns = time.perf_counter_ns()
            st.stack.pop()


@contextlib.contextmanager
def wave(name: str, **counts: int) -> Iterator[Span]:
    """Open a wave whose root span is ``name``; on exit the wave's spans go
    into :data:`WAVES`.  Inside an open wave this is a plain :func:`span`."""
    st = _local
    if st.wave is not None:
        with span(name, **counts) as s:
            yield s
        return
    st.wave = spans = []
    try:
        with span(name, **counts) as root:
            root.parent, root.wave = None, root.id
            yield root
    finally:
        st.wave = None
        WAVES.append(spans)


def self_ns(s: Span, spans: List[Span]) -> int:
    """``s``'s duration less the part its child spans (in ``spans``)
    cover."""
    kids = sorted((max(c.start_ns, s.start_ns), min(c.end_ns, s.end_ns))
                  for c in spans if c.parent == s.id)
    covered, t = 0, s.start_ns
    for a, b in kids:
        a = max(a, t)
        if b > a:
            covered += b - a
            t = b
    return s.duration_ns - covered
