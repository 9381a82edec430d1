"""The program's spans of the window's waves (``repro.core.trace``).

The program logs every wave it serves, its root span ``rstore.serve``
first, in ``trace.WAVES``: warm-up waves before the window, and a writer
cell's read-back waves after it, are there too.  The window's waves are the
last run of consecutive logged waves whose sizes (the root's ``queries``)
are the window's wave sizes, in order, each root no longer than the
benchmark's timer around that wave.  A program without the log, or a window
whose waves are not there (a control in the program's place), gives
``None``.
"""
from typing import List, Optional

MS = 1e-6        # ns -> ms


def window_waves(run) -> Optional[List[list]]:
    try:
        from repro.core import trace
    except ImportError:
        return None
    want = run.window.waves          # (start s, end s, size)
    log = list(trace.WAVES)
    n = len(want)
    if n == 0:
        return None

    def fits(w, wave) -> bool:
        root = w[0]
        return (root.name == "rstore.serve"
                and root.counts.get("queries") == wave[2]
                and root.duration_ns <= (wave[1] - wave[0]) * 1e9 + 1e3)
    for i in range(len(log) - n, -1, -1):
        if all(fits(log[i + j], want[j]) for j in range(n)):
            return log[i:i + n]
    return None


def per_query(run, spans_ms) -> Optional[float]:
    """``spans_ms(wave) -> ms`` summed over the window's waves, per query."""
    waves = window_waves(run)
    if waves is None:
        return None
    return sum(spans_ms(w) for w in waves) / run.n_queries


def total_ms(wave, name: str, **counts) -> float:
    """Summed duration of the wave's spans named ``name`` whose counts
    include ``counts``."""
    return MS * sum(s.duration_ns for s in wave if s.name == name and all(
        s.counts.get(k) == v for k, v in counts.items()))


def self_ms(wave, name: str) -> float:
    """Summed self time (duration less child spans) of the wave's spans
    named ``name``."""
    from repro.core import trace
    return MS * sum(trace.self_ns(s, wave) for s in wave if s.name == name)
