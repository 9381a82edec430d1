"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is reduced from a flat list of events ``(plane, line, name,
start_ns, duration_ns)``: the device planes (``/device:TPU:<n>``) with
their op line, and the benchmark's own host spans (``bench.*``
``TraceAnnotation``\\ s) from the host plane, all on one clock.

- busy: the union of the intervals in which an op ran on a device, inside
  the traced window (the ``bench.window`` span), averaged over the devices
  that ran anything;
- device ops: the summed device time of each op name;
- idle gaps: each stretch of the window in which no op ran on the first
  device, attributed to the innermost ``bench.*`` span that covers it (what
  the host was doing), summed by span name;
- kernel time: the summed device time of a kernel's op events.

On a TPU the op line is ``XLA Ops``, whose events are named by their HLO
instruction text, and the ``XLA Modules`` line names the jitted program
each op ran in.  The store's Pallas kernels all run in modules named
``jit__unknown``, so a kernel is told apart by its call's operands: the
XOR-delta kernel is the ``tpu_custom_call`` whose operands are named
``parent`` and ``child`` (``kernels/deltaenc.py``).
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, str, str, float, float]

DEVICE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"

# how each kernel's op events are recognised by name
KERNEL_OPS = {
    "xor_delta": lambda n: ("tpu_custom_call" in n and "%parent" in n
                            and "%child" in n),
}
HOST_PREFIX = "bench."
WINDOW = "bench.window"


def events_from_dir(log_dir: str) -> List[Event]:
    """The events of the one ``.xplane.pb`` under ``log_dir`` that the
    reduction reads: every device-plane event and the host's ``bench.*``
    spans."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{len(paths)}")
    out: List[Event] = []
    for plane in ProfileData.from_file(paths[0]).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if device or e.name.startswith(HOST_PREFIX):
                    out.append((plane.name, line.name, e.name,
                                float(e.start_ns), float(e.duration_ns)))
    return out


def _union(intervals: Iterable[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(s: float, e: float, lo: float, hi: float
          ) -> Optional[Tuple[float, float]]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def window_of(events: Sequence[Event]) -> Tuple[float, float]:
    spans = [(s, s + d) for _, _, n, s, d in events if n == WINDOW]
    if len(spans) != 1:
        raise RuntimeError(f"expected one {WINDOW} span, found {len(spans)}")
    return spans[0]


def device_ops(events: Sequence[Event], lo: float, hi: float
               ) -> Dict[str, List[Tuple[float, float]]]:
    """Op intervals inside [lo, hi] by device plane."""
    out: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    for plane, line, _, s, d in events:
        if plane.startswith(DEVICE_PREFIX) and line == OP_LINE:
            iv = _clip(s, s + d, lo, hi)
            if iv:
                out[plane].append(iv)
    return out


def reduce(events: Sequence[Event], top: int = 10) -> Dict[str, object]:
    """``busy_s``, ``window_s``, the ``top`` device ops by time and the
    ``top`` idle-gap causes by time."""
    lo, hi = window_of(events)
    ops = device_ops(events, lo, hi)
    busy = {p: _union(iv) for p, iv in ops.items()}
    busy_s = (sum(sum(e - s for s, e in u) for u in busy.values())
              / len(busy) / 1e9) if busy else 0.0

    by_name: Dict[str, float] = defaultdict(float)
    module_of = _module_finder(events)
    for plane, line, name, s, d in events:
        if plane.startswith(DEVICE_PREFIX) and line == OP_LINE:
            iv = _clip(s, s + d, lo, hi)
            if iv:
                by_name[f"{module_of(plane, s)}:{op_label(name)}"] += \
                    (iv[1] - iv[0]) / 1e9

    first = sorted(busy)[0] if busy else None
    gaps, t = [], lo
    for s, e in (busy[first] if first else []):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    segs = host_segments(events)
    idle: Dict[str, float] = defaultdict(float)
    k = 0
    for gs, ge in gaps:
        covered = 0.0
        while k < len(segs) and segs[k][1] <= gs:
            k += 1
        j = k
        while j < len(segs) and segs[j][0] < ge:
            iv = _clip(segs[j][0], segs[j][1], gs, ge)
            if iv:
                idle[segs[j][2]] += (iv[1] - iv[0]) / 1e9
                covered += iv[1] - iv[0]
            j += 1
        if ge - gs > covered:
            idle["(no bench span)"] += (ge - gs - covered) / 1e9

    def ranked(d: Dict[str, float]) -> List[List[object]]:
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                if v > 0][:top]

    return {"busy_s": busy_s, "window_s": (hi - lo) / 1e9,
            "device_ops": ranked(by_name), "idle_gaps": ranked(idle)}


def op_label(name: str) -> str:
    """An op's name without its operands and numbering:
    ``%fusion.3 = u32[..] fusion(..)`` -> ``fusion``."""
    head = name.split(" = ")[0].lstrip("%")
    base, _, num = head.rpartition(".")
    return base if base and num.isdigit() else head


def _module_finder(events: Sequence[Event]):
    """``(plane, t) -> name`` of the module running on ``plane`` at ``t``,
    without its fingerprint: ``jit__lambda(123)`` -> ``jit__lambda``."""
    mods: Dict[str, List[Tuple[float, float, str]]] = defaultdict(list)
    for plane, line, name, s, d in events:
        if plane.startswith(DEVICE_PREFIX) and line == MODULE_LINE:
            mods[plane].append((s, s + d, name.split("(")[0]))
    starts = {p: [m[0] for m in sorted(v)] for p, v in mods.items()}
    mods = {p: sorted(v) for p, v in mods.items()}

    def find(plane: str, t: float) -> str:
        i = bisect.bisect_right(starts.get(plane, []), t) - 1
        if i >= 0 and mods[plane][i][1] >= t:
            return mods[plane][i][2]
        return "?"
    return find


def host_segments(events: Sequence[Event]) -> List[Tuple[float, float, str]]:
    """The host's ``bench.*`` spans (the window aside) cut into disjoint
    segments, each named by the innermost span that covers it.  Spans of
    one thread nest, so a stack of open spans gives the innermost."""
    spans = sorted(((s, s + d, n) for p, _, n, s, d in events
                    if not p.startswith(DEVICE_PREFIX)
                    and n.startswith(HOST_PREFIX) and n != WINDOW),
                   key=lambda x: (x[0], -x[1]))
    out: List[Tuple[float, float, str]] = []
    stack: List[Tuple[float, float, str]] = []
    t = spans[0][0] if spans else 0.0

    def emit(until: float) -> None:
        nonlocal t
        if stack and until > t:
            out.append((t, until, stack[-1][2]))
        t = max(t, until)

    for s, e, n in spans:
        while stack and stack[-1][1] <= s:
            emit(stack[-1][1])
            stack.pop()
        emit(s)
        stack.append((s, e, n))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


def kernel_seconds(events: Sequence[Event], kernel: str) -> float:
    """Summed device time, inside the window, of ``kernel``'s op events
    (``KERNEL_OPS``)."""
    lo, hi = window_of(events)
    match = KERNEL_OPS[kernel]
    total = 0.0
    for plane, line, name, s, d in events:
        if plane.startswith(DEVICE_PREFIX) and line == OP_LINE \
                and match(name):
            iv = _clip(s, s + d, lo, hi)
            if iv:
                total += iv[1] - iv[0]
    return total / 1e9
