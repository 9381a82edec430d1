"""The plain reference: a dict of versions, each ``{pk: payload}``.

A version's state is its parent's with the version's deletes and adds
replayed on it; a key's evolution is every payload ever added under it, in
version order.  Queries are answered by brute force over those dicts.  It
shares nothing with the store under test but the query objects it reads
(``repro.core.Q``), whose fields name what was asked.
"""
from __future__ import annotations

import struct
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


class Reference:
    def __init__(self, attrs: Sequence[str]) -> None:
        self.attrs = tuple(attrs)
        self._delta: Dict[int, Tuple[Optional[int], Dict[int, bytes],
                                     List[int]]] = {}
        self._history: Dict[int, List[Tuple[int, bytes]]] = {}
        self._states: Dict[int, Dict[int, bytes]] = {}
        self._keys: Dict[int, np.ndarray] = {}

    def commit(self, vid: int, parent: Optional[int], adds: Dict[int, bytes],
               dels: Sequence[int],
               state: Optional[Dict[int, bytes]] = None) -> None:
        """Record a version; ``state``, where the caller already holds it,
        saves replaying it."""
        if vid in self._delta:
            raise ValueError(f"version {vid} committed twice")
        self._delta[vid] = (parent, adds, list(dels))
        for pk, payload in adds.items():
            self._history.setdefault(pk, []).append((vid, payload))
        if state is not None:
            self._states[vid] = state

    @property
    def versions(self) -> List[int]:
        return list(self._delta)

    def parent(self, vid: int) -> Optional[int]:
        return self._delta[vid][0]

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

    def sorted_keys(self, vid: int) -> np.ndarray:
        """The version's live primary keys, ascending."""
        if vid not in self._keys:
            self._keys[vid] = np.sort(np.fromiter(
                self.state(vid), dtype=np.int64))
        return self._keys[vid]

    def attr(self, payload: bytes, name: str) -> int:
        return struct.unpack_from("<I", payload, 4 * self.attrs.index(name))[0]

    def _matches(self, q, pk: int, payload: bytes) -> bool:
        k = q.kind
        if k == "version":
            return True
        if k == "records":
            return pk in q.pks
        if k == "range":
            return q.key_lo <= pk <= q.key_hi
        if k == "where":
            return self.attr(payload, q.attr) == q.value
        if k == "where_range":
            return q.key_lo <= self.attr(payload, q.attr) <= q.key_hi
        if k == "and":
            return all(self._matches(c, pk, payload) for c in q.children)
        raise ValueError(f"no reference for query kind {k!r}")

    def expected(self, q, upto: Optional[int] = None):
        """The answer to ``q``, in the form ``QueryResult.value`` takes,
        as of the versions up to ``upto`` (all where None): a key's
        evolution grows as versions are committed."""
        if q.kind == "evolution":
            return sorted((t for t in self._history.get(q.pk, [])
                           if upto is None or t[0] <= upto),
                          key=lambda t: t[0])
        if q.kind == "count":
            return len(self.expected(q.children[0]))
        state = self.state(q.vid)
        if q.kind == "record":
            return state.get(q.pk)
        if q.kind == "records":
            return {pk: state[pk] for pk in q.pks if pk in state}
        return {pk: p for pk, p in state.items() if self._matches(q, pk, p)}
