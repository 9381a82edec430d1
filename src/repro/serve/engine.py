"""Serving engines: the store's query front-end and the LLM decode loop.

:class:`StoreQueryEngine` is the RStore serving surface: it pins a snapshot
per wave of queries and routes every wave through the unified planner
(:mod:`repro.core.plan` via ``Snapshot.execute`` — the same one-launch /
one-multiget pipeline the session API uses), transparently re-pinning when
a compaction pass re-partitions chunk storage under it.

:class:`Engine` is the batched LLM engine: prefill + jitted greedy decode.
The decode loop runs as a single jitted ``lax.scan`` over steps (one dispatch
per generation call, not per token), with caches donated between steps — the
pattern a production server uses per wave of a continuous-batching scheduler.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..core import trace
from ..models.config import ModelConfig
from ..models.model import Model, build_model


class StoreQueryEngine:
    """Store-serving front-end: waves of queries over pinned snapshots.

    Holds one snapshot at a time and executes whole waves against it —
    planning, kernel launches and the KVS multiget are batched per wave by
    the planner, not per query.  A full ``build()`` under the engine
    invalidates the pin and the next wave re-snapshots; a compaction pass
    just re-pins via ``snapshot.refresh()``.  Each wave is one
    ``rstore.serve`` wave in :data:`repro.core.trace.WAVES`.
    """

    def __init__(self, rs) -> None:
        self.rs = rs
        self._snap = None

    def snapshot(self):
        """The current pinned snapshot (taken lazily, kept across waves)."""
        if self._snap is None:
            self._snap = self.rs.snapshot()
        return self._snap

    def _fresh_snapshot(self):
        snap = self.snapshot()
        try:
            snap._check_fresh()
        except RuntimeError:
            try:
                snap = snap.refresh()          # compaction: re-pin in place
            except RuntimeError:
                snap = self.rs.snapshot()      # full rebuild: new snapshot
            self._snap = snap
        return snap

    def serve(self, queries: Sequence[Any]):
        """Execute one wave → :class:`~repro.core.plan.BatchResult`."""
        queries = list(queries)
        with trace.wave("rstore.serve", queries=len(queries)):
            return self._fresh_snapshot().execute(queries)

    def explain(self, queries: Sequence[Any]) -> List[Dict[str, Any]]:
        """Rendered plans + predicted costs for a wave (no execution)."""
        return self._fresh_snapshot().explain(list(queries))

    def warm(self, queries: Sequence[Any]) -> Dict[str, int]:
        """Prefetch a wave's chunks into the cache layer, if one is on."""
        return self._fresh_snapshot().prefetch(list(queries))


class Engine:
    def __init__(self, cfg: ModelConfig, params, max_len: int = 4096):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.params = params
        self.max_len = max_len
        self._prefill = jax.jit(
            functools.partial(self.model.prefill, max_len=max_len))
        self._gen = jax.jit(self._generate_scan, static_argnames=("steps",))

    def _generate_scan(self, params, caches, first_tok, start_pos, *, steps):
        def step(carry, _):
            tok, pos, caches = carry
            nxt, caches = self.model.decode_step(params, caches, tok, pos)
            return (nxt[:, None], pos + 1, caches), nxt

        (_, _, caches), toks = jax.lax.scan(
            step, (first_tok, start_pos, caches), None, length=steps)
        return jnp.moveaxis(toks, 0, 1), caches     # (B, steps)

    def generate(self, batch: Dict[str, jax.Array], steps: int):
        """Greedy-decode ``steps`` tokens after the prompt."""
        prompt_len = batch["tokens"].shape[1]
        assert prompt_len + steps <= self.max_len, "exceeds cache capacity"
        logits, caches = self._prefill(self.params, batch)
        first = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        toks, caches = self._gen(self.params, caches, first,
                                 jnp.int32(prompt_len), steps=steps - 1)
        return jnp.concatenate([first, toks], axis=1)
