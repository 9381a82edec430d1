"""The benchmark's own generator for the paper's B-family datasets.

A copy of the B-family logic of ``repro.core.datagen.generate`` (RStore
section 5.1: a version tree grown from one root, each version extending the
head or, with ``branch_prob``, branching off a random earlier version, and
changing ``pct_update`` of its parent's records: 90% modified, 5% deleted,
5% new keys, picked uniformly or by Zipf).  It draws from the same random
stream in the same order, so for a given seed it yields the same versions
and payloads as that function; ``test_bench_gen.py`` holds it to that.  It
lives here so that a change to ``src/`` cannot move the yardstick, and it
emits what a loader needs directly: the commits and every version's state.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

# (vid, parent or None for the root, adds {pk: payload}, deleted pks)
Commit = Tuple[int, Optional[int], Dict[int, bytes], List[int]]


def payload_maker(rng: np.random.Generator, record_size: int,
                  attr_fields: int, attr_cardinality: int):
    """``make(n)``: the next ``n`` payloads of ``record_size`` random bytes
    whose first ``4 * attr_fields`` bytes are little-endian uint32
    attributes drawn from ``[0, attr_cardinality)``, the layout
    ``datagen_extractor`` reads.

    Each payload is one ``integers(0, 256, record_size, uint8)`` draw and
    one ``integers(0, attr_cardinality, attr_fields, uint32)`` draw, as in
    ``repro.core.datagen``.  Where the record size is a multiple of 4 and
    the cardinality a power of two, each byte quadruple and each attribute
    takes exactly one 32-bit word of the stream (the bytes little-endian,
    the attribute the word's top bits), so ``n`` payloads are cut from one
    draw of words that yields the same bytes."""
    n_pre = 4 * attr_fields
    bits = attr_cardinality.bit_length() - 1
    fast = (record_size % 4 == 0 and record_size >= n_pre
            and attr_cardinality == 1 << bits and 1 <= bits <= 32)

    def one() -> bytes:
        raw = rng.integers(0, 256, size=record_size, dtype=np.uint8)
        if attr_fields > 0:
            vals = rng.integers(0, attr_cardinality, size=attr_fields,
                                dtype=np.uint32)
            pre = np.frombuffer(vals.astype("<u4").tobytes(), dtype=np.uint8)
            if len(raw) < n_pre:
                raw = np.concatenate([raw, np.zeros(n_pre - len(raw),
                                                    np.uint8)])
            raw[:n_pre] = pre
        return raw.tobytes()

    def make(n: int) -> List[bytes]:
        if not fast or attr_fields == 0:
            return [one() for _ in range(n)]
        words = record_size // 4
        w = rng.integers(0, 1 << 32, size=(n, words + attr_fields),
                         dtype=np.uint32)
        rec = w[:, :words].astype("<u4")
        rec[:, :attr_fields] = w[:, words:] >> np.uint32(32 - bits)
        buf = rec.tobytes()
        size = record_size
        return [buf[i * size:(i + 1) * size] for i in range(n)]

    return make


def generate(ds: dict, seed: int
             ) -> Tuple[List[Commit], List[Dict[int, bytes]]]:
    """Commits of the dataset ``ds`` (a configuration's ``dataset`` group)
    in commit order, and each version's state ``{pk: payload}`` indexed by
    version id."""
    if ds.get("size_sigma", 0) or ds.get("merge_prob", 0) or \
            ds.get("p_d") is not None:
        raise ValueError("the B-family generator has fixed record sizes, "
                         "no merges and whole-record rewrites")
    rng = np.random.default_rng(seed)
    payloads = payload_maker(rng, ds["record_size"], ds["attr_fields"],
                             ds["attr_cardinality"])
    n0 = ds["n_base_records"]
    root = dict(zip(range(n0), payloads(n0)))
    commits: List[Commit] = [(0, None, root, [])]
    states: List[Dict[int, bytes]] = [root]

    fm, fi, fd = ds["frac_modify"], ds["frac_insert"], ds["frac_delete"]
    tot = fm + fi + fd
    next_key, head = n0, 0
    for vid in range(1, ds["n_versions"]):
        if rng.random() < ds["branch_prob"] and vid > 2:
            parent = int(rng.integers(0, vid))
        else:
            parent = head
        pmap = states[parent]
        pkeys = np.fromiter(pmap.keys(), dtype=np.int64, count=len(pmap))

        n_sel = max(1, int(len(pkeys) * ds["pct_update"]))
        size = min(n_sel, len(pkeys))
        if ds["update_dist"] == "zipf":
            w = 1.0 / np.power(pkeys + 1.0, ds["zipf_a"])
            w /= w.sum()
            sel = rng.choice(pkeys, size=size, replace=False, p=w)
        else:
            sel = rng.choice(pkeys, size=size, replace=False)
        n_mod = int(len(sel) * fm / tot)
        n_del = int(len(sel) * fd / tot)
        n_ins = max(0, len(sel) - n_mod - n_del)
        mod_keys = sel[:n_mod].tolist()
        del_keys = sel[n_mod:n_mod + n_del].tolist()
        new_keys = list(range(next_key, next_key + n_ins))
        next_key += n_ins

        keys = mod_keys + new_keys
        adds = dict(zip(keys, payloads(len(keys))))
        state = dict(pmap)
        for k in del_keys:
            del state[k]
        state.update(adds)
        commits.append((vid, parent, adds, sorted(set(del_keys))))
        states.append(state)
        head = vid
    return commits, states
