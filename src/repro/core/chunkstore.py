"""Physical chunk layout + chunk maps (§2.4).

A stored chunk holds (a) its records' payloads grouped into *sub-chunks*
(singleton sub-chunks unless §3.4 compression is enabled: records of one
primary key, connected in the version tree, XOR-delta'd against their
sub-chunk parent and zlib'd together), and (b) the chunk map ``M^{C_i}`` —
for each record, the set of versions containing it, stored as a bitmap over
version indices ("the adjacency list in each chunk map file is then converted
to a bitmap, compressed and stored in the KVS").
"""
from __future__ import annotations

import struct
import zlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..kernels import ops as kops
from . import trace
from .types import Partitioning
from .version_graph import VersionGraph


# ------------------------------------------------------------------ chunk map
@dataclass
class ChunkMap:
    """Per-chunk slice of the 3-D mapping M (Fig. 3): record composite keys +
    a (n_rec, W) uint32 bitmap of version-index membership."""

    cks: np.ndarray            # (n_rec,) int64 packed composite keys
    bitmap: np.ndarray         # (n_rec, W) uint32
    n_versions: int

    def records_in_version(self, vidx: int) -> np.ndarray:
        w, bit = divmod(vidx, 32)
        hit = (self.bitmap[:, w] >> np.uint32(bit)) & np.uint32(1)
        return np.flatnonzero(hit)

    def versions_of_record(self, local_idx: int) -> np.ndarray:
        row = self.bitmap[local_idx]
        out = []
        for w in range(len(row)):
            v = int(row[w])
            while v:
                b = v & -v
                out.append(w * 32 + b.bit_length() - 1)
                v ^= b
        return np.asarray([o for o in out if o < self.n_versions], dtype=np.int64)

    def to_bytes(self) -> bytes:
        raw = self.bitmap.astype("<u4").tobytes()
        comp = zlib.compress(raw, level=6)
        head = struct.pack("<IIII", len(self.cks), self.bitmap.shape[1],
                           self.n_versions, len(comp))
        return head + self.cks.astype("<i8").tobytes() + comp

    @staticmethod
    def from_bytes(buf: bytes) -> "ChunkMap":
        n_rec, w, n_ver, clen = struct.unpack_from("<IIII", buf, 0)
        off = 16
        cks = np.frombuffer(buf, dtype="<i8", count=n_rec, offset=off).astype(np.int64)
        off += n_rec * 8
        raw = zlib.decompress(buf[off:off + clen])
        bitmap = np.frombuffer(raw, dtype="<u4").reshape(n_rec, w).astype(np.uint32)
        return ChunkMap(cks=cks, bitmap=bitmap, n_versions=n_ver)


# --------------------------------------------------------------- stored chunk
@dataclass
class SubChunkBlob:
    """One compressed sub-chunk: local record indices (first = raw base, the
    rest XOR-delta'd against their sub-chunk tree parent) + payload blob."""

    local_ids: np.ndarray      # int32 local record indices, tree (BFS) order
    parent_pos: np.ndarray     # int32: position *within sub-chunk* of each
    #                            record's delta parent (-1 = stored raw)
    lengths: np.ndarray        # int32 true payload lengths
    blob: bytes                # zlib(concat of raw-or-delta payloads)


@dataclass
class StoredChunk:
    chunk_id: int
    cks: np.ndarray                      # (n_rec,) packed composite keys
    subchunks: List[SubChunkBlob]
    raw_bytes: int = 0                   # un-encoded payload bytes
    stored_bytes: int = 0                # encoded (what the KVS holds)
    # memoized serialization: chunks are write-once, and the build paths
    # both size the encoding and stage it for the group commit
    _encoded: Optional[bytes] = field(default=None, repr=False, compare=False)

    def payloads(self) -> Dict[int, bytes]:
        """Decode every record: local index -> payload bytes."""
        with trace.span("rstore.decode"):
            return self._payloads()

    def _payloads(self) -> Dict[int, bytes]:
        out: Dict[int, bytes] = {}
        for sc in self.subchunks:
            raw = zlib.decompress(sc.blob)
            parts: List[bytes] = []
            off = 0
            dec: List[bytes] = []
            for i, ln in enumerate(sc.lengths):
                ln = int(ln)
                # deltas are stored at the max(parent,child) length
                p = int(sc.parent_pos[i])
                stored_len = ln if p < 0 else max(ln, len(dec[p]))
                piece = raw[off:off + stored_len]
                off += stored_len
                if p < 0:
                    dec.append(piece[:ln])
                else:
                    plain, _ = kops.xor_delta_bytes(
                        dec[p].ljust(stored_len, b"\0"), piece)
                    dec.append(plain[:ln])
            for li, payload in zip(sc.local_ids, dec):
                out[int(li)] = payload
        return out

    # ------------------------------------------------------------ serialization
    def to_bytes(self) -> bytes:
        if self._encoded is None:
            parts = [struct.pack("<III", self.chunk_id, len(self.cks), len(self.subchunks))]
            parts.append(self.cks.astype("<i8").tobytes())
            for sc in self.subchunks:
                parts.append(struct.pack("<II", len(sc.local_ids), len(sc.blob)))
                parts.append(sc.local_ids.astype("<i4").tobytes())
                parts.append(sc.parent_pos.astype("<i4").tobytes())
                parts.append(sc.lengths.astype("<i4").tobytes())
                parts.append(sc.blob)
            self._encoded = b"".join(parts)
        return self._encoded

    @staticmethod
    def from_bytes(buf: bytes) -> "StoredChunk":
        cid, n_rec, n_sub = struct.unpack_from("<III", buf, 0)
        off = 12
        cks = np.frombuffer(buf, dtype="<i8", count=n_rec, offset=off).astype(np.int64)
        off += 8 * n_rec
        subs = []
        for _ in range(n_sub):
            n, blen = struct.unpack_from("<II", buf, off)
            off += 8
            li = np.frombuffer(buf, dtype="<i4", count=n, offset=off).astype(np.int32)
            off += 4 * n
            pp = np.frombuffer(buf, dtype="<i4", count=n, offset=off).astype(np.int32)
            off += 4 * n
            ln = np.frombuffer(buf, dtype="<i4", count=n, offset=off).astype(np.int32)
            off += 4 * n
            blob = buf[off:off + blen]
            off += blen
            subs.append(SubChunkBlob(li, pp, ln, blob))
        sc = StoredChunk(chunk_id=cid, cks=cks, subchunks=subs)
        sc.stored_bytes = len(buf)
        sc.raw_bytes = int(sum(s.lengths.sum() for s in subs))
        return sc


# -------------------------------------------------------------------- builder
def build_chunk(graph: VersionGraph, record_ids: np.ndarray, chunk_id: int,
                vidx_of: Dict[int, int], n_versions: int,
                rec_versions_csr: Tuple[np.ndarray, np.ndarray],
                subchunk_groups: Optional[List[np.ndarray]] = None,
                compress_level: int = 6) -> Tuple[StoredChunk, ChunkMap]:
    """Assemble one physical chunk + its chunk map.

    ``subchunk_groups``: optional list of record-id arrays (each a connected
    same-primary-key group in sub-chunk tree order, §3.4); defaults to
    singleton groups.  Records absent from any group get singletons.
    """
    store = graph.store
    local_of = {int(r): i for i, r in enumerate(record_ids)}
    cks = store.cks[record_ids]

    groups: List[np.ndarray]
    if subchunk_groups is None:
        groups = [np.array([r], dtype=np.int64) for r in record_ids]
    else:
        seen = set()
        groups = []
        for grp in subchunk_groups:
            groups.append(np.asarray(grp, dtype=np.int64))
            seen.update(int(g) for g in grp)
        for r in record_ids:
            if int(r) not in seen:
                groups.append(np.array([r], dtype=np.int64))

    raw_total = 0
    subs: List[SubChunkBlob] = []
    tree_parent_rid = _subchunk_parents(graph, groups)
    for grp, parents in zip(groups, tree_parent_rid):
        local = np.array([local_of[int(r)] for r in grp], dtype=np.int32)
        lens = store.sizes[grp].astype(np.int32)
        pieces: List[bytes] = []
        payloads = [store.payload(int(r)) if store.has_payloads() else b"\0" * int(store.sizes[r])
                    for r in grp]
        raw_total += int(lens.sum())
        ppos = np.full(len(grp), -1, dtype=np.int32)
        pos_of = {int(r): i for i, r in enumerate(grp)}
        for i, r in enumerate(grp):
            par = parents[i]
            if par is None or int(par) not in pos_of:
                pieces.append(payloads[i])
            else:
                pi = pos_of[int(par)]
                ppos[i] = pi
                w = max(len(payloads[pi]), len(payloads[i]))
                delta, _ = kops.xor_delta_bytes(payloads[pi].ljust(w, b"\0"),
                                                payloads[i].ljust(w, b"\0"))
                pieces.append(delta)
        blob = zlib.compress(b"".join(pieces), level=compress_level)
        subs.append(SubChunkBlob(local_ids=local, parent_pos=ppos,
                                 lengths=lens, blob=blob))

    chunk = StoredChunk(chunk_id=chunk_id, cks=cks, subchunks=subs,
                        raw_bytes=raw_total)
    chunk.stored_bytes = len(chunk.to_bytes())

    # ---- chunk map: bitmap over version indices --------------------------
    W = (n_versions + 31) // 32
    bitmap = np.zeros((len(record_ids), W), dtype=np.uint32)
    indptr, vidxs = rec_versions_csr
    for i, r in enumerate(record_ids):
        vs = vidxs[indptr[r]:indptr[r + 1]]
        # bitwise_or.at: unbuffered — duplicate word indices must accumulate
        np.bitwise_or.at(bitmap[i], vs // 32,
                         np.uint32(1) << (vs % 32).astype(np.uint32))
    cmap = ChunkMap(cks=cks, bitmap=bitmap, n_versions=n_versions)
    return chunk, cmap


def _subchunk_parents(graph: VersionGraph, groups: List[np.ndarray]):
    """For each group, the delta-parent record id of each member (None = raw).
    Members are same-primary-key records connected in the version tree; the
    parent of record (K, Vc) is the record (K, Vp) live at the nearest proper
    ancestor of Vc — within the group, that is the group member whose origin
    version is the closest ancestor."""
    origins = graph.store.origin_versions()
    out = []
    for grp in groups:
        if len(grp) == 1:
            out.append([None])
            continue
        grp_origin = {int(origins[r]): int(r) for r in grp}
        parents: List[Optional[int]] = []
        for r in grp:
            v = int(origins[r])
            p = graph.tree_parent(v)
            found = None
            while p is not None:
                if p in grp_origin:
                    found = grp_origin[p]
                    break
                p = graph.tree_parent(p)
            parents.append(found)
        out.append(parents)
    return out
