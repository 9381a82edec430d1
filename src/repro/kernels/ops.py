"""Public jit'd wrappers around the Pallas kernels.

These handle padding/blocking to the kernels' tile contracts and expose
NumPy-friendly entry points for the host-side partitioners.

Dispatch policy, decided per call from the backend JAX runs on: on TPU the
Pallas kernels run compiled; on CPU the entry points run their jitted jnp
twins from :mod:`repro.kernels.ref` (bit-identical — asserted by
tests/test_kernels.py, which also runs the kernels under interpret=True);
any other platform raises.  Interpret-mode Pallas executes kernel bodies in
Python and is far too slow for store-sized batches, so it is never a
fallback.
"""
from __future__ import annotations

import collections
import functools
from typing import Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import bitmap as _bitmap
from . import deltaenc as _deltaenc
from . import minhash as _minhash
from . import ref

_P_LANE = 128


def _pad_to(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _compiled(kernel: Callable) -> Callable:
    """``kernel`` jitted, compiled for the chip, under its own name and
    parameter names: the trace's module is ``jit_<kernel>`` and its
    operands keep the kernel's argument names."""
    @functools.wraps(kernel)
    def run(*args):
        return kernel(*args, interpret=False)
    return jax.jit(run)


# Compiled-kernel launches since import, by kernel name (TPU path only).
KERNEL_LAUNCHES: collections.Counter = collections.Counter()


def _pick(name: str, twin: Callable) -> Callable:
    """The compiled kernel ``KERNELS[name]`` on TPU, its jnp twin on CPU."""
    platform = jax.default_backend()
    if platform == "tpu":
        KERNEL_LAUNCHES[name] += 1
        return KERNELS[name]
    if platform == "cpu":
        return twin
    raise RuntimeError(f"no kernel path for JAX platform {platform!r}: "
                       "the store runs its Pallas kernels on TPU and their "
                       "jnp twins on CPU")


# ------------------------------------------------------------------ minhash
_minhash_kernel = _compiled(_minhash.minhash)
_minhash_twin = jax.jit(ref.minhash_ref)


def hash_family(n_hashes: int, seed: int = 0) -> Tuple[np.ndarray, np.ndarray]:
    """Multiply-shift universal hash family: odd multipliers + offsets."""
    rng = np.random.default_rng(seed)
    a = (rng.integers(0, 2**32, size=n_hashes, dtype=np.uint32) | 1).astype(np.uint32)
    b = rng.integers(0, 2**32, size=n_hashes, dtype=np.uint32)
    return a, b


def minhash_padded(versions_padded: np.ndarray, a: np.ndarray,
                   b: np.ndarray) -> np.ndarray:
    """Pad (R, D) rows to tile boundaries and min-hash them. Returns (R, L)."""
    R, D = versions_padded.shape
    Rp = _pad_to(max(R, 1), _minhash.BLOCK_R)
    Dp = _pad_to(max(D, 1), _P_LANE)
    buf = np.full((Rp, Dp), _minhash.PAD_VERSION, dtype=np.int32)
    buf[:R, :D] = versions_padded
    out = _pick("minhash", _minhash_twin)(
        jnp.asarray(buf), jnp.asarray(a), jnp.asarray(b))
    return np.asarray(out)[:, :R].T  # (R, L)


def minhash_csr(indptr: np.ndarray, col: np.ndarray, a: np.ndarray,
                b: np.ndarray, *, block_rows: int = 8192) -> np.ndarray:
    """Min-hash ragged CSR rows.

    Rows are processed in blocks; each block is padded to its own max degree
    (rounded to the 128-lane boundary and bucketed to powers of two to bound
    recompiles).  Returns (R, L) uint32; empty rows → 0xFFFFFFFF.
    """
    R = len(indptr) - 1
    L = len(a)
    out = np.empty((R, L), dtype=np.uint32)
    for lo in range(0, R, block_rows):
        hi = min(lo + block_rows, R)
        ptr = indptr[lo:hi + 1]
        deg = np.diff(ptr)
        dmax = int(deg.max()) if len(deg) else 0
        Dp = _P_LANE
        while Dp < dmax:
            Dp *= 2
        block = np.full((hi - lo, Dp), _minhash.PAD_VERSION, dtype=np.int32)
        # scatter CSR rows into the padded block
        rows = np.repeat(np.arange(hi - lo), deg)
        offs = np.arange(ptr[-1] - ptr[0]) - np.repeat(ptr[:-1] - ptr[0], deg)
        block[rows, offs] = col[ptr[0]:ptr[-1]]
        out[lo:hi] = minhash_padded(block, a, b)
    return out


# ---------------------------------------------------------------- xor delta
_xor_kernel = _compiled(_deltaenc.xor_delta)
_xor_twin = jax.jit(ref.xor_delta_ref)


def _bytes_to_words(buf: bytes, width: int) -> np.ndarray:
    arr = np.frombuffer(buf, dtype=np.uint8)
    pad = _pad_to(max(width, 4), 4)
    out = np.zeros(pad, dtype=np.uint8)
    out[:len(arr)] = arr
    return out.view(np.uint32)


def xor_delta_batch(parent: np.ndarray,
                    child: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """(N, W) uint32 batches → (delta (N, W), changed_words (N,)). Pads N."""
    N, W = parent.shape
    Np = _pad_to(max(N, 1), _deltaenc.BLOCK_N)
    Wp = _pad_to(max(W, 1), _P_LANE)
    pb = np.zeros((Np, Wp), dtype=np.uint32)
    cb = np.zeros((Np, Wp), dtype=np.uint32)
    pb[:N, :W] = parent
    cb[:N, :W] = child
    d, cnt = _pick("xor_delta", _xor_twin)(jnp.asarray(pb), jnp.asarray(cb))
    return np.asarray(d)[:N, :W], np.asarray(cnt)[:N]


def xor_delta_bytes(parent: bytes, child: bytes) -> Tuple[bytes, int]:
    """Delta-encode one payload against its parent (decode is the same call)."""
    w = max(len(parent), len(child))
    pw = _bytes_to_words(parent, w)
    cw = _bytes_to_words(child, w)
    d, cnt = xor_delta_batch(pw[None, :], cw[None, :])
    return d[0].tobytes()[:w], int(cnt[0])


# ------------------------------------------------------------------- bitmap
# Fused bitmap-plan launches since import ("and_popcount family": the
# pairwise AND kernel and the bitmap VM).  The planner's one-launch-per-batch
# contract is asserted against deltas of this counter.
BITMAP_LAUNCHES = 0


_and_kernel = _compiled(_bitmap.and_popcount)
_and_twin = jax.jit(ref.and_popcount_ref)


def and_popcount_batch(bitmaps: np.ndarray,
                       row: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """AND (N, W) bitmaps against a row; returns (anded, popcounts).

    ``row`` is a single shared (W,)/(1, W) bitmap (broadcast against every
    bitmap — the single-query index-AND) or a pairwise (N, W) batch (row i
    ANDs bitmaps[i] — one kernel launch plans a whole query session).
    """
    global BITMAP_LAUNCHES
    BITMAP_LAUNCHES += 1
    N, W = bitmaps.shape
    row = np.asarray(row)
    if row.ndim == 1:
        row = row[None, :]
    if row.shape not in ((1, W), (N, W)):
        raise ValueError(f"row must be ({W},), (1, {W}) or ({N}, {W}); "
                         f"got {row.shape}")
    pairwise = row.shape[0] == N and N != 1
    Np = _pad_to(max(N, 1), _bitmap.BLOCK_N)
    Wp = _pad_to(max(W, 1), _P_LANE)
    bb = np.zeros((Np, Wp), dtype=np.uint32)
    rb = np.zeros((Np if pairwise else 1, Wp), dtype=np.uint32)
    bb[:N, :W] = bitmaps
    rb[:row.shape[0], :W] = row
    anded, cnt = _pick("and_popcount", _and_twin)(jnp.asarray(bb),
                                                  jnp.asarray(rb))
    return np.asarray(anded)[:N, :W], np.asarray(cnt)[:N]


_vm_kernel = _compiled(_bitmap.bitmap_vm)
_vm_twin = jax.jit(ref.bitmap_vm_ref)


def bitmap_vm_batch(regs: np.ndarray,
                    prog: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Run one bitmap program over an (S, W) uint32 register file.

    ``prog`` is (P, 4) int32 ``(opcode, dst, lhs, rhs)`` rows (opcodes
    ``bitmap.OP_AND`` / ``OP_OR`` / ``OP_ANDNOT``); an empty program is
    legal and passes the registers through.  Pads S and W to the lane
    boundary and P to a multiple of 8 with OR-identity no-ops (``regs[0] =
    regs[0] | regs[0]``) to bound jit recompiles, then returns the final
    registers ``(S, W)`` and per-row popcounts ``(S,)`` unpadded.  One call
    = one fused launch, whatever the predicate-tree shape.
    """
    global BITMAP_LAUNCHES
    BITMAP_LAUNCHES += 1
    S, W = regs.shape
    prog = np.asarray(prog, dtype=np.int32).reshape(-1, 4)
    if len(prog) and (prog[:, 1:].min() < 0 or prog[:, 1:].max() >= S):
        raise ValueError(f"program row operand out of range [0, {S})")
    Sp = _pad_to(max(S, 1), _P_LANE)   # popcount output lane dim
    Wp = _pad_to(max(W, 1), _P_LANE)
    Pp = _pad_to(max(len(prog), 1), 8)
    rb = np.zeros((Sp, Wp), dtype=np.uint32)
    rb[:S, :W] = regs
    pg = np.zeros((Pp, 4), dtype=np.int32)
    pg[:, 0] = _bitmap.OP_OR           # no-op padding: regs[0] |= regs[0]
    pg[:len(prog)] = prog
    out, cnt = _pick("bitmap_vm", _vm_twin)(jnp.asarray(rb), jnp.asarray(pg))
    return np.asarray(out)[:S, :W], np.asarray(cnt)[:S]


# the compiled program behind each entry point's TPU path, by kernel name
KERNELS = {"minhash": _minhash_kernel, "xor_delta": _xor_kernel,
           "and_popcount": _and_kernel, "bitmap_vm": _vm_kernel}
