"""Pallas TPU kernel: min-hash shingle computation (§3.1).

The SHINGLE partitioner's dominant cost is computing, for every record, ``L``
min-hashes over the set of versions the record belongs to (millions of
records × dozens of hash lanes).  TPU adaptation: version lists are padded
into ``(R, D)`` int32 tiles (CSR rows padded with -1); the kernel streams
``(BLOCK_R, D)`` tiles through VMEM, evaluates the multiply-shift universal
hash ``h_l(v) = a_l · v + b_l  (mod 2^32)`` on the VPU for each lane, and
takes a masked row-min.  Output is laid out ``(L, R)`` so the record axis
rides the 128-wide lane dimension.

Working set per grid step: BLOCK_R·D·4 bytes (≤1 MiB for D ≤ 2048) — well
under VMEM.  BLOCK_R = 128 keeps both tile axes hardware-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

BLOCK_R = 128
PAD_VERSION = -1
_EMPTY_HASH = np.uint32(0xFFFFFFFF)
_SIGN = np.uint32(0x80000000)


def _minhash_kernel(vers_ref, a_ref, b_ref, out_ref, *, n_hashes: int):
    v = vers_ref[...]                                  # (BLOCK_R, D) int32
    valid = v != PAD_VERSION
    vu = v.astype(jnp.uint32)
    for l in range(n_hashes):                          # static unroll over lanes
        a = a_ref[0, l]
        b = b_ref[0, l]
        hv = a * vu + b                                # uint32 wraparound hash
        hv = jnp.where(valid, hv, _EMPTY_HASH)
        # the TPU has no unsigned min-reduction: flipping the sign bit maps
        # uint32 order onto int32 order, so take the min there and map back
        hs = jax.lax.bitcast_convert_type(hv ^ _SIGN, jnp.int32)
        m = jax.lax.bitcast_convert_type(jnp.min(hs, axis=1), jnp.uint32)
        out_ref[l, :] = m ^ _SIGN


def minhash(versions_padded: jax.Array, a: jax.Array, b: jax.Array,
            *, interpret: bool = True) -> jax.Array:
    """Min-hash each padded row.

    Args:
      versions_padded: (R, D) int32, rows padded with -1.  R % 128 == 0,
        D % 128 == 0 (callers pad; see ops.minhash_csr).
      a, b: (L,) uint32 hash-family parameters (a odd).
    Returns:
      (L, R) uint32 min-hash values; empty rows yield 0xFFFFFFFF.
    """
    R, D = versions_padded.shape
    L = a.shape[0]
    if R % BLOCK_R:
        raise ValueError(f"R={R} must be a multiple of {BLOCK_R}")
    a2 = a.reshape(1, L)
    b2 = b.reshape(1, L)
    grid = (R // BLOCK_R,)
    return pl.pallas_call(
        functools.partial(_minhash_kernel, n_hashes=L),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_R, D), lambda i: (i, 0)),
            pl.BlockSpec((1, L), lambda i: (0, 0)),
            pl.BlockSpec((1, L), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((L, BLOCK_R), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((L, R), jnp.uint32),
        interpret=interpret,
    )(versions_padded, a2, b2)
