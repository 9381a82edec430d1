"""Pallas TPU kernel: XOR-delta record encoding (§3.4 record-level compression).

Sub-chunk compression delta-encodes each record against its version-tree
parent.  For fixed-width payloads (the framework's checkpoint blocks and the
paper's equal-sized JSON records) the delta is a word-wise XOR — zero words
mark unchanged bytes, which downstream entropy coding (zlib on host) or
sparse encoding exploits.  The same kernel powers gradient/update compression
in ``train/grad_compress.py``.

Layout: payloads as (N, W) uint32 words.  Grid streams (BLOCK_N, block_w)
tiles through VMEM (a 64 KiB record is W = 16384 words, too wide for one
tile); outputs the XOR tile plus a per-record changed-word count laid out
(1, N) so the record axis rides the lane dimension, summed over the W blocks.  Decode is the same
XOR (an involution), so one kernel serves both directions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bitmap import _lane_block

BLOCK_N = 128


# VMEM budget for one (BLOCK_N, block_w) tile; parent, child and delta
# tiles are each double-buffered, so the kernel holds ~6x this
_TILE_BYTES = 1 << 20


def _xor_delta_kernel(parent_ref, child_ref, delta_ref, count_ref):
    d = parent_ref[...] ^ child_ref[...]   # (BLOCK_N, block_w) uint32
    delta_ref[...] = d

    @pl.when(pl.program_id(1) == 0)
    def _():
        count_ref[...] = jnp.zeros_like(count_ref)

    count_ref[0, :] += jnp.sum((d != 0).astype(jnp.int32), axis=1)


def xor_delta(parent: jax.Array, child: jax.Array,
              *, interpret: bool = True) -> tuple[jax.Array, jax.Array]:
    """XOR-delta encode (or decode) fixed-width payloads.

    Args:
      parent, child: (N, W) uint32; N % 128 == 0, W % 128 == 0 (callers pad).
    Returns:
      (delta (N, W) uint32, changed_words (N,) int32).
    """
    N, W = parent.shape
    if parent.shape != child.shape:
        raise ValueError("parent/child shape mismatch")
    if N % BLOCK_N or W % 128:
        raise ValueError(f"N={N} and W={W} must be multiples of 128")
    bw = _lane_block(W, BLOCK_N, _TILE_BYTES)
    tile = pl.BlockSpec((BLOCK_N, bw), lambda i, j: (i, j))
    delta, counts = pl.pallas_call(
        _xor_delta_kernel,
        grid=(N // BLOCK_N, W // bw),
        in_specs=[tile, tile],
        out_specs=[
            tile,
            pl.BlockSpec((1, BLOCK_N), lambda i, j: (0, i)),  # summed over j
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, W), jnp.uint32),
            jax.ShapeDtypeStruct((1, N), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(parent, child)
    return delta, counts[0]
