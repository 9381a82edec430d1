"""Pallas TPU kernels: bitmap AND + popcount, and the bitmap VM (§2.4).

Record/range retrieval intersects the two lossy projections (key→chunks and
version→chunks).  With chunk membership as bitmaps (1 bit per chunk), the
intersection is a bitwise AND and the candidate count a popcount.  The
``and_popcount`` kernel ANDs a batch of key bitmaps (N, W) against either one
shared version bitmap (1, W) held in VMEM across the whole grid (single-query
index-ANDing) or a per-row batch of version bitmaps (N, W) tiled with the
keys (the plan/execute engine's batched sessions: row i carries query i's
version bitmap), emitting the AND tiles plus per-row popcounts.

Composite predicates (``Q.and_``/``Q.or_``/``Q.not_`` trees planned by
``core/plan.py``) need more than one pairwise AND, so ``bitmap_vm`` runs a
small *bitmap program*: an (S, W) uint32 register file (leaf rows — OR'd
posting lists and version bitmaps — followed by zeroed instruction outputs)
and a (P, 4) int32 instruction stream ``(opcode, dst, lhs, rhs)`` with
opcodes AND / OR / ANDNOT.  Instructions execute in order (``regs[dst] =
op(regs[lhs], regs[rhs])``), so an arbitrary predicate tree over projection
and secondary-index bitmaps evaluates in ONE fused launch; the final
register file and per-row popcounts come back together.  An empty program
passes the register file through unchanged.  The instruction stream lives in
SMEM (scalar memory) — its fields drive dynamic row indexing into the VMEM
register file.  Bitmap columns are independent, so the register file is
tiled along W: each grid step runs the whole program over one lane block and
the per-row popcounts accumulate across blocks.

Popcount uses the SWAR bit-twiddle (no LUT: TPU VPU has no gather), entirely
in uint32 lanes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_N = 128

# bitmap-VM opcodes (prog[:, 0])
OP_AND = 0
OP_OR = 1
OP_ANDNOT = 2


def _popcount32(v: jax.Array) -> jax.Array:
    v = v - ((v >> 1) & np.uint32(0x55555555))
    v = (v & np.uint32(0x33333333)) + ((v >> 2) & np.uint32(0x33333333))
    v = (v + (v >> 4)) & np.uint32(0x0F0F0F0F)
    return (v * np.uint32(0x01010101)) >> 24


def _and_popcount_kernel(bms_ref, row_ref, out_ref, cnt_ref):
    # (BLOCK_N, W) & (1, W) broadcasts; & (BLOCK_N, W) is elementwise
    x = bms_ref[...] & row_ref[...]
    out_ref[...] = x
    cnt_ref[0, :] = jnp.sum(_popcount32(x).astype(jnp.int32), axis=1)


def and_popcount(bitmaps: jax.Array, row: jax.Array,
                 *, interpret: bool = True) -> tuple[jax.Array, jax.Array]:
    """AND a batch of bitmaps against one shared row or per-row bitmaps.

    Args:
      bitmaps: (N, W) uint32, N % 128 == 0.
      row: (1, W) uint32 (broadcast against every row) or (N, W) uint32
        (pairwise: row i ANDs bitmaps[i] — the batched-session plan path).
    Returns:
      (anded (N, W) uint32, popcounts (N,) int32).
    """
    N, W = bitmaps.shape
    if row.shape not in ((1, W), (N, W)):
        raise ValueError(f"row must be (1, {W}) or ({N}, {W}), got {row.shape}")
    pairwise = row.shape[0] == N and N != 1
    if N % BLOCK_N:
        raise ValueError(f"N={N} must be a multiple of {BLOCK_N}")
    grid = (N // BLOCK_N,)
    row_spec = (pl.BlockSpec((BLOCK_N, W), lambda i: (i, 0)) if pairwise
                else pl.BlockSpec((1, W), lambda i: (0, 0)))
    anded, counts = pl.pallas_call(
        _and_popcount_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_N, W), lambda i: (i, 0)),
            row_spec,
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_N, W), lambda i: (i, 0)),
            pl.BlockSpec((1, BLOCK_N), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, W), jnp.uint32),
            jax.ShapeDtypeStruct((1, N), jnp.int32),
        ],
        interpret=interpret,
    )(bitmaps, row)
    return anded, counts[0]


# ------------------------------------------------------------------ bitmap VM
# VMEM budget for one (S, block_w) register-file tile; the input and output
# tiles are each double-buffered, so the kernel holds ~4x this
_VM_TILE_BYTES = 2 << 20


def _lane_block(width: int, rows: int, tile_bytes: int) -> int:
    """Widest multiple of 128 lanes that divides ``width`` and keeps a
    ``(rows, block)`` uint32 tile within ``tile_bytes`` (at least 128)."""
    best = 128
    for b in range(128, width + 1, 128):
        if width % b == 0 and rows * b * 4 <= tile_bytes:
            best = b
    return best


def _bitmap_vm_kernel(prog_ref, regs_ref, out_ref, cnt_ref):
    # one lane block of the register file: copy it, then execute the whole
    # program in place; every instruction reads/writes (1, block_w) rows at
    # dynamic (SMEM-sourced) sublane offsets.  Columns are independent, so
    # each block runs the same program and popcounts add up across blocks.
    out_ref[...] = regs_ref[...]

    def body(i, carry):
        op = prog_ref[i, 0]
        dst = prog_ref[i, 1]
        a = out_ref[pl.ds(prog_ref[i, 2], 1), :]
        b = out_ref[pl.ds(prog_ref[i, 3], 1), :]
        out_ref[pl.ds(dst, 1), :] = jnp.where(
            op == OP_AND, a & b, jnp.where(op == OP_OR, a | b, a & ~b))
        return carry

    jax.lax.fori_loop(0, prog_ref.shape[0], body, 0)

    @pl.when(pl.program_id(0) == 0)
    def _():
        cnt_ref[...] = jnp.zeros_like(cnt_ref)

    cnt_ref[0, :] += jnp.sum(_popcount32(out_ref[...]).astype(jnp.int32),
                             axis=1)


def bitmap_vm(regs: jax.Array, prog: jax.Array,
              *, interpret: bool = True) -> tuple[jax.Array, jax.Array]:
    """Execute a bitmap program over an (S, W) uint32 register file.

    Args:
      regs: (S, W) uint32 register file (leaf bitmaps + zeroed scratch rows),
        S % 128 == 0 and W % 128 == 0 (callers pad).
      prog: (P, 4) int32 instructions ``(opcode, dst, lhs, rhs)`` with
        opcode in {OP_AND, OP_OR, OP_ANDNOT} and row operands in [0, S).
        P == 0 is the empty program (register file passes through).
    Returns:
      (final registers (S, W) uint32, per-row popcounts (S,) int32).
    """
    S, W = regs.shape
    P = prog.shape[0]
    if prog.ndim != 2 or prog.shape[1] != 4:
        raise ValueError(f"prog must be (P, 4) int32, got {prog.shape}")
    if P == 0:
        # nothing to execute — popcount-only; keeps the kernel's loop bounds
        # static and the empty-program contract explicit
        counts = jnp.sum(_popcount32(regs).astype(jnp.int32), axis=1)
        return regs, counts
    if W % 128:
        raise ValueError(f"W={W} must be a multiple of 128")
    bw = _lane_block(W, S, _VM_TILE_BYTES)
    out, counts = pl.pallas_call(
        _bitmap_vm_kernel,
        grid=(W // bw,),
        in_specs=[
            pl.BlockSpec((P, 4), lambda j: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((S, bw), lambda j: (0, j)),
        ],
        out_specs=[
            pl.BlockSpec((S, bw), lambda j: (0, j)),
            pl.BlockSpec((1, S), lambda j: (0, 0)),     # accumulated over j
        ],
        out_shape=[
            jax.ShapeDtypeStruct((S, W), jnp.uint32),
            jax.ShapeDtypeStruct((1, S), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(prog, regs)
    return out, counts[0]
