"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles,
swept over shapes, plus hypothesis property tests on the wrappers."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import bitmap as kbitmap
from repro.kernels import deltaenc as kdelta
from repro.kernels import minhash as kminhash
from repro.kernels import ops, ref


# ------------------------------------------------------------------ minhash
@pytest.mark.parametrize("R,D", [(128, 128), (256, 128), (128, 384), (512, 256)])
@pytest.mark.parametrize("L", [1, 4, 16])
def test_minhash_kernel_matches_ref(R, D, L):
    rng = np.random.default_rng(R * 1000 + D + L)
    vers = rng.integers(0, 10_000, size=(R, D)).astype(np.int32)
    vers[rng.random((R, D)) < 0.4] = -1
    a, b = ops.hash_family(L, seed=7)
    got = kminhash.minhash(jnp.asarray(vers), jnp.asarray(a), jnp.asarray(b),
                           interpret=True)
    want = ref.minhash_ref(jnp.asarray(vers), jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("base", [0x7FFFFFF0, 0x80000000, 0xFFFFFF00])
def test_minhash_kernel_high_hash_values(base):
    """Hashes at or above 0x80000000 (the kernel takes its min in the signed
    domain): rows straddle the sign bit, sit wholly above it, or wrap."""
    rng = np.random.default_rng(base % 1000)
    vers = rng.integers(0, 64, size=(256, 128)).astype(np.int32)
    vers[rng.random((256, 128)) < 0.3] = -1
    a = np.array([1, 3, 0x9E3779B1], dtype=np.uint32)
    b = np.full(3, base, dtype=np.uint32)
    got = kminhash.minhash(jnp.asarray(vers), jnp.asarray(a), jnp.asarray(b),
                           interpret=True)
    want = ref.minhash_ref(jnp.asarray(vers), jnp.asarray(a), jnp.asarray(b))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    hv = (a[:, None, None].astype(np.uint64) * vers[None].astype(np.uint64)
          + base) % 2**32
    assert (hv[:, vers >= 0] >= 0x80000000).any()


def _minhash_kernel_rows(rows: np.ndarray, a, b) -> np.ndarray:
    """The kernel (interpret=True) on rows padded to its tiles; (R, L)."""
    R, D = rows.shape
    buf = np.full((-(-R // kminhash.BLOCK_R) * kminhash.BLOCK_R,
                   -(-D // 128) * 128), kminhash.PAD_VERSION, dtype=np.int32)
    buf[:R, :D] = rows
    out = kminhash.minhash(jnp.asarray(buf), jnp.asarray(a), jnp.asarray(b),
                           interpret=True)
    return np.asarray(out)[:, :R].T


def test_minhash_empty_rows_are_maxval():
    """An all-padding row hashes to 0xFFFFFFFF, which the kernel carries
    through its signed-domain min as int32 max and maps back."""
    vers = np.full((128, 128), -1, dtype=np.int32)
    vers[::2, :5] = np.arange(5)            # empty rows beside filled ones
    a, b = ops.hash_family(3)
    for out in (_minhash_kernel_rows(vers, a, b),
                ops.minhash_padded(vers, a, b)):
        assert (out[1::2] == 0xFFFFFFFF).all()
        assert (out[::2] != 0xFFFFFFFF).all()


def test_minhash_is_permutation_invariant():
    """Min-hash of a set cannot depend on element order (the property the
    partitioner relies on), in the kernel and in the entry point."""
    rng = np.random.default_rng(0)
    row = rng.choice(5000, size=60, replace=False).astype(np.int32)
    a, b = ops.hash_family(8, 3)
    m1 = _minhash_kernel_rows(row[None, :], a, b)
    m2 = _minhash_kernel_rows(rng.permutation(row)[None, :], a, b)
    np.testing.assert_array_equal(m1, m2)
    np.testing.assert_array_equal(
        m1, ops.minhash_padded(rng.permutation(row)[None, :], a, b))


@given(st.lists(st.lists(st.integers(0, 2**20), min_size=0, max_size=40),
                min_size=1, max_size=20))
@settings(max_examples=25, deadline=None)
def test_minhash_csr_equals_python_min(rows):
    indptr = np.cumsum([0] + [len(r) for r in rows]).astype(np.int64)
    col = np.asarray([v for r in rows for v in r], dtype=np.int64)
    a, b = ops.hash_family(4, 1)
    got = ops.minhash_csr(indptr, col, a, b)
    for i, r in enumerate(rows):
        for l in range(4):
            if not r:
                assert got[i, l] == 0xFFFFFFFF
            else:
                want = min(((int(a[l]) * v + int(b[l])) & 0xFFFFFFFF) for v in set(r))
                assert got[i, l] == want


# ---------------------------------------------------------------- xor delta
@pytest.mark.parametrize("N,W", [(128, 128), (256, 256), (384, 512)])
def test_xor_delta_kernel_matches_ref(N, W):
    rng = np.random.default_rng(N + W)
    p = rng.integers(0, 2**32, size=(N, W), dtype=np.uint32)
    c = rng.integers(0, 2**32, size=(N, W), dtype=np.uint32)
    d, cnt = kdelta.xor_delta(jnp.asarray(p), jnp.asarray(c), interpret=True)
    dr, cr = ref.xor_delta_ref(jnp.asarray(p), jnp.asarray(c))
    np.testing.assert_array_equal(np.asarray(d), np.asarray(dr))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cr))


@pytest.mark.parametrize("N,W,block_w", [(128, 384, 128), (256, 1024, 256)])
def test_xor_delta_kernel_multi_block_matches_ref(N, W, block_w, monkeypatch):
    """Tiled along W: changed-word counts add up across the lane blocks."""
    monkeypatch.setattr(kdelta, "_TILE_BYTES", kdelta.BLOCK_N * block_w * 4)
    rng = np.random.default_rng(N + W + block_w)
    p = rng.integers(0, 2**32, size=(N, W), dtype=np.uint32)
    c = p.copy()
    c[rng.random((N, W)) < 0.3] ^= np.uint32(1)
    d, cnt = kdelta.xor_delta(jnp.asarray(p), jnp.asarray(c), interpret=True)
    dr, cr = ref.xor_delta_ref(jnp.asarray(p), jnp.asarray(c))
    np.testing.assert_array_equal(np.asarray(d), np.asarray(dr))
    np.testing.assert_array_equal(np.asarray(cnt), np.asarray(cr))


@given(st.binary(min_size=0, max_size=300), st.binary(min_size=0, max_size=300))
@settings(max_examples=50, deadline=None)
def test_xor_delta_bytes_roundtrip(parent, child):
    """decode(parent, encode(parent, child)) == child — the §3.4 invariant."""
    w = max(len(parent), len(child))
    delta, _ = ops.xor_delta_bytes(parent.ljust(w, b"\0"), child.ljust(w, b"\0"))
    back, _ = ops.xor_delta_bytes(parent.ljust(w, b"\0"), delta)
    assert back[:len(child)] == child
    assert all(x == 0 for x in back[len(child):])


def test_xor_delta_identical_is_zero():
    p = np.arange(256 * 128, dtype=np.uint32).reshape(256, 128)
    d, cnt = ops.xor_delta_batch(p, p)
    assert (d == 0).all() and (cnt == 0).all()


# ------------------------------------------------------------------- bitmap
@pytest.mark.parametrize("N,W", [(128, 128), (256, 256)])
def test_bitmap_kernel_matches_ref(N, W):
    rng = np.random.default_rng(N * 7 + W)
    bms = rng.integers(0, 2**32, size=(N, W), dtype=np.uint32)
    row = rng.integers(0, 2**32, size=(1, W), dtype=np.uint32)
    a1, c1 = kbitmap.and_popcount(jnp.asarray(bms), jnp.asarray(row), interpret=True)
    a2, c2 = ref.and_popcount_ref(jnp.asarray(bms), jnp.asarray(row))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


@given(st.integers(1, 64), st.integers(1, 33), st.integers(0, 2**31))
@settings(max_examples=30, deadline=None)
def test_bitmap_popcount_exact(n, w, seed):
    rng = np.random.default_rng(seed)
    bms = rng.integers(0, 2**32, size=(n, w), dtype=np.uint32)
    row = rng.integers(0, 2**32, size=w, dtype=np.uint32)
    anded, cnt = ops.and_popcount_batch(bms, row)
    want = np.array([sum(bin(int(x)).count("1") for x in r) for r in bms & row])
    np.testing.assert_array_equal(anded, bms & row)
    np.testing.assert_array_equal(cnt, want)


# ---------------------------------------------------------------- bitmap VM
def _vm_oracle(regs: np.ndarray, prog: np.ndarray):
    """Plain-python simulation of the bitmap VM (independent of ref.py)."""
    r = regs.copy()
    for op, dst, lhs, rhs in np.asarray(prog, dtype=np.int64).reshape(-1, 4):
        a, b = r[lhs], r[rhs]
        r[dst] = (a & b if op == kbitmap.OP_AND
                  else a | b if op == kbitmap.OP_OR else a & ~b)
    cnt = np.array([sum(bin(int(x)).count("1") for x in row) for row in r])
    return r, cnt


def _random_prog(rng, S: int, P: int) -> np.ndarray:
    prog = np.empty((P, 4), dtype=np.int32)
    prog[:, 0] = rng.integers(0, 3, size=P)
    prog[:, 1:] = rng.integers(0, S, size=(P, 3))
    return prog


@pytest.mark.parametrize("S,W,P", [(128, 128, 8), (128, 256, 32), (256, 128, 1)])
def test_bitmap_vm_kernel_matches_ref(S, W, P):
    rng = np.random.default_rng(S * 13 + W + P)
    regs = rng.integers(0, 2**32, size=(S, W), dtype=np.uint32)
    prog = _random_prog(rng, S, P)
    o1, c1 = kbitmap.bitmap_vm(jnp.asarray(regs), jnp.asarray(prog),
                               interpret=True)
    o2, c2 = ref.bitmap_vm_ref(jnp.asarray(regs), jnp.asarray(prog))
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


@pytest.mark.parametrize("S,W,P,block_w", [(128, 384, 16, 128),
                                            (256, 512, 40, 256)])
def test_bitmap_vm_kernel_multi_block_matches_ref(S, W, P, block_w,
                                                  monkeypatch):
    """Tiled along W: every lane block runs the whole program and the
    popcounts add up across blocks."""
    monkeypatch.setattr(kbitmap, "_VM_TILE_BYTES", S * block_w * 4)
    rng = np.random.default_rng(S + W + P + block_w)
    regs = rng.integers(0, 2**32, size=(S, W), dtype=np.uint32)
    prog = _random_prog(rng, S, P)
    o1, c1 = kbitmap.bitmap_vm(jnp.asarray(regs), jnp.asarray(prog),
                               interpret=True)
    o2, c2 = ref.bitmap_vm_ref(jnp.asarray(regs), jnp.asarray(prog))
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o2))
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))


@pytest.mark.parametrize("width,rows,budget,want", [
    (128, 4096, 1, 128),            # never below one lane tile
    (8192, 256, 2 << 20, 2048),
    (384, 8, 2 << 20, 384),
    (1152, 128, 256 << 10, 384),    # widest divisor within budget
])
def test_lane_block(width, rows, budget, want):
    assert kbitmap._lane_block(width, rows, budget) == want


@pytest.mark.parametrize("op", [kbitmap.OP_AND, kbitmap.OP_OR, kbitmap.OP_ANDNOT])
def test_bitmap_vm_each_op_exact(op):
    rng = np.random.default_rng(40 + op)
    regs = rng.integers(0, 2**32, size=(4, 9), dtype=np.uint32)
    prog = np.array([[op, 3, 0, 1]], dtype=np.int32)
    out, cnt = ops.bitmap_vm_batch(regs, prog)
    want, wcnt = _vm_oracle(regs, prog)
    np.testing.assert_array_equal(out, want)
    np.testing.assert_array_equal(cnt, wcnt)


def test_bitmap_vm_empty_program_passes_through():
    rng = np.random.default_rng(3)
    regs = rng.integers(0, 2**32, size=(5, 7), dtype=np.uint32)
    out, cnt = ops.bitmap_vm_batch(regs, np.zeros((0, 4), dtype=np.int32))
    np.testing.assert_array_equal(out, regs)
    want = np.array([sum(bin(int(x)).count("1") for x in r) for r in regs])
    np.testing.assert_array_equal(cnt, want)
    # kernel-level empty program too (the P == 0 short-circuit)
    o, c = kbitmap.bitmap_vm(jnp.asarray(regs), jnp.zeros((0, 4), jnp.int32))
    np.testing.assert_array_equal(np.asarray(o), regs)
    np.testing.assert_array_equal(np.asarray(c), want)


def test_bitmap_vm_all_zero_bitmaps():
    regs = np.zeros((6, 11), dtype=np.uint32)
    prog = np.array([[kbitmap.OP_OR, 4, 0, 1],
                     [kbitmap.OP_ANDNOT, 5, 2, 3]], dtype=np.int32)
    out, cnt = ops.bitmap_vm_batch(regs, prog)
    assert (out == 0).all() and (cnt == 0).all()


@given(st.integers(2, 24), st.integers(1, 17), st.integers(0, 12),
       st.integers(0, 2**31))
@settings(max_examples=30, deadline=None)
def test_bitmap_vm_property_matches_oracle(s, w, p, seed):
    rng = np.random.default_rng(seed)
    regs = rng.integers(0, 2**32, size=(s, w), dtype=np.uint32)
    prog = _random_prog(rng, s, p)
    out, cnt = ops.bitmap_vm_batch(regs, prog)
    want, wcnt = _vm_oracle(regs, prog)
    np.testing.assert_array_equal(out, want)
    np.testing.assert_array_equal(cnt, wcnt)


def test_bitmap_vm_operand_out_of_range_raises():
    regs = np.zeros((4, 4), dtype=np.uint32)
    with pytest.raises(ValueError, match="out of range"):
        ops.bitmap_vm_batch(regs, np.array([[0, 4, 0, 1]], dtype=np.int32))
    with pytest.raises(ValueError, match="out of range"):
        ops.bitmap_vm_batch(regs, np.array([[0, 0, -1, 1]], dtype=np.int32))


def test_bitmap_vm_counts_one_launch():
    regs = np.ones((3, 3), dtype=np.uint32)
    before = ops.BITMAP_LAUNCHES
    ops.bitmap_vm_batch(regs, np.zeros((0, 4), dtype=np.int32))
    assert ops.BITMAP_LAUNCHES - before == 1


def test_cpu_runs_twins_and_other_platforms_raise(monkeypatch):
    """On the CPU the entry points run the jnp twins and launch no compiled
    kernel; on a platform with neither path they raise."""
    before = dict(ops.KERNEL_LAUNCHES)
    ops.bitmap_vm_batch(np.ones((3, 3), dtype=np.uint32),
                        np.zeros((0, 4), dtype=np.int32))
    ops.xor_delta_bytes(b"abcd", b"abce")
    assert dict(ops.KERNEL_LAUNCHES) == before
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="no kernel path"):
        ops.xor_delta_bytes(b"abcd", b"abce")
