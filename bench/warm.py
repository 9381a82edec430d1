"""Set-up that keeps compiles of the bitmap VM out of the window, and the
switch that keeps the persistent compilation cache out of it.

The store compiles its bitmap-VM kernel once for each padded register-file
shape (``kernels/ops.py``): a wave of ``wave_max`` queries can build a
few dozen.  Set-up runs the program's own compiled kernel once on each of
them, so that they are in the process's jit cache (and, after the first
run, loaded from the persistent cache) before the window opens.

The device-table gather compiles once for every index length
(``core/kvs.py``; ROADMAP S4), and a table can be asked for thousands of
lengths: compiling them all would take longer than a run.  So the window
meets new lengths, and compiles them.  To keep that cost the same in every
run, whatever earlier runs left in the persistent cache, the window runs
with the persistent cache switched off (``cache_off``): a length the
process has not met is compiled, not loaded.
"""
from __future__ import annotations

import contextlib
from typing import Iterable, List, Tuple

LANE = 128


def _pad(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def bitmap_shapes(n_chunks: Iterable[int], wave_max: int
                  ) -> List[Tuple[int, int, int]]:
    """``(S, W, P)`` register-file and program shapes, padded as
    ``ops.bitmap_vm_batch`` pads them, for waves of up to ``wave_max``
    queries over stores of ``n_chunks`` chunks: at most five register rows
    and two instructions per query."""
    ws = sorted({_pad(max((n + 31) // 32, 1), LANE) for n in n_chunks})
    s_max, p_max = _pad(5 * wave_max, LANE), _pad(2 * wave_max + 8, 8)
    return [(s, w, p) for w in ws for s in range(LANE, s_max + 1, LANE)
            for p in range(8, min(p_max, s) + 1, 8)]


def warm_bitmap(n_chunks: Iterable[int], wave_max: int) -> int:
    """Run the compiled bitmap VM once on every shape; returns how many."""
    import jax.numpy as jnp
    from repro.kernels import ops
    vm = ops.KERNELS["bitmap_vm"]
    shapes = bitmap_shapes(n_chunks, wave_max)
    for s, w, p in shapes:
        prog = jnp.zeros((p, 4), jnp.int32).at[:, 0].set(1)   # OR no-ops
        for out in vm(jnp.zeros((s, w), jnp.uint32), prog):
            out.block_until_ready()
    return len(shapes)


@contextlib.contextmanager
def cache_off():
    """JAX's persistent compilation cache switched off, and back on."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
