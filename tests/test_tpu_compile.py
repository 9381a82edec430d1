"""Compile the store's device programs for a described TPU v5e at real widths.

Nothing runs: each program is lowered and compiled for one chip of a
``v5e:2x2`` topology that is described, not attached, so the TPU compiler
refuses here what it would refuse on the chip (unaligned tiles, too much
VMEM, a program larger than device memory).  The topology is described
inside a fixture, never at import, so every test worker collects the same
tests and only the worker running this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import bitmap as kbitmap
from repro.kernels import deltaenc as kdelta
from repro.kernels import minhash as kminhash


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep these compiles out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_bitmap_vm_compiles(one_chip):
    # W = 8192 words: a 16 GB table of 64 KiB chunks (262,144 chunks)
    c = _compile(lambda r, p: kbitmap.bitmap_vm(r, p, interpret=False),
                 _sds((256, 8192), jnp.uint32, one_chip),
                 _sds((128, 4), jnp.int32, one_chip))
    assert "tpu_custom_call" in c.as_text()


def test_xor_delta_compiles(one_chip):
    # W = 16384 words: one 64 KiB record, the default chunk capacity
    c = _compile(lambda p, q: kdelta.xor_delta(p, q, interpret=False),
                 _sds((128, 16384), jnp.uint32, one_chip),
                 _sds((128, 16384), jnp.uint32, one_chip))
    assert "tpu_custom_call" in c.as_text()


def test_minhash_compiles(one_chip):
    c = _compile(lambda v, a, b: kminhash.minhash(v, a, b, interpret=False),
                 _sds((8192, 256), jnp.int32, one_chip),
                 _sds((8,), jnp.uint32, one_chip),
                 _sds((8,), jnp.uint32, one_chip))
    assert "tpu_custom_call" in c.as_text()


def test_device_table_gather_compiles(one_chip):
    # the ShardedDeviceKVS multiget's largest block over a 1 GiB uint32 slot
    # table: its output, the most a gather holds on the device, is 16 MiB
    from repro.core import kvs
    slot_words = (1 << 16) // 4
    n_slots = (1 << 30) // (1 << 16)
    block = kvs.GATHER_BLOCK_ROWS
    c = _compile(kvs.gather_rows,
                 _sds((n_slots, slot_words), jnp.uint32, one_chip),
                 _sds((block,), jnp.int32, one_chip))
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes >= 1 << 30
    assert mem.output_size_in_bytes == block * (1 << 16) == 16 << 20


@pytest.mark.parametrize("name,shapes,params", [
    ("bitmap_vm", [((128, 128), jnp.uint32), ((8, 4), jnp.int32)],
     ("regs", "prog")),
    ("xor_delta", [((128, 128), jnp.uint32), ((128, 128), jnp.uint32)],
     ("parent", "child")),
])
def test_kernel_programs_are_named_by_kernel(one_chip, name, shapes, params):
    # the trace names a program by its module and an op's operands by the
    # kernel's parameters (the XOR-delta kernel is found by its operands)
    from repro.kernels import ops
    low = ops.KERNELS[name].lower(*(_sds(s, d, one_chip) for s, d in shapes))
    assert f"module @jit_{name} " in low.as_text()
    hlo = low.as_text(dialect="hlo")
    assert all(f"{p}.1 = " in hlo for p in params)


def test_device_table_gather_is_named(one_chip):
    from repro.core import ShardedDeviceKVS
    kvs = ShardedDeviceKVS(slot_bytes=512, n_slots=8)
    low = kvs._gather.lower(_sds((8, 128), jnp.uint32, one_chip),
                            _sds((4,), jnp.int32, one_chip))
    assert "module @jit_gather_rows " in low.as_text()
