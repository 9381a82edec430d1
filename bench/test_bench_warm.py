"""The set-up run of every bitmap-VM shape, and the window's cache switch."""
import jax
import jax.numpy as jnp

import warm


def test_bitmap_shapes_cover_a_full_wave():
    shapes = warm.bitmap_shapes([3800], 64)
    assert {w for _, w, _ in shapes} == {128}
    assert {s for s, _, _ in shapes} == {128, 256, 384}
    assert max(p for _, _, p in shapes) == 136
    assert all(p <= s and p % 8 == 0 for s, _, p in shapes)
    assert {w for _, w, _ in warm.bitmap_shapes([3800, 3800 + 4096], 64)} \
        == {128, 256}


def test_cache_off_turns_the_persistent_cache_off_and_back(tmp_path):
    from jax._src import compilation_cache as cc
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_enable_compilation_cache)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    try:
        f = jax.jit(lambda x: x * 3 + 1)
        with warm.cache_off():
            f(jnp.ones(7)).block_until_ready()
            assert not cc.is_cache_used(jax.devices()[0].client)
        assert jax.config.jax_enable_compilation_cache
        f(jnp.ones(9)).block_until_ready()
        assert cc.is_cache_used(jax.devices()[0].client)
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_enable_compilation_cache", before[1])
        cc.reset_cache()
