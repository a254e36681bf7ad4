"""Compile the serve path's kernels and its fused decode block for a
described TPU v5e chip, with no chip attached.

The TPU compiler refuses what Pallas interpret mode accepts (block shapes
not aligned to the tiling, kernels over the VMEM budget, programs over
the device's memory), so these compiles guard every change to the main
path at its real widths: yi-9b's (``config.one_chip_config``).  Nothing
runs; results and times come only from a run on the chip
(``chip_smoke.py``).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import one_chip_config
from repro.kernels import flash_attention, paged_decode
from repro.launch.compiles import has_kernel
from repro.models import model as M

NUM_SLOTS, MAX_LEN, PAGE_SIZE, K_BLOCK = 8, 2048, 16, 8
HBM_BYTES = 16e9            # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    # only an installation without the TPU compiler skips; any other
    # failure to describe the chip fails the tests that need it
    pytest.importorskip("libtpu", reason="no TPU compiler installed")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(tree, sharding):
    return jax.tree.map(lambda s: _spec(s.shape, s.dtype, sharding), tree)


def test_paged_decode_compiles_at_yi9b_widths(one_chip):
    cfg = one_chip_config()
    H, Hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    maxp = MAX_LEN // PAGE_SIZE
    pool = (NUM_SLOTS * maxp + 1, PAGE_SIZE, Hkv, dh)        # + scratch page
    fn = jax.jit(functools.partial(paged_decode.paged_decode_partial,
                                   interpret=False))
    compiled = fn.lower(
        _spec((NUM_SLOTS, H, dh), jnp.bfloat16, one_chip),
        _spec(pool, jnp.bfloat16, one_chip),
        _spec(pool, jnp.bfloat16, one_chip),
        _spec((NUM_SLOTS, maxp), jnp.int32, one_chip),
        _spec((NUM_SLOTS,), jnp.int32, one_chip)).compile()
    assert has_kernel(compiled.as_text(), "paged_decode")
    acc, l, m = compiled.out_info
    assert acc.shape == (NUM_SLOTS, H, dh)
    assert l.shape == m.shape == (NUM_SLOTS, H)


@pytest.mark.parametrize("sq", [32, 512])
def test_flash_attention_compiles_at_yi9b_widths(one_chip, sq):
    cfg = one_chip_config()
    H, Hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    fn = jax.jit(functools.partial(flash_attention.flash_attention,
                                   causal=True, interpret=False))
    compiled = fn.lower(
        _spec((NUM_SLOTS, sq, H, dh), jnp.bfloat16, one_chip),
        _spec((NUM_SLOTS, sq, Hkv, dh), jnp.bfloat16, one_chip),
        _spec((NUM_SLOTS, sq, Hkv, dh), jnp.bfloat16, one_chip)).compile()
    assert has_kernel(compiled.as_text(), "flash_attention")


def test_decode_block_fits_one_chip(one_chip, monkeypatch):
    """The chip smoke's fused K-block decode program: the Pallas paged
    kernel is in it, and its arguments (weights + KV pool + slot state)
    plus temporaries fit one chip's HBM."""
    # kernels/ops.py picks the Pallas path by the default backend, which is
    # the CPU here; the program is compiled for the TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = one_chip_config()
    params = _on(M.abstract_params(cfg), one_chip)
    caches = _on(M.abstract_caches(cfg, NUM_SLOTS, MAX_LEN, paged=True,
                                   page_size=PAGE_SIZE), one_chip)
    i32 = functools.partial(_spec, (NUM_SLOTS,), jnp.int32, one_chip)
    fn = jax.jit(lambda p, c, t, pos, alive, rem: M.decode_block_fn(
        p, c, t, pos, alive, rem, cfg, k_steps=K_BLOCK, eos_id=None,
        max_len=MAX_LEN), donate_argnums=(1, 2, 3, 4, 5))
    compiled = fn.lower(params, caches, i32(), i32(),
                        _spec((NUM_SLOTS,), jnp.bool_, one_chip),
                        i32()).compile()
    assert has_kernel(compiled.as_text(), "paged_decode")
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, (mem.argument_size_in_bytes,
                              mem.temp_size_in_bytes)
