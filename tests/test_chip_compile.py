"""Ahead-of-time compiles of the decode-path Pallas kernels for a TPU v5e.

Interpret mode cannot see what the chip's compiler (Mosaic) refuses:
blocks whose last two dims are neither (8, 128)-aligned nor the array's
own, vector loads from SMEM, shape casts it has no layout for.  These
tests compile each kernel at qwen1.5-0.5b decode widths (B=8 slots, 16
MHA kv-heads, d_head 64, 16-token pages, 128 pages per slot, a 1024-page
shared pool) for a v5e chip that is described, not attached: nothing
runs, no chip is needed, each compile takes about a second.

The topology is described inside a module-scoped fixture (never at
import), so every pytest-xdist worker collects the same tests and only
the worker that runs this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.paged_attention.kernel import (
    paged_attention_pallas, paged_attention_pallas_shared)

B, K, G, DH, T, NP, POOL = 8, 16, 1, 64, 16, 128, 1024


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described v5e:2x2, with the persistent compile
    cache off: an entry compiled for a described chip cannot be read
    back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile_hlo(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _pool_page(kv_quant, lead, sd):
    """Abstract page pool [*lead, T(/2), dh] in the format's storage dtype."""
    dtype, rows = {"none": (jnp.bfloat16, T), "kv8": (jnp.int8, T),
                   "kv4": (jnp.uint8, T // 2)}[kv_quant]
    return jax.ShapeDtypeStruct(lead + (rows, DH), dtype, sharding=sd)


@pytest.mark.parametrize("layout,kv_quant,partitions", [
    ("striped", "none", 1), ("striped", "kv8", 1), ("striped", "kv4", 1),
    ("striped", "none", 16),
    ("shared", "none", 1), ("shared", "kv8", 1), ("shared", "kv4", 1),
    ("shared", "kv8", 16),
])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, layout, kv_quant,
                                              partitions):
    sd = one_chip
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sd)
    q = S((B, K, G, DH), jnp.float32)
    i32 = lambda shape: S(shape, jnp.int32)
    kw = dict(kv_quant=kv_quant, partitions=partitions)
    if layout == "shared":
        pages = _pool_page(kv_quant, (K, POOL), sd)
        args = [q, pages, pages, i32((B, NP)), i32((B, NP)), i32((B,))]
        scale = S((K, POOL), jnp.float32)

        def fn(q, kp, vp, table, base, length, ks=None, vs=None):
            return paged_attention_pallas_shared(
                q, kp, vp, table, base, length, k_scale=ks, v_scale=vs, **kw)
    else:
        pages = _pool_page(kv_quant, (B, K, NP), sd)
        args = [q, pages, pages, i32((B, NP)), i32((B,))]
        scale = S((B, K, NP), jnp.float32)

        def fn(q, kp, vp, base, length, ks=None, vs=None):
            return paged_attention_pallas(
                q, kp, vp, base, length, k_scale=ks, v_scale=vs, **kw)
    if kv_quant != "none":
        args += [scale, scale]
    assert "tpu_custom_call" in _compile_hlo(fn, *args)


def test_flash_attention_kernel_compiles_for_v5e(one_chip):
    """Prefill-width flash attention: S=1024, dh padded to 128 lanes."""
    x = jax.ShapeDtypeStruct((1, 16, 1024, 128), jnp.float32,
                             sharding=one_chip)
    hlo = _compile_hlo(lambda q, k, v: flash_attention_pallas(
        q, k, v, scale=DH ** -0.5, sq_valid=1024, sk_valid=1024), x, x, x)
    assert "tpu_custom_call" in hlo
