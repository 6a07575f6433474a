"""Ahead-of-time compiles of the decode-path Pallas kernels for a TPU v5e.

Interpret mode cannot see what the chip's compiler (Mosaic) refuses:
blocks whose last two dims are neither (8, 128)-aligned nor the array's
own, vector loads from SMEM, shape casts it has no layout for.  These
tests compile each kernel at qwen1.5-0.5b decode widths (B=8 slots, 16
MHA kv-heads, d_head 64, 16-token pages, 128 pages per slot, a 1024-page
shared pool) for a v5e chip that is described, not attached: nothing
runs, no chip is needed, each compile takes about a second.  The last
test compiles the whole serving decode step at both benchmark cells'
shapes (a few seconds each) and reads its HLO for pool-sized copies.

The topology is described inside a module-scoped fixture (never at
import), so every pytest-xdist worker collects the same tests and only
the worker that runs this file loads the TPU compiler.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import EngineConfig, get_config
from repro.core.engine import KVNANDEngine
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.paged_attention.kernel import (
    paged_attention_pallas, paged_attention_pallas_shared)
from repro.models.registry import Model
from repro.models.transformer import Runtime

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


@pytest.mark.parametrize("layout,kv_quant,partitions,layers", [
    ("striped", "none", 1, 0), ("striped", "kv8", 1, 0),
    ("striped", "kv4", 1, 0), ("striped", "none", 16, 0),
    ("shared", "none", 1, 0), ("shared", "kv8", 1, 0), ("shared", "kv4", 1, 0),
    ("shared", "kv8", 16, 0),
    # the decode step's form: the stacked pool and a traced layer index
    ("striped", "none", 1, 24), ("shared", "none", 1, 24),
])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, layout, kv_quant,
                                              partitions, layers):
    sd = one_chip
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sd)
    i32 = lambda shape: S(shape, jnp.int32)
    stack = (layers,) if layers else ()
    if layout == "shared":
        call, lead, table = (paged_attention_pallas_shared, (K, POOL),
                             [i32((B, NP))])
    else:
        call, lead, table = paged_attention_pallas, (B, K, NP), []
    pages = _pool_page(kv_quant, stack + lead, sd)
    args = [S((B, K, G, DH), jnp.float32), pages, pages, *table,
            i32((B, NP)), i32((B,))]
    named = {}
    if kv_quant != "none":
        named.update(k_scale=S(lead, jnp.float32),
                     v_scale=S(lead, jnp.float32))
    if layers:
        named["layer"] = i32(())

    def fn(*a):
        return call(*a[:len(args)], **dict(zip(named, a[len(args):])),
                    kv_quant=kv_quant, partitions=partitions)
    assert "tpu_custom_call" in _compile_hlo(fn, *args, *named.values())


def test_flash_attention_kernel_compiles_for_v5e(one_chip):
    """Prefill-width flash attention: S=1024, dh padded to 128 lanes."""
    x = jax.ShapeDtypeStruct((1, 16, 1024, 128), jnp.float32,
                             sharding=one_chip)
    hlo = _compile_hlo(lambda q, k, v: flash_attention_pallas(
        q, k, v, scale=DH ** -0.5, sq_valid=1024, sk_valid=1024), x, x, x)
    assert "tpu_custom_call" in hlo


_OP = re.compile(r"^\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(")


def _decode_step_ops(sd, slots, max_context):
    """(pool shape, [(dims, opcode)] of every array-valued instruction)
    of the serving decode step (striped bf16 pool, ragged appends,
    Pallas kernel) at full qwen1.5-0.5b width, compiled for `sd`."""
    cfg, rt = get_config("qwen1.5-0.5b"), Runtime()
    eng = KVNANDEngine(cfg, EngineConfig(page_tokens=T, uniform_lengths=False,
                                         attn_impl="pallas"), rt)
    on = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sd), tree)
    params = on(jax.eval_shape(Model(cfg, rt).init, jax.random.PRNGKey(0)))
    cache = on(eng.abstract_cache(slots, max_context))
    step = jax.jit(lambda p, c, t, a: eng.decode_step(p, c, t, active=a),
                   donate_argnums=(1,))
    hlo = step.lower(params, cache,
                     jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=sd),
                     jax.ShapeDtypeStruct((slots,), jnp.bool_, sharding=sd)
                     ).compile().as_text()
    assert hlo.count('custom_call_target="tpu_custom_call"') == 1
    ops = [(tuple(int(d) for d in m.group(2).split(",") if d), m.group(3))
           for m in map(_OP.match, hlo.splitlines()) if m]
    return cache.k_pages_g.shape, ops


@pytest.mark.parametrize("slots,max_context,pool_copies", [
    (32, 1024, 0),   # qwen05b-chat's server
    # qwen05b-longctx's server: with d_head 64 the cache's default device
    # layout puts the 256-page axis in the lanes ({3,5,4,2,1,0}), which
    # the kernel cannot read, so K and V are converted to row-major on
    # entry and back on exit: four whole-pool copies, none per layer
    (8, 4096, 4),
])
def test_decode_step_touches_pool_in_place(one_chip, slots, max_context,
                                           pool_copies):
    """The compiled decode step reads and appends the KV pools in place:
    no op outputs a layer's slice of the pool (the kernel reads the
    stacked pool at a prefetched layer), and the pool is copied whole no
    more often than the cache's own layout forces."""
    pool, ops = _decode_step_ops(one_chip, slots, max_context)
    layer_slices = {pool[1:], (1,) + pool[1:]}
    assert [op for dims, op in ops if dims in layer_slices] == []
    copies = [op for dims, op in ops
              if dims == pool and op not in ("parameter", "get-tuple-element",
                                             "dynamic-update-slice",
                                             "bitcast")]
    assert copies == ["copy"] * pool_copies
