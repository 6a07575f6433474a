"""Ahead-of-time compiles of the decode-path Pallas kernels for a TPU v5e.

Interpret mode cannot see what the chip's compiler (Mosaic) refuses:
blocks whose last two dims are neither (8, 128)-aligned nor the array's
own, vector loads from SMEM, shape casts it has no layout for.  These
tests compile each kernel at qwen1.5-0.5b decode widths (B=8 slots, 16
MHA kv-heads, d_head 64, 16-token pages, 128 pages per slot, a 1024-page
shared pool; and the chat and longctx cells' slots and pages) and at
trinity-mini-l8's (32 slots, 4 kv-heads of 8 query
heads, d_head 128; a global pool of 256 pages per slot, window rings of
136) for a v5e chip that is described, not attached: nothing runs, no
chip is needed, each compile takes about a second.  The last test
compiles the whole serving decode step at the benchmark cells' shapes
(a few seconds each; ~30 s for trinity-mini-l8) and reads its HLO for
pool-sized copies.

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

import dataclasses

from repro.configs import EngineConfig, get_config
from repro.core.engine import KVNANDEngine
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.paged_attention.kernel import (
    paged_attention_pallas, paged_attention_pallas_shared)
from repro.models.registry import Model
from repro.models.transformer import Runtime

T = 16
# (slots, kv heads, group, d_head, pages per slot, shared-pool pages)
SHAPES = {"qwen": (8, 16, 1, 64, 128, 1024),
          "qwen-chat": (32, 16, 1, 64, 64, 2048),
          "qwen-longctx": (8, 16, 1, 64, 256, 2048),
          "trinity-global": (32, 4, 8, 128, 256, 8192),
          "trinity-window": (32, 4, 8, 128, 136, 4352)}


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


def _pool_page(kv_quant, lead, sd, dh):
    """Abstract page pool [*lead, T(/2), dh] in the format's storage dtype."""
    dtype, rows = {"none": (jnp.bfloat16, T), "kv8": (jnp.int8, T),
                   "kv4": (jnp.uint8, T // 2)}[kv_quant]
    return jax.ShapeDtypeStruct(lead + (rows, dh), dtype, sharding=sd)


def _case(layout, kv_quant, partitions, layers, shape="qwen"):
    name = f"{layout}-{kv_quant}-{partitions}-{layers}"
    return pytest.param(layout, kv_quant, partitions, layers, shape,
                        id=name if shape == "qwen" else f"{name}-{shape}")


@pytest.mark.parametrize("layout,kv_quant,partitions,layers,shape", [
    _case("striped", "none", 1, 0), _case("striped", "kv8", 1, 0),
    _case("striped", "kv4", 1, 0), _case("striped", "none", 16, 0),
    _case("shared", "none", 1, 0), _case("shared", "kv8", 1, 0),
    _case("shared", "kv4", 1, 0), _case("shared", "kv8", 16, 0),
    # the decode step's form: the stacked pool and a traced layer index
    _case("striped", "none", 1, 24), _case("shared", "none", 1, 24),
    # trinity-mini-l8's: G = 8 query heads per kv head, d_head 128, its
    # 2 global layers' pool and its 6 window layers' rings
    _case("striped", "none", 1, 2, "trinity-global"),
    _case("striped", "none", 1, 6, "trinity-window"),
    _case("shared", "none", 1, 6, "trinity-window"),
    # the benchmark's Qwen cells: chat's 32 slots × 64 pages, and
    # longctx's 8 × 256 pages in 16 partitions
    _case("striped", "none", 1, 24, "qwen-chat"),
    _case("striped", "none", 16, 24, "qwen-longctx"),
])
def test_paged_decode_kernel_compiles_for_v5e(one_chip, layout, kv_quant,
                                              partitions, layers, shape):
    B, K, G, DH, NP, POOL = SHAPES[shape]
    sd = one_chip
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sd)
    i32 = lambda shape: S(shape, jnp.int32)
    stack = (layers,) if layers else ()
    if layout == "shared":
        call, lead, table = (paged_attention_pallas_shared, (K, POOL),
                             [i32((B, NP))])
    else:
        call, lead, table = paged_attention_pallas, (B, K, NP), []
    pages = _pool_page(kv_quant, stack + lead, sd, DH)
    args = [S((B, K, G, DH), jnp.float32), pages, pages, *table,
            i32((B, NP)), i32((B,))]
    named = {}
    if kv_quant != "none":
        named.update(k_scale=S(lead, jnp.float32),
                     v_scale=S(lead, jnp.float32))
    if layers:
        named["layer"] = i32(())

    window = 2048 if shape == "trinity-window" else None

    def fn(*a):
        return call(*a[:len(args)], **dict(zip(named, a[len(args):])),
                    kv_quant=kv_quant, partitions=partitions, window=window)
    assert "tpu_custom_call" in _compile_hlo(fn, *args, *named.values())


def test_flash_attention_kernel_compiles_for_v5e(one_chip):
    """Prefill-width flash attention: S=1024, dh padded to 128 lanes."""
    x = jax.ShapeDtypeStruct((1, 16, 1024, 128), jnp.float32,
                             sharding=one_chip)
    hlo = _compile_hlo(lambda q, k, v: flash_attention_pallas(
        q, k, v, scale=64 ** -0.5, sq_valid=1024, sk_valid=1024), x, x, x)
    assert "tpu_custom_call" in hlo


_OP = re.compile(r"^\s*(?:ROOT )?%\S+ = (\w+)\[([\d,]*)\]\S* ([\w\-]+)\(")


ARCHS = {"qwen1.5-0.5b": ("qwen1.5-0.5b", {}),
         "trinity-mini-l8": ("trinity-mini", {"n_layers": 8,
                                              "n_experts_held": 16})}


def _decode_step_ops(sd, slots, max_context, arch="qwen1.5-0.5b"):
    """(pool shapes, [(dims, opcode)] of every array-valued instruction)
    of the serving decode step (striped bf16 pool, ragged appends,
    Pallas kernel) at the full width of `arch`, compiled for `sd`."""
    name, cut = ARCHS[arch]
    cfg, rt = dataclasses.replace(get_config(name), **cut), Runtime()
    eng = KVNANDEngine(cfg, EngineConfig(page_tokens=T, attn_impl="pallas"),
                       rt)
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
    # one paged-attention call per layer of each stack's scanned group
    assert hlo.count('custom_call_target="tpu_custom_call"') == \
        sum(st.period for st in eng.stacks)
    ops = [(tuple(int(d) for d in m.group(2).split(",") if d), m.group(3))
           for m in map(_OP.match, hlo.splitlines()) if m]
    pools = [p.shape for p in (cache.k_pages_g, cache.k_pages_w)
             if p is not None]
    return pools, ops


@pytest.mark.parametrize("slots,max_context,pool_copies,arch", [
    # qwen05b-chat's server
    pytest.param(32, 1024, 0, "qwen1.5-0.5b", id="32-1024-0"),
    # qwen05b-longctx's server: with d_head 64 the cache's default device
    # layout puts the 256-page axis in the lanes ({3,5,4,2,1,0}), which
    # the kernel cannot read, so K and V are converted to row-major on
    # entry and back on exit: four whole-pool copies, none per layer
    pytest.param(8, 4096, 4, "qwen1.5-0.5b", id="8-4096-4"),
    # trinity-mini-agent's server: d_head 128 keeps both the global pool
    # and the window rings row-major, read in place with no copy
    pytest.param(32, 4096, 0, "trinity-mini-l8",
                 id="32-4096-0-trinity-mini-l8"),
])
def test_decode_step_touches_pool_in_place(one_chip, slots, max_context,
                                           pool_copies, arch):
    """The compiled decode step reads and appends the KV pools in place:
    no op outputs a layer's slice of a pool (the kernel reads the
    stacked pool at a prefetched layer), and a pool is copied whole no
    more often than the cache's own layout forces."""
    pools, ops = _decode_step_ops(one_chip, slots, max_context, arch)
    for pool in pools:
        layer_slices = {pool[1:], (1,) + pool[1:]}
        assert [op for dims, op in ops if dims in layer_slices] == []
        copies = [op for dims, op in ops
                  if dims == pool and op not in (
                      "parameter", "get-tuple-element",
                      "dynamic-update-slice", "bitcast")]
        assert copies == ["copy"] * pool_copies
