"""Multi-device correctness (8 fake host devices via subprocess):
ring attention, sharded paged decode + in-shard appends, compressed-DP
train step, elastic checkpoint restore across topologies."""
import pytest

from tests._mp import run_multidevice

COMMON = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from repro.distributed.sharding import make_mesh
mesh = make_mesh((2, 4), ("data", "model"))
assert len(jax.devices()) == 8
"""


@pytest.mark.slow
def test_ring_attention_matches_flash():
    run_multidevice(COMMON + """
from repro.core.seqpar import ring_attention
from repro.kernels.flash_attention import flash_attention
B, S, H, K, dh = 4, 128, 6, 3, 32
ks = jax.random.split(jax.random.PRNGKey(0), 3)
q = jax.random.normal(ks[0], (B, S, H, dh))
k = jax.random.normal(ks[1], (B, S, K, dh))
v = jax.random.normal(ks[2], (B, S, K, dh))
for causal, window in ((True, None), (True, 40), (False, None)):
    with jax.set_mesh(mesh):
        out = jax.jit(lambda q, k, v: ring_attention(
            q, k, v, mesh, causal=causal, window=window))(q, k, v)
    ref = flash_attention(q, k, v, causal=causal, window=window, impl="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)
print("ring OK")
""")


@pytest.mark.slow
def test_sharded_paged_decode_and_append():
    run_multidevice(COMMON + """
from repro.core import seqpar
from repro.kernels.paged_attention import paged_attention_partial
B, K, G, NP, T, dh, L = 4, 2, 3, 8, 16, 32, 2
H = K * G
ks = jax.random.split(jax.random.PRNGKey(1), 5)
kd = jax.random.normal(ks[0], (B, NP*T, K, dh))
vd = jax.random.normal(ks[1], (B, NP*T, K, dh))
kp = kd.reshape(B, NP, T, K, dh).transpose(0, 3, 1, 2, 4)
vp = vd.reshape(B, NP, T, K, dh).transpose(0, 3, 1, 2, 4)
base = jnp.broadcast_to((jnp.arange(NP)*T)[None], (B, NP)).astype(jnp.int32)
q = jax.random.normal(ks[2], (B, H, dh))
length = jnp.full((B,), 100, jnp.int32)
# sharded partial+combine == single-device full
with jax.set_mesh(mesh):
    o_sh = jax.jit(lambda *a: seqpar.paged_decode_attention_sharded(
        *a, mesh, batch_axes=("data",), page_axes=("model",)))(
        q, kp, vp, base, length)
o_ref, _, _ = paged_attention_partial(q, kp, vp, base, length, impl="ref")
np.testing.assert_allclose(np.asarray(o_sh), np.asarray(o_ref),
                           atol=3e-5, rtol=3e-5)
# in-shard uniform append == direct write
pool_k = jnp.zeros((L, B, K, NP, T, dh))
pool_v = jnp.zeros((L, B, K, NP, T, dh))
k1 = jax.random.normal(ks[3], (B, K, dh))
v1 = jax.random.normal(ks[4], (B, K, dh))
phys = jnp.full((B,), 5, jnp.int32)   # page 5 -> owned by shard 2 of 4
slot = jnp.full((B,), 7, jnp.int32)
with jax.set_mesh(mesh):
    nk, nv = jax.jit(lambda *a: seqpar.sharded_append_uniform(
        *a, mesh, batch_axes=("data",), page_axes=("model",)))(
        pool_k, pool_v, 1, k1, v1, phys, slot)
expect = pool_k.at[1, :, :, 5, 7].set(k1)
np.testing.assert_allclose(np.asarray(nk), np.asarray(expect), atol=1e-6)
assert float(jnp.abs(nv[0]).max()) == 0.0
print("paged sharded OK")
""")


@pytest.mark.slow
def test_prefill_fill_sharded_matches_reference():
    run_multidevice(COMMON + """
from repro.core import seqpar, paged_kv
L, B, K, NP, T, dh = 2, 4, 2, 8, 8, 16
S = 50
kv = jax.random.normal(jax.random.PRNGKey(0), (B, S, K, dh))
pool = jnp.zeros((L, B, K, NP, T, dh))
with jax.set_mesh(mesh):
    out = jax.jit(lambda p, kv: seqpar.sharded_prefill_fill(
        p, kv, 1, mesh, batch_axes=("data",), page_axes=("model",)))(
        pool, kv)
ref = paged_kv.fill_prefill_at(pool, kv, jnp.asarray(1))
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)
print("prefill fill OK")
""")


@pytest.mark.slow
def test_engine_decode_sharded_matches_single_device():
    run_multidevice(COMMON + """
from repro.configs import get_config, EngineConfig
from repro.models.registry import Model
from repro.models.transformer import Runtime
from repro.core.engine import KVNANDEngine
cfg = get_config("qwen2.5-32b").reduced()
rt = Runtime()
m = Model(cfg, rt)
params = m.init(jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 20), 0,
                          cfg.vocab_size, jnp.int32)
eng1 = KVNANDEngine(cfg, EngineConfig(page_tokens=4, kv_dtype="float32"),
                    rt, mesh=None)
lg1, c1 = eng1.prefill(params, {"tokens": toks[:, :16]}, 28)
for t in range(3):
    lg1, c1 = eng1.decode_step(params, c1, toks[:, 16+t:17+t])
engN = KVNANDEngine(cfg, EngineConfig(page_tokens=4, kv_dtype="float32"),
                    rt, mesh=mesh)
with jax.set_mesh(mesh):
    lgN, cN = jax.jit(lambda p, b: engN.prefill(p, b, 28))(
        params, {"tokens": toks[:, :16]})
    step = jax.jit(lambda p, c, t: engN.decode_step(p, c, t))
    for t in range(3):
        lgN, cN = step(params, cN, toks[:, 16+t:17+t])
np.testing.assert_allclose(np.asarray(lg1), np.asarray(lgN),
                           atol=5e-4, rtol=5e-4)
print("engine sharded == single device OK")
""", timeout=900)


@pytest.mark.slow
def test_engine_decode_sharded_quantized_matches_single_device():
    """kv8 pools: sharded prefill quantization, in-shard requantizing
    appends, and scale-carrying sharded attention == single-device quant."""
    run_multidevice(COMMON + """
from repro.configs import get_config, EngineConfig
from repro.models.registry import Model
from repro.models.transformer import Runtime
from repro.core.engine import KVNANDEngine
cfg = get_config("qwen2.5-32b").reduced()
rt = Runtime()
m = Model(cfg, rt)
params = m.init(jax.random.PRNGKey(0))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 20), 0,
                          cfg.vocab_size, jnp.int32)
ec = EngineConfig(page_tokens=4, kv_dtype="float32", kv_quant="kv8")
eng1 = KVNANDEngine(cfg, ec, rt, mesh=None)
lg1, c1 = eng1.prefill(params, {"tokens": toks[:, :16]}, 28)
for t in range(3):
    lg1, c1 = eng1.decode_step(params, c1, toks[:, 16+t:17+t])
engN = KVNANDEngine(cfg, ec, rt, mesh=mesh)
with jax.set_mesh(mesh):
    lgN, cN = jax.jit(lambda p, b: engN.prefill(p, b, 28))(
        params, {"tokens": toks[:, :16]})
    step = jax.jit(lambda p, c, t: engN.decode_step(p, c, t))
    for t in range(3):
        lgN, cN = step(params, cN, toks[:, 16+t:17+t])
np.testing.assert_allclose(np.asarray(lg1), np.asarray(lgN),
                           atol=5e-3, rtol=5e-3)
print("engine sharded quant == single device OK")
""", timeout=900)


@pytest.mark.slow
def test_compressed_train_step_close_to_exact():
    run_multidevice("""
import jax, jax.numpy as jnp, numpy as np
from repro.distributed.sharding import make_mesh
mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
from repro.configs import get_config, EngineConfig
from repro.models.registry import Model
from repro.models.transformer import Runtime
from repro.training.optimizer import AdamWConfig
from repro.training.train_step import (
    make_train_step, make_compressed_train_step, init_train_state)
cfg = get_config("qwen1.5-0.5b").reduced()
rt = Runtime()
m = Model(cfg, rt)
params = m.init(jax.random.PRNGKey(0))
acfg = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=100,
                   min_lr_ratio=1.0)
batch = {
  "tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                               cfg.vocab_size, jnp.int32),
  "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0,
                               cfg.vocab_size, jnp.int32)}
with jax.set_mesh(mesh):
    s0 = init_train_state(params, acfg)
    step = jax.jit(make_train_step(cfg, rt, acfg, EngineConfig()))
    s1, m1 = step(s0, batch)
    sc0 = init_train_state(params, acfg, compressed=True)
    cstep = jax.jit(make_compressed_train_step(cfg, rt, acfg,
                                               EngineConfig(), mesh))
    sc1, m2 = cstep(sc0, batch)
# int8-compressed cross-pod grads track the exact step closely
assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3
diffs = [float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())
         for a, b in zip(jax.tree.leaves(s1.params),
                         jax.tree.leaves(sc1.params))]
scale = max(float(jnp.abs(x).max()) for x in jax.tree.leaves(s1.params))
assert max(diffs) / scale < 0.05, (max(diffs), scale)
print("compressed train OK", float(m1["loss"]), float(m2["loss"]))
""", timeout=900)


@pytest.mark.slow
def test_elastic_checkpoint_restore_different_topology():
    run_multidevice("""
import jax, jax.numpy as jnp, numpy as np, tempfile
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint.checkpoint import save_checkpoint, restore_checkpoint
from repro.launch.mesh import mesh_from_devices
mesh8 = mesh_from_devices(jax.devices())            # 4x2 or similar
w = jnp.arange(64.0).reshape(8, 8)
sh8 = NamedSharding(mesh8, P("data", "model"))
state = {"w": jax.device_put(w, sh8)}
d = tempfile.mkdtemp()
save_checkpoint(d, 0, state)
# restart on HALF the fleet (4 devices)
mesh4 = mesh_from_devices(jax.devices()[:4])
sh4 = NamedSharding(mesh4, P("data", "model"))
restored, _ = restore_checkpoint(d, 0, state, shardings={"w": sh4})
assert restored["w"].sharding == sh4
np.testing.assert_array_equal(np.asarray(restored["w"]), np.asarray(w))
print("elastic restore OK", mesh4.shape)
""")


@pytest.mark.slow
def test_sharded_chunk_fill_and_attention_match_reference():
    """Chunked prefill on a mesh: the slot/page-ownership-guarded chunk
    fill and the page-sharded chunk attention (combine over `model`) must
    match the single-device chunk oracle — for bf16 and kv8 pools."""
    run_multidevice(COMMON + """
from repro.core import seqpar, paged_kv
from repro.kernels.paged_attention.ref import paged_chunk_attention_ref

L, B, K, NP, T, dh = 2, 4, 2, 8, 8, 16
S, slot, layer, page0 = 16, 2, 1, 2
kv = jax.random.normal(jax.random.PRNGKey(0), (1, S, K, dh))

# --- fill: intersection of local page range x owned batch row ---------
pool = jnp.zeros((L, B, K, NP, T, dh))
with jax.set_mesh(mesh):
    out = jax.jit(lambda p, kv: seqpar.sharded_chunk_fill(
        p, kv, layer, slot, page0, S, mesh,
        batch_axes=("data",), page_axes=("model",)))(pool, kv)
ref = paged_kv.fill_chunk_global_at(pool, kv, jnp.asarray(layer),
                                    jnp.asarray(slot), jnp.asarray(page0),
                                    jnp.asarray(S))
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)

# quantized variant carries per-page scales
qpool = jnp.zeros((L, B, K, NP, T, dh), jnp.int8)
qscale = jnp.zeros((L, B, K, NP), jnp.float32)
with jax.set_mesh(mesh):
    qo, so = jax.jit(lambda p, s, kv: seqpar.sharded_chunk_fill(
        p, kv, layer, slot, page0, S, mesh, batch_axes=("data",),
        page_axes=("model",), scale=s, kv_quant="kv8"))(qpool, qscale, kv)
qr, sr = paged_kv.fill_chunk_global_at(
    qpool, kv, jnp.asarray(layer), jnp.asarray(slot), jnp.asarray(page0),
    jnp.asarray(S), scale=qscale, kv_quant="kv8")
# sharded vs single-device reduce order can differ by 1 ULP in the page
# amax -> scales to ~1e-7 rtol, codes to at most one rounding tie
assert int(jnp.abs(qo.astype(jnp.int32) - qr.astype(jnp.int32)).max()) <= 1
np.testing.assert_allclose(np.asarray(so), np.asarray(sr), rtol=1e-6)

# --- past-context chunk attention: partials combined over pages -------
H, G = 6, 3
ks = jax.random.split(jax.random.PRNGKey(1), 3)
kp = jax.random.normal(ks[0], (1, K, NP, T, dh))
vp = jax.random.normal(ks[1], (1, K, NP, T, dh))
q = jax.random.normal(ks[2], (1, 12, H, dh))
base = (jnp.arange(NP, dtype=jnp.int32) * T)[None]
start = jnp.asarray(40, jnp.int32)
q_pos = 40 + jnp.arange(12, dtype=jnp.int32)
with jax.set_mesh(mesh):
    o_sh, m_sh, l_sh = jax.jit(lambda *a: seqpar.sharded_chunk_attention(
        *a, mesh, page_axes=("model",)))(q, kp, vp, base, start, q_pos)
o_rf, m_rf, l_rf = paged_chunk_attention_ref(q, kp, vp, base, start, q_pos)
np.testing.assert_allclose(np.asarray(o_sh), np.asarray(o_rf),
                           atol=3e-5, rtol=3e-5)
np.testing.assert_allclose(np.asarray(l_sh), np.asarray(l_rf),
                           atol=3e-5, rtol=3e-5)
print("sharded chunk fill + attention OK")
""")


@pytest.mark.slow
def test_engine_prefill_chunk_sharded_matches_single_device():
    """prefill_chunk on a mesh (global-pool arch): sharded chunk fills +
    sharded past partials reproduce the single-device chunk path."""
    run_multidevice(COMMON + """
from repro.configs import get_config, EngineConfig
from repro.models.registry import Model
from repro.models.transformer import Runtime
from repro.core.engine import KVNANDEngine
cfg = get_config("qwen2.5-32b").reduced()
rt = Runtime()
m = Model(cfg, rt)
params = m.init(jax.random.PRNGKey(0))
n, C, ctx = 24, 16, 32
toks = jax.random.randint(jax.random.PRNGKey(1), (1, 32), 1,
                          cfg.vocab_size, jnp.int32)
def chunked(engine, cache, use_jit):
    lg = None
    for c0 in range(0, 32, C):
        cl = min(C, n - c0)
        fn = lambda p, c, t, s, st, nn: engine.prefill_chunk(
            p, c, {"tokens": t}, s, st, nn, first=(c0 == 0))
        if use_jit:
            fn = jax.jit(fn)
        lg, cache = fn(params, cache, toks[:, c0:c0 + C], 2,
                       jnp.asarray(c0, jnp.int32), jnp.asarray(cl, jnp.int32))
    return lg, cache
eng1 = KVNANDEngine(cfg, EngineConfig(page_tokens=4, kv_dtype="float32",
                                      uniform_lengths=False), rt)
lg1, _ = chunked(eng1, eng1.init_cache(4, ctx), False)
engN = KVNANDEngine(cfg, EngineConfig(page_tokens=4, kv_dtype="float32",
                                      uniform_lengths=False), rt, mesh=mesh)
with jax.set_mesh(mesh):
    lgN, _ = chunked(engN, engN.init_cache(4, ctx), True)
np.testing.assert_allclose(np.asarray(lg1), np.asarray(lgN),
                           atol=5e-4, rtol=5e-4)
print("sharded prefill_chunk == single device OK")
""", timeout=900)


@pytest.mark.slow
def test_shared_pool_sharded_decode_and_append():
    """Shared-pool P_total sharded over `model`: table-walked partial
    attention + owning-shard appends match the single-device oracle."""
    run_multidevice(COMMON + """
from repro.core import seqpar
from repro.kernels.paged_attention import paged_attention_partial
B, K, G, NP, T, dh, L = 4, 2, 3, 8, 16, 32, 2
P = B * NP
H = K * G
ks = jax.random.split(jax.random.PRNGKey(5), 4)
pool_k = jax.random.normal(ks[0], (K, P, T, dh))
pool_v = jax.random.normal(ks[1], (K, P, T, dh))
q = jax.random.normal(ks[2], (B, H, dh))
table = jnp.asarray(np.random.default_rng(3).permutation(P).reshape(B, NP),
                    jnp.int32)
base = jnp.broadcast_to((jnp.arange(NP) * T)[None], (B, NP)).astype(jnp.int32)
length = jnp.array([7, 33, 64, 128], jnp.int32)
ref, _, _ = paged_attention_partial(q, pool_k, pool_v, base, length,
                                    impl="ref", page_table=table)
with jax.set_mesh(mesh):
    out = jax.jit(lambda q, kp, vp, tbl, b, ln:
                  seqpar.paged_decode_attention_sharded_shared(
                      q, kp, vp, tbl, b, ln, mesh, batch_axes=("data",),
                      page_axes=("model",), impl="ref"))(
        q, pool_k, pool_v, table, base, length)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                           atol=3e-5, rtol=3e-5)
pools_k = jnp.zeros((L, K, P, T, dh))
pools_v = jnp.zeros((L, K, P, T, dh))
phys = jnp.array([3, 11, 19, 30], jnp.int32)
slot = jnp.array([0, 5, 15, 2], jnp.int32)
kn = jax.random.normal(ks[3], (B, K, dh))
with jax.set_mesh(mesh):
    ok, ov = jax.jit(lambda kp, vp, kn, vn, ph, sl:
                     seqpar.sharded_append_shared(
                         kp, vp, 1, kn, vn, ph, sl, mesh,
                         batch_axes=("data",), page_axes=("model",)))(
        pools_k, pools_v, kn, -kn, phys, slot)
for b_ in range(B):
    np.testing.assert_allclose(np.asarray(ok[1, :, phys[b_], slot[b_]]),
                               np.asarray(kn[b_]), atol=1e-6)
assert float(jnp.abs(ok[0]).max()) == 0.0
print("shared-pool sharded OK")
""")
