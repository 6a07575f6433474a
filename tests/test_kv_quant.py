"""Quantized KV pages (kv8/kv4): pack/unpack round trips, paged-attention
parity vs the bf16 reference (ref + Pallas interpret), scale round-trip
through the decode append paths, and engine-level prefill+decode fidelity."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import EngineConfig, get_config
from repro.core import paged_kv
from repro.core.engine import KVNANDEngine
from repro.core.quant import (dequantize_kv_page, kv_page_tokens_stored,
                              kv_quant_bits, pack_int4_tokens,
                              quantize_kv_page, unpack_int4_tokens)
from repro.kernels.paged_attention import paged_attention_partial

# output-tolerance per format vs the bf16 pool on unit-normal data
TOL = {"kv8": 0.05, "kv4": 0.5}


def _build(B, K, NP, T, dh, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    kd = jax.random.normal(ks[0], (B, NP * T, K, dh), jnp.float32)
    vd = jax.random.normal(ks[1], (B, NP * T, K, dh), jnp.float32)
    k_pages = kd.reshape(B, NP, T, K, dh).transpose(0, 3, 1, 2, 4)
    v_pages = vd.reshape(B, NP, T, K, dh).transpose(0, 3, 1, 2, 4)
    base = jnp.broadcast_to((jnp.arange(NP) * T)[None], (B, NP)
                            ).astype(jnp.int32)
    q = jax.random.normal(ks[2], (B, K, dh), jnp.float32)
    return k_pages, v_pages, base


# ---------------------------------------------------------------------------
# format primitives
# ---------------------------------------------------------------------------

def test_int4_token_pack_roundtrip():
    q = jax.random.randint(jax.random.PRNGKey(0), (3, 2, 16, 8), 0, 16
                           ).astype(jnp.int8)
    packed = pack_int4_tokens(q)
    assert packed.shape == (3, 2, 8, 8) and packed.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(unpack_int4_tokens(packed)),
                                  np.asarray(q) - 8)


@pytest.mark.parametrize("fmt,rel", [("kv8", 1 / 127), ("kv4", 1 / 7)])
def test_page_quant_roundtrip_error_bound(fmt, rel):
    """|x - deq(quant(x))| ≤ scale/2 per element, scale = amax/qmax."""
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 4, 16, 32))
    q, s = quantize_kv_page(x, fmt)
    assert s.shape == (2, 3, 4)
    back = dequantize_kv_page(q, s, fmt)
    amax = jnp.max(jnp.abs(x), axis=(-2, -1))
    bound = (amax * rel / 2 + 1e-6)[..., None, None]
    assert bool(jnp.all(jnp.abs(back - x) <= bound))


def test_storage_geometry():
    assert kv_quant_bits("none") == 16
    assert kv_quant_bits("kv8") == 8
    assert kv_quant_bits("kv4") == 4
    assert kv_page_tokens_stored(64, "kv4") == 32
    assert kv_page_tokens_stored(64, "kv8") == 64
    with pytest.raises(ValueError):
        kv_page_tokens_stored(9, "kv4")
    with pytest.raises(ValueError):
        EngineConfig(kv_quant="kv4", page_tokens=9)
    with pytest.raises(ValueError):
        EngineConfig(kv_quant="int3")


# ---------------------------------------------------------------------------
# paged-attention parity
# ---------------------------------------------------------------------------

SWEEP = [
    # B, K, G, NP, T, dh, lengths, window
    (2, 3, 4, 8, 16, 32, (100, 37), None),
    (2, 3, 4, 8, 16, 32, (100, 37), 24),
    (1, 2, 8, 16, 8, 16, (128,), None),
    (2, 4, 2, 8, 32, 64, (200, 256), None),
]


@pytest.mark.parametrize("case", SWEEP)
@pytest.mark.parametrize("fmt", ["kv8", "kv4"])
def test_quant_parity_vs_bf16_ref(case, fmt):
    """Quantized attention ≈ bf16-pool attention (tolerance-gated), and the
    Pallas-interpret kernel matches the quantized jnp ref bit-tightly."""
    B, K, G, NP, T, dh, lengths, window = case
    kp, vp, base = _build(B, K, NP, T, dh)
    q = jax.random.normal(jax.random.PRNGKey(9), (B, K * G, dh))
    length = jnp.asarray(lengths, jnp.int32)

    o_ref, m_ref, l_ref = paged_attention_partial(
        q, kp, vp, base, length, window=window, impl="ref")

    qk, sk = quantize_kv_page(kp, fmt)
    qv, sv = quantize_kv_page(vp, fmt)
    o_q, m_q, l_q = paged_attention_partial(
        q, qk, qv, base, length, window=window, impl="ref",
        kv_quant=fmt, k_scale=sk, v_scale=sv)
    assert float(jnp.abs(o_q - o_ref).max()) < TOL[fmt]

    o_i, m_i, l_i = paged_attention_partial(
        q, qk, qv, base, length, window=window, impl="interpret",
        kv_quant=fmt, k_scale=sk, v_scale=sv, pages_per_block=4)
    np.testing.assert_allclose(np.asarray(o_i), np.asarray(o_q),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(m_i), np.asarray(m_q),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(l_i), np.asarray(l_q),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("fmt", ["kv8", "kv4"])
def test_quant_partial_stats_merge(fmt):
    """Cross-shard (m, ℓ) merge is format-agnostic: splitting a quantized
    pool across two 'devices' reproduces the unsplit result."""
    from repro.core.seqpar import merge_two
    B, K, G, NP, T, dh = 1, 2, 2, 8, 8, 32
    kp, vp, base = _build(B, K, NP, T, dh)
    qk, sk = quantize_kv_page(kp, fmt)
    qv, sv = quantize_kv_page(vp, fmt)
    q = jax.random.normal(jax.random.PRNGKey(5), (B, K * G, dh))
    length = jnp.asarray([60], jnp.int32)
    o_full, _, _ = paged_attention_partial(
        q, qk, qv, base, length, kv_quant=fmt, k_scale=sk, v_scale=sv,
        impl="ref")
    half = NP // 2
    parts = []
    for sl in (slice(None, half), slice(half, None)):
        parts.append(paged_attention_partial(
            q, qk[:, :, sl], qv[:, :, sl], base[:, sl], length,
            kv_quant=fmt, k_scale=sk[:, :, sl], v_scale=sv[:, :, sl],
            impl="ref"))
    o, _, _ = merge_two(*parts[0], *parts[1])
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_full),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# scale round-trip through the append paths
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", ["kv8", "kv4"])
@pytest.mark.parametrize("uniform", [True, False])
def test_append_requantizes_only_touched_page(fmt, uniform):
    L, B, K, NP, T, dh = 2, 2, 3, 4, 8, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (L, B, K, NP, T, dh))
    pool, scale = quantize_kv_page(x, fmt)
    layer = jnp.asarray(1, jnp.int32)
    lengths = (jnp.asarray([12, 12], jnp.int32) if uniform
               else jnp.asarray([12, 19], jnp.int32))
    phys, slot = lengths // T, lengths % T
    val = jax.random.normal(jax.random.PRNGKey(1), (B, K, dh))
    # uniform: lockstep rows (every row on one page and slot), the input
    # a static batch gives the ragged writer
    pool2, scale2 = jax.jit(paged_kv.append_token_quant,
                            static_argnames=("fmt",))(
        pool, scale, layer, phys, slot, val, fmt=fmt)

    deq = dequantize_kv_page(pool2, scale2, fmt)
    rel = {"kv8": 1 / 127, "kv4": 1 / 7}[fmt]
    for b in range(B):
        p, sl = int(phys[b]), int(slot[b])
        # the new token reads back within one quantization step
        amax = float(jnp.abs(deq[1, b, :, p]).max())
        err = float(jnp.abs(deq[1, b, :, p, sl] - val[b]).max())
        assert err <= amax * rel / 2 + 1e-5, (b, err)
        # untouched pages: codes AND scales bit-identical
        for pp in range(NP):
            if pp == p:
                continue
            np.testing.assert_array_equal(np.asarray(pool2[1, b, :, pp]),
                                          np.asarray(pool[1, b, :, pp]))
            np.testing.assert_array_equal(np.asarray(scale2[1, b, :, pp]),
                                          np.asarray(scale[1, b, :, pp]))
    # other layers fully untouched
    np.testing.assert_array_equal(np.asarray(pool2[0]), np.asarray(pool[0]))
    np.testing.assert_array_equal(np.asarray(scale2[0]),
                                  np.asarray(scale[0]))


@pytest.mark.parametrize("fmt", ["kv8", "kv4"])
@pytest.mark.parametrize("uniform", [True, False])
def test_append_ignores_stale_page_garbage(fmt, uniform):
    """A recycled page holding a previous occupant's 50×-larger K/V must
    not inflate the new scale: dead slots (> slot) are zeroed before
    requantization, so the real token keeps full format precision."""
    L, B, K, NP, T, dh = 1, 2, 2, 2, 8, 8
    stale = 50.0 * jax.random.normal(jax.random.PRNGKey(0),
                                     (L, B, K, NP, T, dh))
    pool, scale = quantize_kv_page(stale, fmt)
    layer = jnp.asarray(0, jnp.int32)
    # fresh sequences, slot 0: lockstep rows (uniform) or the second row
    # on its stripe's other page
    lengths = jnp.asarray([0, 0 if uniform else T], jnp.int32)
    phys, slot = lengths // T, lengths % T
    val = jax.random.normal(jax.random.PRNGKey(1), (B, K, dh))  # O(1) data
    pool2, scale2 = paged_kv.append_token_quant(pool, scale, layer, phys,
                                                slot, val, fmt)
    deq = dequantize_kv_page(pool2, scale2, fmt)
    rel = {"kv8": 1 / 127, "kv4": 1 / 7}[fmt]
    for b in range(B):
        p = int(phys[b])
        err = float(jnp.abs(deq[0, b, :, p, 0] - val[b]).max())
        amax = float(jnp.abs(val[b]).max())
        # the touched page's scale reflects the NEW token only, not the
        # 50× stale occupant (untouched pages keep their stale scale by
        # design)
        assert err <= amax * rel / 2 + 1e-5, (b, err)
        assert float(scale2[0, b, :, p].max()) < \
            float(scale[0, b, :, p].max()) / 10


def test_dse_kv_format_fidelity_guard():
    """recommend_engine_config only drops KV bits when it buys real
    latency: short context (weight-bound) keeps full-width KV, long
    context (KV-bound) picks a low-bit page format."""
    from repro.core import dse
    short = dse.recommend_engine_config("llama3.1-70b", 128)
    long = dse.recommend_engine_config("llama3.1-70b", 100_000)
    assert short.kv_quant == "none", short
    assert long.kv_quant in ("kv8", "kv4"), long


@pytest.mark.parametrize("fmt", ["kv8", "kv4"])
def test_prefill_fill_quant_roundtrip(fmt):
    B, S, K, dh, T, NP, L = 2, 50, 3, 8, 16, 8, 4
    kv = jax.random.normal(jax.random.PRNGKey(0), (B, S, K, dh))
    Ts = kv_page_tokens_stored(T, fmt)
    pool = jnp.zeros((L, B, K, NP, Ts, dh),
                     jnp.int8 if fmt == "kv8" else jnp.uint8)
    scale = jnp.zeros((L, B, K, NP), jnp.float32)
    pool, scale = paged_kv.fill_prefill_at_quant(pool, scale, kv,
                                                 jnp.asarray(2), fmt)
    deq = dequantize_kv_page(pool[2], scale[2], fmt)     # [B, K, NP, T, dh]
    dense = deq.transpose(0, 2, 3, 1, 4).reshape(B, NP * T, K, dh)[:, :S]
    tol = {"kv8": 0.02, "kv4": 0.35}[fmt]
    assert float(jnp.abs(dense - kv).max()) < tol
    # other layers untouched (still the all-zero init codes)
    assert float(jnp.abs(pool[1].astype(jnp.float32)).max()) == 0.0
    assert float(jnp.abs(scale[1]).max()) == 0.0


# ---------------------------------------------------------------------------
# engine-level fidelity (prefill + decode, both pools)
# ---------------------------------------------------------------------------

def _golden_err(arch, fmt, n_decode=3, S=21, T=8):
    from repro.models.registry import Model
    from repro.models.transformer import Runtime
    cfg = get_config(arch).reduced()
    cap = (cfg.n_experts / cfg.top_k) if cfg.is_moe else 1.25
    rt = Runtime(moe_capacity=cap)
    m = Model(cfg, rt)
    params = m.init(jax.random.PRNGKey(0))
    eng = KVNANDEngine(cfg, EngineConfig(page_tokens=T, kv_quant=fmt,
                                         kv_dtype="float32"), rt)
    B = 2
    toks = jax.random.randint(jax.random.PRNGKey(42), (B, S + n_decode), 0,
                              cfg.vocab_size, jnp.int32)
    logits_full, _ = m.forward(params, {"tokens": toks})
    lg, cache = eng.prefill(params, {"tokens": toks[:, :S]},
                            max_context=S + n_decode + 2)
    errs = [float(jnp.abs(lg - logits_full[:, S - 1]).max())]
    for t in range(n_decode):
        lg, cache = eng.decode_step(params, cache,
                                    toks[:, S + t:S + t + 1])
        errs.append(float(jnp.abs(lg - logits_full[:, S + t]).max()))
    return max(errs) / float(jnp.abs(logits_full).max())


@pytest.mark.parametrize("fmt", ["kv8", "kv4"])
@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "gemma3-12b"])
def test_engine_decode_quant_close_to_forward(arch, fmt):
    """Quantized decode (global + window pools) tracks the full forward
    within format tolerance; scales survive append across pages."""
    assert _golden_err(arch, fmt) < TOL[fmt]


def test_cache_spec_quant_leaves():
    cfg = get_config("gemma3-12b").reduced()
    spec = paged_kv.cache_spec(cfg, EngineConfig(page_tokens=16,
                                                 kv_quant="kv4"), 2, 128)
    assert spec["k_pages_g"][1] == jnp.uint8
    assert spec["k_pages_g"][0][4] == 8                   # packed token dim
    assert spec["k_scale_g"][0] == spec["k_pages_g"][0][:4]
    assert spec["k_scale_w"][1] == jnp.float32
    # bf16 default untouched
    spec0 = paged_kv.cache_spec(cfg, EngineConfig(page_tokens=16), 2, 128)
    assert "k_scale_g" not in spec0
    assert spec0["k_pages_g"][0][4] == 16
