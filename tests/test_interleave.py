"""Interleaved chunked prefill: token parity with the splice baseline
(f32 + kv8, dense + window-ring + recurrent + prefix archs), freedom from
decode starvation under a full admission queue, chunked quant fill parity
with the one-shot prefill fill, and the engine-level chunk oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import EngineConfig, get_config
from repro.core import paged_kv
from repro.core.engine import KVNANDEngine
from repro.models.registry import Model
from repro.models.transformer import Runtime
from repro.serving.scheduler import (ContinuousBatcher, Request,
                                     SpliceBatcher, _splice_slot_ref)

ARCH = "qwen1.5-0.5b"

F32 = dict(page_tokens=16, uniform_lengths=False, kv_dtype="float32")
KV8 = dict(page_tokens=16, uniform_lengths=False, kv_quant="kv8")

PROMPTS = [list(range(1, 8)), list(range(3, 24)), list(range(2, 13)),
           [5, 4, 3]]


def _model(arch=ARCH):
    cfg = get_config(arch).reduced()
    rt = Runtime()
    return cfg, rt, Model(cfg, rt).init(jax.random.PRNGKey(0))


def _drain(cls, cfg, params, prompts, *, eng=None, max_new=5, slots=2,
           ctx=96, chunk=16):
    b = cls(cfg, params, batch_slots=slots, max_context=ctx,
            temperature=0.0, eng=eng, prefill_chunk_tokens=chunk)
    for uid, p in enumerate(prompts):
        b.submit(Request(uid, list(p), max_new=max_new))
    done = b.run_to_completion()
    return {u: r.output for u, r in done.items()}, b


# ---------------------------------------------------------------------------
# scheduler-level parity: interleaved == splice baseline, token for token
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("eng_kw", [F32, KV8], ids=["f32", "kv8"])
def test_interleaved_matches_splice(eng_kw):
    """Golden-engine configs (f32 and kv8): the interleaved scheduler must
    produce token-identical outputs to the splice-based path."""
    cfg, rt, params = _model()
    o1, b1 = _drain(ContinuousBatcher, cfg, params, PROMPTS,
                    eng=EngineConfig(**eng_kw))
    o2, b2 = _drain(SpliceBatcher, cfg, params, PROMPTS,
                    eng=EngineConfig(**eng_kw))
    assert o1 == o2
    assert b1.stats["decode_stall_tokens"] == 0
    assert b2.stats["decode_stall_tokens"] > 0
    assert b1.stats["prefill_chunks"] > len(PROMPTS)  # genuinely chunked


def test_interleaved_matches_splice_window():
    """gemma3: window-ring chunk fills + past-window partials across
    chunk boundaries (prompt longer than the ring)."""
    cfg, rt, params = _model("gemma3-12b")
    prompts = PROMPTS + [list(range(1, 78))]       # > reduced window of 64
    o1, _ = _drain(ContinuousBatcher, cfg, params, prompts, max_new=4)
    o2, _ = _drain(SpliceBatcher, cfg, params, prompts, max_new=4)
    assert o1 == o2


@pytest.mark.parametrize("arch", ["rwkv6-3b", "hymba-1.5b"])
def test_interleaved_recurrent_and_prefix(arch):
    """ssm/hybrid (and meta-token prefix) archs prefill as ONE exact
    whole-prompt chunk — still spliceless, still in place."""
    cfg, rt, params = _model(arch)
    o1, b1 = _drain(ContinuousBatcher, cfg, params, PROMPTS, max_new=4)
    o2, _ = _drain(SpliceBatcher, cfg, params, PROMPTS, max_new=4)
    assert o1 == o2
    assert b1.stats["prefill_chunks"] == len(PROMPTS)


def test_splice_never_called_from_interleaved_step(monkeypatch):
    """The interleaved scheduler must not touch the splice path at all."""
    import repro.serving.scheduler as sched

    def boom(*a, **k):
        raise AssertionError("_splice_slot reached from interleaved step")

    monkeypatch.setattr(sched, "_splice_slot", boom)
    cfg, rt, params = _model()
    outs, _ = _drain(ContinuousBatcher, cfg, params, PROMPTS[:2])
    assert sorted(outs) == [0, 1]


# ---------------------------------------------------------------------------
# no decode starvation: a full queue cannot stall active decoders
# ---------------------------------------------------------------------------

def test_no_decode_starvation_under_full_queue():
    cfg, rt, params = _model()
    b = ContinuousBatcher(cfg, params, batch_slots=2, max_context=96,
                          temperature=0.0, prefill_chunk_tokens=16)
    for uid in range(6):
        b.submit(Request(uid, list(range(1, 40)), max_new=6))
    overlapped = 0
    while b.queue or any(r is not None for r in b.slots):
        ready = {i: len(b.slots[i].output) for i, r in enumerate(b.slots)
                 if r is not None and i not in b._prefill_live}
        uid_of = {i: b.slots[i].uid for i in ready}
        chunks_before = b.stats["prefill_chunks"]
        b.step()
        did_chunk = b.stats["prefill_chunks"] > chunks_before
        for i, n0 in ready.items():
            req = (b.slots[i] if b.slots[i] is not None
                   and b.slots[i].uid == uid_of[i]
                   else b.completed[uid_of[i]])
            # every decode-ready slot advanced this step, prefill or not
            assert len(req.output) == n0 + 1
            if did_chunk:
                overlapped += 1
    assert overlapped > 0           # prefill genuinely shared steps
    assert b.stats["decode_stall_tokens"] == 0
    assert len(b.completed) == 6


# ---------------------------------------------------------------------------
# chunked quantized fills == one-shot prefill fills (page for page)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", ["kv8", "kv4"])
def test_chunked_quant_fill_matches_oneshot(fmt):
    """Page-aligned chunk fills must reproduce `fill_prefill_at_quant`
    bit-for-bit on every page holding real tokens (same codes + scales):
    requantization granularity is the page, not the chunk."""
    L, B, K, NP, T, dh = 2, 3, 2, 6, 8, 16
    Ts = T // 2 if fmt == "kv4" else T
    S, slot, layer, chunk = 40, 1, 1, 16
    kv = jax.random.normal(jax.random.PRNGKey(0), (B, S, K, dh))
    dt = paged_kv.quant.kv_storage_dtype(fmt)

    pool_a = jnp.zeros((L, B, K, NP, Ts, dh), dt)
    scale_a = jnp.zeros((L, B, K, NP), jnp.float32)
    pool_a, scale_a = paged_kv.fill_prefill_at_quant(
        pool_a, scale_a, kv, jnp.asarray(layer), fmt)

    pool_b = jnp.zeros((L, B, K, NP, Ts, dh), dt)
    scale_b = jnp.zeros((L, B, K, NP), jnp.float32)
    for c0 in range(0, S, chunk):
        cl = min(chunk, S - c0)
        pool_b, scale_b = paged_kv.fill_chunk_global_at(
            pool_b, kv[slot:slot + 1, c0:c0 + chunk], jnp.asarray(layer),
            jnp.asarray(slot), jnp.asarray(c0 // T), jnp.asarray(cl),
            scale=scale_b, kv_quant=fmt)

    n_pages = -(-S // T)
    np.testing.assert_array_equal(
        np.asarray(pool_a[layer, slot, :, :n_pages]),
        np.asarray(pool_b[layer, slot, :, :n_pages]))
    np.testing.assert_array_equal(
        np.asarray(scale_a[layer, slot, :, :n_pages]),
        np.asarray(scale_b[layer, slot, :, :n_pages]))
    # other slots' stripes untouched by the chunk fills
    assert float(jnp.abs(pool_b[:, 0].astype(jnp.float32)).max()) == 0.0
    assert float(jnp.abs(pool_b[:, 2].astype(jnp.float32)).max()) == 0.0


def test_chunk_window_fill_matches_ring():
    """Ring chunk fills reproduce the one-shot window fill for the pages
    still inside the ring (newest NP source pages)."""
    L, B, K, NP, T, dh = 2, 2, 2, 3, 8, 16
    S, slot, layer = 40, 0, 1
    kv = jax.random.normal(jax.random.PRNGKey(1), (B, S, K, dh))
    pool_a = jnp.zeros((L, B, K, NP, T, dh))
    pool_a = paged_kv.fill_window_at(pool_a, kv, jnp.asarray(layer))
    pool_b = jnp.zeros((L, B, K, NP, T, dh))
    for c0 in range(0, S, 16):
        cl = min(16, S - c0)
        pool_b = paged_kv.fill_chunk_window_at(
            pool_b, kv[slot:slot + 1, c0:c0 + 16], jnp.asarray(layer),
            jnp.asarray(slot), jnp.asarray(c0 // T), jnp.asarray(cl))
    np.testing.assert_allclose(np.asarray(pool_a[layer, slot]),
                               np.asarray(pool_b[layer, slot]), atol=0)


def test_chunk_window_fill_padded_chunk_wider_than_ring():
    """A mostly-padding chunk spanning more pages than the ring must still
    land its few VALID pages (a trailing padding page may not shadow the
    valid page NP positions older in the ring)."""
    L, B, K, NP, T, dh = 1, 1, 1, 3, 8, 4
    C, cl = 48, 1                      # 6 chunk pages, only page 0 valid
    kv = jax.random.normal(jax.random.PRNGKey(2), (1, C, K, dh))
    pool = jnp.zeros((L, B, K, NP, T, dh))
    pool = paged_kv.fill_chunk_window_at(
        pool, kv, jnp.asarray(0), jnp.asarray(0), jnp.asarray(0),
        jnp.asarray(cl))
    np.testing.assert_allclose(np.asarray(pool[0, 0, :, 0, :1]),
                               np.asarray(kv[0, :1].transpose(1, 0, 2)),
                               atol=0)
    # padding pages (never valid) left the rest of the ring untouched
    assert float(jnp.abs(pool[0, 0, :, 1:]).max()) == 0.0


# ---------------------------------------------------------------------------
# engine level: chunked prefill == full prefill + splice, then decode
# ---------------------------------------------------------------------------

def test_engine_prefill_chunk_matches_full():
    cfg, rt, params = _model()
    eng = KVNANDEngine(cfg, EngineConfig(page_tokens=8, kv_dtype="float32",
                                         uniform_lengths=False), rt)
    B, ctx, n, C = 3, 64, 21, 16
    prompt = jnp.arange(1, n + 1, dtype=jnp.int32)[None]
    lg_ref, c1 = eng.prefill(params, {"tokens": prompt}, ctx)
    cache_ref = _splice_slot_ref(eng.init_cache(B, ctx), c1, 1)
    cache = eng.init_cache(B, ctx)
    padded = -(-n // C) * C
    toks = jnp.concatenate([prompt[0], jnp.zeros(padded - n, jnp.int32)])
    for c0 in range(0, padded, C):
        cl = min(C, n - c0)
        lg, cache = eng.prefill_chunk(
            params, cache, {"tokens": toks[None, c0:c0 + C]},
            jnp.asarray(1), jnp.asarray(c0), jnp.asarray(cl),
            first=(c0 == 0))
    scale = float(jnp.abs(lg_ref).max())
    assert float(jnp.abs(lg - lg_ref).max()) / scale < 2e-4
    # decode continues identically from both caches (slot 1 active only)
    act = jnp.array([False, True, False])
    toks_d = jnp.array([[3], [11], [4]], jnp.int32)
    for _ in range(3):
        l1, cache = eng.decode_step(params, cache, toks_d, active=act)
        l2, cache_ref = eng.decode_step(params, cache_ref, toks_d)
        assert float(jnp.abs(l1[1] - l2[1]).max()) / scale < 2e-4


def test_engine_active_mask_freezes_inactive_slots():
    """A decode step with an active mask must leave inactive slots'
    stripes and lengths bit-identical."""
    cfg, rt, params = _model()
    eng = KVNANDEngine(cfg, EngineConfig(page_tokens=8, kv_dtype="float32",
                                         uniform_lengths=False), rt)
    cache = eng.init_cache(2, 64)
    _, cache = eng.prefill_chunk(
        params, cache, {"tokens": jnp.arange(1, 17, dtype=jnp.int32)[None]},
        jnp.asarray(0), jnp.asarray(0), jnp.asarray(16), first=True)
    before_k = np.asarray(cache.k_pages_g[:, 1]).copy()
    toks = jnp.array([[3], [9]], jnp.int32)
    _, cache2 = eng.decode_step(params, cache, toks,
                                active=jnp.array([True, False]))
    np.testing.assert_array_equal(np.asarray(cache2.k_pages_g[:, 1]),
                                  before_k)
    assert int(cache2.lengths[1]) == int(cache.lengths[1])
    assert int(cache2.lengths[0]) == int(cache.lengths[0]) + 1


def test_decode_step_kernel_in_place_matches_ref():
    """The decode step whose kernel reads each layer of the stacked pools
    in place (Pallas interpret) matches the jnp ref path, with one row
    inactive: same logits for the active rows, same pools, and the
    inactive row's stripe and length untouched."""
    cfg, rt, params = _model()
    kw = dict(page_tokens=8, kv_dtype="float32", uniform_lengths=False)
    B, ctx = 3, 64
    cache0 = None
    for impl in ("ref", "interpret"):
        eng = KVNANDEngine(cfg, EngineConfig(attn_impl=impl, **kw), rt)
        if cache0 is None:
            cache0 = eng.init_cache(B, ctx)
            for b, n in ((0, 13), (1, 5), (2, 21)):
                _, cache0 = eng.prefill_chunk(
                    params, cache0,
                    {"tokens": jnp.arange(1, n + 1, dtype=jnp.int32)[None]},
                    jnp.asarray(b), jnp.asarray(0), jnp.asarray(n),
                    first=True)
        cache, logits = cache0, []
        act = jnp.array([True, False, True])
        for tok in (3, 7):
            lg, cache = eng.decode_step(
                params, cache, jnp.full((B, 1), tok, jnp.int32), active=act)
            logits.append(lg)
        if impl == "ref":
            want, want_cache = logits, cache
    scale = float(jnp.abs(want[0]).max())
    for got, ref in zip(logits, want):
        assert float(jnp.abs(got[::2] - ref[::2]).max()) / scale < 2e-5
    for pool in ("k_pages_g", "v_pages_g"):
        np.testing.assert_allclose(np.asarray(getattr(cache, pool)),
                                   np.asarray(getattr(want_cache, pool)),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_array_equal(
            np.asarray(getattr(cache, pool)[:, 1]),
            np.asarray(getattr(cache0, pool)[:, 1]))
    np.testing.assert_array_equal(np.asarray(cache.lengths), [15, 5, 23])
