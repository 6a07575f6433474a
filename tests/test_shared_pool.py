"""Shared-pool paged KV (§IV-D FTL mapping): token parity with the
stripe layout across formats/archs, capacity-proportional admission,
prefix-cache sharing with COW, and the table-indexed kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import EngineConfig, get_config
from repro.core import paged_kv
from repro.core.engine import KVNANDEngine
from repro.kernels.paged_attention import paged_attention_partial
from repro.models.registry import Model
from repro.models.transformer import Runtime
from repro.serving.scheduler import (ContinuousBatcher, Request,
                                     SpliceBatcher)

PROMPTS = [list(range(1, 8)), list(range(3, 24)), list(range(2, 13)),
           [5, 4, 3]]


def _model(arch="qwen1.5-0.5b"):
    cfg = get_config(arch).reduced()
    rt = Runtime()
    return cfg, rt, Model(cfg, rt).init(jax.random.PRNGKey(0))


def _drain(cfg, params, eng, prompts, *, slots=2, ctx=96, chunk=16,
           max_new=4):
    b = ContinuousBatcher(cfg, params, batch_slots=slots, max_context=ctx,
                          temperature=0.0, eng=eng,
                          prefill_chunk_tokens=chunk)
    for uid, p in enumerate(prompts):
        b.submit(Request(uid, list(p), max_new=max_new))
    done = b.run_to_completion()
    return {u: r.output for u, r in done.items()}, b


def _engs(**kw):
    stripe = EngineConfig(page_tokens=16, **kw)
    shared = EngineConfig(page_tokens=16, shared_pool=True, **kw)
    return stripe, shared


# ---------------------------------------------------------------------------
# token parity: shared pool == stripe layout, all formats + window ring
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [dict(kv_dtype="float32"),
                                dict(kv_quant="kv8"),
                                dict(kv_quant="kv4")],
                         ids=["f32", "kv8", "kv4"])
def test_shared_matches_stripe_formats(kw):
    cfg, rt, params = _model()
    stripe, shared = _engs(**kw)
    o1, _ = _drain(cfg, params, stripe, PROMPTS)
    o2, b2 = _drain(cfg, params, shared, PROMPTS)
    assert o1 == o2
    b2.alloc.check()
    # at drain only the prefix cache still holds pages — all of them
    # reclaimable, so the pool conserves capacity across request waves
    assert b2.alloc.live_count == b2.prefix_cache.evictable_pages()


def test_shared_matches_stripe_window_ring():
    """gemma3 local:global mix: both pools shared, ring through table_w."""
    cfg, rt, params = _model("gemma3-12b")
    prompts = PROMPTS + [list(range(1, 78))]     # > reduced window of 64
    stripe, shared = _engs(kv_dtype="float32")
    o1, _ = _drain(cfg, params, stripe, prompts)
    o2, b2 = _drain(cfg, params, shared, prompts)
    assert o1 == o2
    b2.alloc.check()
    b2.alloc_w.check()
    assert b2.alloc_w.live_count == 0            # rings fully reclaimed


def test_shared_matches_stripe_recurrent_prefix_archs():
    """hymba (meta-token prefix + hybrid state) via whole-prompt chunks."""
    cfg, rt, params = _model("hymba-1.5b")
    stripe, shared = _engs(kv_dtype="float32")
    o1, _ = _drain(cfg, params, stripe, PROMPTS[:2])
    o2, b2 = _drain(cfg, params, shared, PROMPTS[:2])
    assert o1 == o2
    assert b2.prefix_cache is None               # prefix sharing gated off


def test_oneshot_prefill_shared_matches_stripe():
    """Engine-level one-shot prefill + decode through the table."""
    cfg, rt, params = _model()
    toks = jnp.tile(jnp.arange(1, 22, dtype=jnp.int32)[None], (2, 1))
    outs = []
    for shared in (False, True):
        eng = KVNANDEngine(cfg, EngineConfig(
            page_tokens=16, kv_dtype="float32",
            shared_pool=shared), rt)
        lg, cache = eng.prefill(params, {"tokens": toks}, 96)
        seq = [np.asarray(lg)]
        tok = jnp.argmax(lg, -1)[:, None].astype(jnp.int32)
        for _ in range(3):
            lg, cache = eng.decode_step(params, cache, tok)
            seq.append(np.asarray(lg))
            tok = jnp.argmax(lg, -1)[:, None].astype(jnp.int32)
        outs.append(seq)
    for a, b in zip(*outs):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# capacity-proportional admission
# ---------------------------------------------------------------------------

def test_capacity_proportional_admission():
    """6 slots whose summed max_context stripes (6·8 = 48 pages) can NOT
    fit the 16-page pool are admitted concurrently and drain with outputs
    identical to the stripe layout."""
    cfg, rt, params = _model()
    shared = EngineConfig(page_tokens=16, kv_dtype="float32", shared_pool=True,
                          total_pages=16)
    prompts = [list(range(1 + i, 12 + i)) for i in range(6)]
    o2, b = _drain(cfg, params, shared, prompts, slots=6, ctx=128)
    assert len(o2) == 6
    assert b.stats["pool_total_pages"] == 16
    npg = -(-128 // 16)
    assert 6 * npg > b.stats["pool_total_pages"]   # old layout: impossible
    assert b.stats["pool_peak_pages"] <= 16
    b.alloc.check()
    stripe = EngineConfig(page_tokens=16, kv_dtype="float32")
    o1, _ = _drain(cfg, params, stripe, prompts, slots=6, ctx=128)
    assert o1 == o2


def test_admission_waits_for_pages_then_drains():
    """A pool two requests wide: the third waits, no deadlock, FIFO kept."""
    cfg, rt, params = _model()
    shared = EngineConfig(page_tokens=16, kv_dtype="float32", shared_pool=True,
                          total_pages=4)
    prompts = [list(range(1, 18))] * 3          # 2 pages each incl. max_new
    o, b = _drain(cfg, params, shared, prompts, slots=3, ctx=96)
    assert sorted(o) == [0, 1, 2]
    b.alloc.check()


def test_admission_discounts_pinned_cache_pages():
    """A prefix hit PINS the cached pages it maps, so admission must not
    count them as evictable slack: an exact repeat whose growth does not
    fit must WAIT (not crash the allocator mid-flight)."""
    cfg, rt, params = _model()
    shared = EngineConfig(page_tokens=16, kv_dtype="float32", shared_pool=True,
                          total_pages=10)
    b = ContinuousBatcher(cfg, params, batch_slots=2, max_context=160,
                          temperature=0.0, eng=shared,
                          prefill_chunk_tokens=16)
    prompt_a = list(range(1, 73))               # 72 tokens -> 5 cached pages
    b.submit(Request(0, prompt_a, max_new=8))
    b.run_to_completion()
    assert b.prefix_cache.evictable_pages() == 5
    # a live request holds the remaining free pages...
    b.submit(Request(1, list(range(200, 270)), max_new=8))
    # ...and an exact repeat with large growth cannot fund its fresh
    # pages from the cache pages it itself maps — it must defer
    b.submit(Request(2, prompt_a, max_new=32))
    done = b.run_to_completion()
    assert sorted(done) == [0, 1, 2]
    assert done[2].output[:8] == done[0].output
    b.alloc.check()


def test_submit_rejects_impossible_footprint():
    cfg, rt, params = _model()
    shared = EngineConfig(page_tokens=16, kv_dtype="float32", shared_pool=True,
                          total_pages=2)
    b = ContinuousBatcher(cfg, params, batch_slots=2, max_context=96,
                          eng=shared, prefill_chunk_tokens=16)
    with pytest.raises(ValueError, match="shared pool"):
        b.submit(Request(0, list(range(60)), max_new=8))


# ---------------------------------------------------------------------------
# prefix cache: shared-prefix trace, exact-repeat fork, COW
# ---------------------------------------------------------------------------

def test_prefix_cache_hits_with_unchanged_outputs():
    cfg, rt, params = _model()
    sysp = list(range(100, 132))                # 2 full shared pages
    prompts = [sysp + list(range(i * 7, i * 7 + 9)) for i in range(3)]
    prompts.append(list(prompts[0]))            # exact whole-prompt repeat
    stripe, shared = _engs(kv_dtype="float32")
    o1, _ = _drain(cfg, params, stripe, prompts, ctx=128)
    o2, b = _drain(cfg, params, shared, prompts, ctx=128)
    assert o1 == o2
    assert b.stats["prefix_hit_pages"] > 0
    assert b.stats["cow_copies"] > 0            # partial-page single-writer
    b.alloc.check()


def test_exact_repeat_skips_prefill_and_cows_partial_page():
    cfg, rt, params = _model()
    _, shared = _engs(kv_dtype="float32")
    b = ContinuousBatcher(cfg, params, batch_slots=2, max_context=96,
                          temperature=0.0, eng=shared,
                          prefill_chunk_tokens=16)
    p = list(range(1, 22))                      # 21 tokens: partial page 1
    b.submit(Request(0, p, max_new=4))
    b.run_to_completion()
    chunks_before = b.stats["prefill_chunks"]
    b.submit(Request(1, p, max_new=4))
    done = b.run_to_completion()
    assert done[0].output == done[1].output
    assert b.stats["prefill_chunks"] == chunks_before   # no recompute
    assert b.stats["cow_copies"] >= 2          # register COW + fork COW
    b.alloc.check()


def test_splice_batcher_fails_fast_on_shared_pool():
    cfg, rt, params = _model()
    _, shared = _engs(kv_dtype="float32")
    with pytest.raises(ValueError, match="stripe"):
        SpliceBatcher(cfg, params, batch_slots=2, max_context=96,
                      eng=shared)


# ---------------------------------------------------------------------------
# table-indexed kernel: shared Pallas index map == gathered oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", ["none", "kv8", "kv4"])
def test_shared_kernel_matches_gather_ref(fmt):
    from repro.core import quant

    B, K, G, NP, T, dh = 3, 2, 2, 4, 8, 16
    P = B * NP
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, K * G, dh))
    table = jnp.asarray(
        np.random.default_rng(0).permutation(P).reshape(B, NP), jnp.int32)
    base = jnp.broadcast_to((jnp.arange(NP) * T)[None], (B, NP))
    length = jnp.array([5, 17, 32], jnp.int32)
    kd = jax.random.normal(ks[1], (K, P, T, dh))
    vd = jax.random.normal(ks[2], (K, P, T, dh))
    ksc = vsc = None
    if fmt != "none":
        kd, ksc = quant.quantize_kv_page(kd, fmt)
        vd, vsc = quant.quantize_kv_page(vd, fmt)
    kw = dict(page_table=table, kv_quant=fmt, k_scale=ksc, v_scale=vsc)
    for window in (None, 12):
        o_ref, m_ref, l_ref = paged_attention_partial(
            q, kd, vd, base, length, impl="ref", window=window, **kw)
        o_pl, m_pl, l_pl = paged_attention_partial(
            q, kd, vd, base, length, impl="interpret", window=window, **kw)
        np.testing.assert_allclose(np.asarray(o_pl), np.asarray(o_ref),
                                   atol=3e-5, rtol=3e-5)
        np.testing.assert_allclose(np.asarray(l_pl), np.asarray(l_ref),
                                   atol=3e-5, rtol=3e-5)


def test_shared_chunk_fill_matches_stripe_chunk_fill():
    """Table-indirected chunk fills produce the same page bytes as the
    stripe fills (the slot's pages, gathered, are bit-identical)."""
    L, B, K, NP, T, dh = 2, 3, 2, 6, 8, 16
    S, slot, layer = 40, 1, 1
    kv = jax.random.normal(jax.random.PRNGKey(0), (B, S, K, dh))
    tb = jnp.asarray(
        np.random.default_rng(1).permutation(B * NP).reshape(B, NP),
        jnp.int32)
    for fmt in ("none", "kv8"):
        dt = paged_kv.quant.kv_storage_dtype(fmt) if fmt != "none" \
            else jnp.float32
        pool_a = jnp.zeros((L, B, K, NP, T, dh), dt)
        pool_b = jnp.zeros((L, K, B * NP, T, dh), dt)
        sc_a = jnp.zeros((L, B, K, NP), jnp.float32)
        sc_b = jnp.zeros((L, K, B * NP), jnp.float32)
        for c0 in range(0, S, 16):
            cl = min(16, S - c0)
            args = (jnp.asarray(layer), jnp.asarray(slot),
                    jnp.asarray(c0 // T), jnp.asarray(cl))
            argsh = (jnp.asarray(layer), tb[slot],
                     jnp.asarray(c0 // T), jnp.asarray(cl))
            if fmt == "none":
                pool_a = paged_kv.fill_chunk_global_at(
                    pool_a, kv[slot:slot + 1, c0:c0 + 16], *args)
                pool_b = paged_kv.fill_chunk_global_at_shared(
                    pool_b, kv[slot:slot + 1, c0:c0 + 16], argsh[0],
                    argsh[1], argsh[2], argsh[3])
            else:
                pool_a, sc_a = paged_kv.fill_chunk_global_at(
                    pool_a, kv[slot:slot + 1, c0:c0 + 16], *args,
                    scale=sc_a, kv_quant=fmt)
                pool_b, sc_b = paged_kv.fill_chunk_global_at_shared(
                    pool_b, kv[slot:slot + 1, c0:c0 + 16], argsh[0],
                    argsh[1], argsh[2], argsh[3], scale=sc_b,
                    kv_quant=fmt)
        np.testing.assert_array_equal(np.asarray(pool_b[:, :, tb[slot]]),
                                      np.asarray(pool_a[:, slot]))
        if fmt != "none":
            np.testing.assert_array_equal(np.asarray(sc_b[:, :, tb[slot]]),
                                          np.asarray(sc_a[:, slot]))
