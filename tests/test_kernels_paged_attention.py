"""Paged decode attention: ref + Pallas-interpret vs dense oracle, sweeping
page geometry, GQA widths, windows, ragged lengths, dtypes — and the
split-page `partitions` axis against the monolithic walk."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant import quantize_kv_page
from repro.kernels.flash_attention import dense_attention_ref
from repro.kernels.paged_attention import (paged_attention_partial,
                                           paged_chunk_attention)

SWEEP = [
    # B, K, G, NP, T, dh, lengths, window, dtype
    (2, 3, 4, 8, 16, 32, (100, 37), None, jnp.float32),
    (2, 3, 4, 8, 16, 32, (100, 37), 24, jnp.float32),
    (1, 8, 1, 4, 8, 64, (30,), None, jnp.float32),
    (2, 2, 8, 16, 8, 16, (128, 5), None, jnp.float32),
    (1, 5, 5, 8, 16, 64, (99,), 40, jnp.float32),
    (2, 4, 2, 8, 32, 128, (200, 256), None, jnp.bfloat16),
]


def _build(B, K, NP, T, dh, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    kd = jax.random.normal(ks[0], (B, NP * T, K, dh), jnp.float32)
    vd = jax.random.normal(ks[1], (B, NP * T, K, dh), jnp.float32)
    k_pages = kd.reshape(B, NP, T, K, dh).transpose(0, 3, 1, 2, 4)
    v_pages = vd.reshape(B, NP, T, K, dh).transpose(0, 3, 1, 2, 4)
    base = jnp.broadcast_to((jnp.arange(NP) * T)[None], (B, NP)
                            ).astype(jnp.int32)
    return (kd.astype(dtype), vd.astype(dtype),
            k_pages.astype(dtype), v_pages.astype(dtype), base)


@pytest.mark.parametrize("case", SWEEP)
@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_vs_dense(case, impl):
    B, K, G, NP, T, dh, lengths, window, dtype = case
    H = K * G
    kd, vd, kp, vp, base = _build(B, K, NP, T, dh, dtype)
    q = jax.random.normal(jax.random.PRNGKey(9), (B, H, dh), jnp.float32
                          ).astype(dtype)
    length = jnp.asarray(lengths, jnp.int32)
    o, m, l = paged_attention_partial(q, kp, vp, base, length,
                                      window=window, impl=impl,
                                      pages_per_block=4)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    for b in range(B):
        L = int(lengths[b])
        ref = dense_attention_ref(
            q[b:b + 1, None].astype(jnp.float32),
            kd[b:b + 1, :L].astype(jnp.float32),
            vd[b:b + 1, :L].astype(jnp.float32),
            causal=True, window=window, q_offset=L - 1)
        np.testing.assert_allclose(np.asarray(o[b], np.float32),
                                   np.asarray(ref[0, 0]), atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# split-page `partitions` parity: every entry point, every pool format,
# every layout, partitions in {1, 4, NP} — identical math to the
# monolithic walk (partitions resolve through the same merge core the
# cross-device combine uses).

def _quantize(kp, vp, fmt):
    if fmt == "none":
        return kp, vp, None, None
    kq, ks = quantize_kv_page(kp, fmt)
    vq, vs = quantize_kv_page(vp, fmt)
    return kq, vq, ks, vs


def _shared_pool(kp, vp, ks, vs, seed=3):
    """Scatter a striped [B,K,NP,...] pool into a shared [K,P_total,...]
    pool behind a random per-slot page table."""
    B, K, NP = kp.shape[:3]
    Pt = B * NP + 4
    rng = np.random.default_rng(seed)
    table = jnp.asarray(rng.permutation(Pt)[:B * NP].reshape(B, NP),
                        jnp.int32)
    def scatter(pages):
        pool = jnp.zeros((K, Pt) + pages.shape[3:], pages.dtype)
        for b in range(B):
            pool = pool.at[:, table[b]].set(pages[b])
        return pool
    kpool, vpool = scatter(kp), scatter(vp)
    kspool = None if ks is None else scatter(ks)
    vspool = None if vs is None else scatter(vs)
    return kpool, vpool, kspool, vspool, table


PARITY_FMTS = ["none", "kv8", "kv4"]


@pytest.mark.parametrize("fmt", PARITY_FMTS)
@pytest.mark.parametrize("layout", ["striped", "shared"])
@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_decode_partitions_parity(fmt, layout, impl):
    B, K, G, NP, T, dh = 2, 2, 4, 16, 8, 32
    H = K * G
    window = 40 if fmt == "none" else None
    _, _, kp, vp, base = _build(B, K, NP, T, dh, jnp.float32)
    kp, vp, ks, vs = _quantize(kp, vp, fmt)
    table = None
    if layout == "shared":
        kp, vp, ks, vs, table = _shared_pool(kp, vp, ks, vs)
    q = jax.random.normal(jax.random.PRNGKey(9), (B, H, dh))
    length = jnp.asarray([NP * T - 3, NP * T // 2 + 1], jnp.int32)
    kw = dict(window=window, impl=impl, kv_quant=fmt,
              k_scale=ks, v_scale=vs, page_table=table)
    ref = paged_attention_partial(q, kp, vp, base, length,
                                  partitions=1, **kw)
    for P in (4, NP):
        got = paged_attention_partial(q, kp, vp, base, length,
                                      partitions=P, **kw)
        for a, b in zip(ref, got):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("fmt", PARITY_FMTS)
@pytest.mark.parametrize("layout", ["striped", "shared"])
@pytest.mark.parametrize("mode", ["chunk", "verify", "one_shot"])
def test_chunk_partitions_parity(fmt, layout, mode):
    """The three multi-token shapes: chunked prefill (scalar start),
    speculative verify (per-row start, per-row q_pos) and one-shot
    prefill from position 0."""
    B, K, G, NP, T, dh, S = 2, 2, 2, 8, 8, 16, 4
    H = K * G
    _, _, kp, vp, base = _build(B, K, NP, T, dh, jnp.float32)
    kp, vp, ks, vs = _quantize(kp, vp, fmt)
    table = None
    if layout == "shared":
        kp, vp, ks, vs, table = _shared_pool(kp, vp, ks, vs)
    q = jax.random.normal(jax.random.PRNGKey(11), (B, S, H, dh))
    if mode == "chunk":
        start = jnp.int32(NP * T // 2)
        q_pos = start + jnp.arange(S)
    elif mode == "verify":
        start = jnp.asarray([NP * T - S - 1, NP * T // 3], jnp.int32)
        q_pos = start[:, None] + jnp.arange(S)[None, :]
    else:
        start = jnp.int32(0)
        q_pos = jnp.arange(S)
    kw = dict(window=None, kv_quant=fmt, k_scale=ks, v_scale=vs,
              page_table=table)
    ref = paged_chunk_attention(q, kp, vp, base, start, q_pos,
                                partitions=1, **kw)
    for P in (4, NP):
        got = paged_chunk_attention(q, kp, vp, base, start, q_pos,
                                    partitions=P, **kw)
        for a, b in zip(ref, got):
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       atol=5e-4, rtol=5e-4)


def test_unknown_impl_raises():
    B, K, G, NP, T, dh = 1, 2, 2, 4, 8, 16
    _, _, kp, vp, base = _build(B, K, NP, T, dh, jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(0), (B, K * G, dh))
    length = jnp.asarray([20], jnp.int32)
    with pytest.raises(ValueError, match="unknown attention impl"):
        paged_attention_partial(q, kp, vp, base, length, impl="oracle")
    with pytest.raises(ValueError, match="unknown attention impl"):
        paged_chunk_attention(q[:, None], kp, vp, base, jnp.int32(0),
                              jnp.arange(1), impl="chunked")


def test_pages_per_block_degradation_is_loud():
    """A blocking request the page count cannot honor raises instead of
    silently serializing page-at-a-time; explicit ppb=1 still works."""
    B, K, G, NP, T, dh = 1, 2, 2, 7, 8, 16   # 7 pages: no even divisor
    _, _, kp, vp, base = _build(B, K, NP, T, dh, jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(0), (B, K * G, dh))
    length = jnp.asarray([50], jnp.int32)
    with pytest.raises(ValueError, match="pages_per_block"):
        paged_attention_partial(q, kp, vp, base, length, impl="interpret",
                                pages_per_block=4)
    o, m, l = paged_attention_partial(q, kp, vp, base, length,
                                      impl="interpret", pages_per_block=1)
    o_ref, m_ref, l_ref = paged_attention_partial(q, kp, vp, base, length,
                                                  impl="ref")
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)


def test_partial_stats_merge():
    """Splitting the page pool across two 'devices' and merging (m, l)
    reproduces the full attention — the paper's NPU aggregation."""
    from repro.core.seqpar import merge_two
    B, K, G, NP, T, dh = 1, 2, 2, 8, 8, 32
    H = K * G
    kd, vd, kp, vp, base = _build(B, K, NP, T, dh, jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(5), (B, H, dh))
    length = jnp.asarray([60], jnp.int32)
    o_full, _, _ = paged_attention_partial(q, kp, vp, base, length)
    half = NP // 2
    o1, m1, l1 = paged_attention_partial(q, kp[:, :, :half],
                                         vp[:, :, :half], base[:, :half],
                                         length)
    o2, m2, l2 = paged_attention_partial(q, kp[:, :, half:],
                                         vp[:, :, half:], base[:, half:],
                                         length)
    o, _, _ = merge_two(o1, m1, l1, o2, m2, l2)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_full),
                               atol=2e-5, rtol=2e-5)


def test_empty_shard_is_safe():
    """A shard holding no valid pages contributes zero weight."""
    from repro.core.seqpar import merge_two
    B, K, G, NP, T, dh = 1, 2, 2, 4, 8, 16
    kd, vd, kp, vp, base = _build(B, K, NP, T, dh, jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(5), (B, K * G, dh))
    length = jnp.asarray([20], jnp.int32)
    o_full, m_full, l_full = paged_attention_partial(q, kp, vp, base, length)
    empty_base = jnp.full_like(base, -(10 ** 9))
    o2, m2, l2 = paged_attention_partial(q, kp, vp, empty_base, length)
    assert float(l2.max()) == 0.0
    o, _, _ = merge_two(o_full, m_full, l_full, o2, m2, l2)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_full),
                               atol=1e-6)


@pytest.mark.parametrize("impl", ["ref", "interpret"])
@pytest.mark.parametrize("partitions", [1, 2])
@pytest.mark.parametrize("kv_quant", ["none", "kv8"])
@pytest.mark.parametrize("layout", ["striped", "shared"])
def test_stacked_pool_at_layer_matches_layer_slice(layout, kv_quant,
                                                   partitions, impl):
    """The walk over layer l of a stacked pool (layer index traced, pool
    read in place) returns exactly what the call on pool[l] returns."""
    L, B, K, G, NP, T, dh, P, layer = 3, 2, 2, 2, 8, 8, 32, 20, 1
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    lead = (L, B, K, NP) if layout == "striped" else (L, K, P)
    kp = jax.random.normal(ks[0], lead + (T, dh), jnp.float32)
    vp = jax.random.normal(ks[1], lead + (T, dh), jnp.float32)
    q = jax.random.normal(ks[2], (B, K * G, dh), jnp.float32)
    length = jnp.asarray([61, 23], jnp.int32)
    base = jnp.broadcast_to((jnp.arange(NP) * T)[None], (B, NP)
                            ).astype(jnp.int32)
    kw = dict(impl=impl, partitions=partitions, pages_per_block=2)
    if layout == "shared":
        kw["page_table"] = jax.random.permutation(ks[3], P)[:B * NP
                                                            ].reshape(B, NP)
    if kv_quant == "none":
        kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
    else:
        (kp, sk), (vp, sv) = (quantize_kv_page(kp, kv_quant),
                              quantize_kv_page(vp, kv_quant))
        kw.update(kv_quant=kv_quant, k_scale=sk[layer], v_scale=sv[layer])

    stacked = jax.jit(lambda kp, vp, lyr: paged_attention_partial(
        q, kp, vp, base, length, layer=lyr, **kw))(kp, vp, jnp.int32(layer))
    sliced = jax.jit(lambda kp, vp: paged_attention_partial(
        q, kp, vp, base, length, **kw))(kp[layer], vp[layer])
    for got, want in zip(stacked, sliced):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# the folded, clamped walk: Kh kv-heads per grid step, and no dead block
# fetched or scored — against the ref path and the dense oracle, on rows
# whose live blocks end anywhere in the walk.

def _kernel_walk(q, kp, vp, base, length, *, table=None, fmt="none",
                 ks=None, vs=None, partitions=1, window=None,
                 pages_per_block=4, heads="all"):
    """The interpret-mode kernel called directly (so the test can force
    Kh < K through its VMEM budget), partials merged: (o [B, H, dh],
    m [B, H], l [B, H]) like `paged_attention_partial`."""
    from repro.kernels.paged_attention import kernel as kmod
    from repro.kernels.paged_attention import merge_partials
    B, H, dh = q.shape
    K = kp.shape[0] if table is not None else kp.shape[1]
    G = H // K
    kw = dict(window=window, interpret=True, kv_quant=fmt, k_scale=ks,
              v_scale=vs, partitions=partitions)
    if table is not None:
        head_bytes = kp.shape[-2] * dh * kp.dtype.itemsize
    else:
        head_bytes = pages_per_block * kp.shape[-2] * dh * kp.dtype.itemsize
    if heads == "split":        # room for two heads' K+V, double-buffered
        kw["kv_vmem_bytes"] = 4 * 2 * head_bytes
        assert kmod._heads_per_step(K, head_bytes, kw["kv_vmem_bytes"]) \
            == 2 < K
    if table is not None:
        o, m, l = kmod.paged_attention_pallas_shared(
            q.reshape(B, K, G, dh), kp, vp, table, base, length, **kw)
    else:
        o, m, l = kmod.paged_attention_pallas(
            q.reshape(B, K, G, dh), kp, vp, base, length,
            pages_per_block=pages_per_block, **kw)
    if partitions > 1:
        o, m, l = merge_partials(o, m, l, axis=2)
    return o.reshape(B, H, dh), m.reshape(B, H), l.reshape(B, H)


def _check_walk(got, q, kp, vp, base, length, kd, vd, *, table=None,
                fmt="none", ks=None, vs=None, window=None):
    """`got` against the ref path (o, m, l, every row) and, unquantized,
    against the dense oracle (o, rows holding a key)."""
    want = paged_attention_partial(q, kp, vp, base, length, window=window,
                                   impl="ref", kv_quant=fmt, k_scale=ks,
                                   v_scale=vs, page_table=table)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-4, rtol=5e-4)
    if fmt != "none":
        return
    for b, L in enumerate(np.asarray(length)):
        if L == 0:
            assert float(jnp.max(got[2][b])) == 0.0    # the empty partial
            continue
        ref = dense_attention_ref(q[b:b + 1, None], kd[b:b + 1, :L],
                                  vd[b:b + 1, :L], causal=True,
                                  window=window, q_offset=int(L) - 1)
        np.testing.assert_allclose(np.asarray(got[0][b]),
                                   np.asarray(ref[0, 0]),
                                   atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("heads", ["all", "split"])
@pytest.mark.parametrize("fmt", PARITY_FMTS)
@pytest.mark.parametrize("layout", ["striped", "shared"])
def test_folded_walk_skips_dead_blocks(layout, fmt, heads):
    """Rows of 0 keys, one key, exactly one 4-page block, the full stripe,
    and 40 keys — whose second partition (pages 8-15) holds no live
    block and finalizes as the empty partial."""
    B, K, G, NP, T, dh = 5, 4, 2, 16, 8, 32
    kd, vd, kp, vp, base = _build(B, K, NP, T, dh, jnp.float32)
    kp, vp, ks, vs = _quantize(kp, vp, fmt)
    table = None
    if layout == "shared":
        kp, vp, ks, vs, table = _shared_pool(kp, vp, ks, vs)
    q = jax.random.normal(jax.random.PRNGKey(7), (B, K * G, dh))
    length = jnp.asarray([0, 1, 4 * T, NP * T, 40], jnp.int32)
    kw = dict(table=table, fmt=fmt, ks=ks, vs=vs)
    got = _kernel_walk(q, kp, vp, base, length, partitions=2, heads=heads,
                       **kw)
    _check_walk(got, q, kp, vp, base, length, kd, vd, **kw)


@pytest.mark.parametrize("fmt", ["none", "kv8"])
@pytest.mark.parametrize("layout", ["striped", "shared"])
def test_folded_walk_window_ring_before_and_after_wrap(layout, fmt):
    """A window ring of 8 pages (a 40-key window at 8-token pages, as
    `cache_spec` sizes it): a row that has not wrapped (30 keys: one live
    block of two) and rows that have (100 and 129 keys: every block
    live, the oldest pages outside the window)."""
    from repro.core.paged_kv import window_page_positions
    B, K, G, NPw, T, dh, window = 3, 2, 4, 8, 8, 32, 40
    lengths = (30, 100, 129)
    S = max(lengths)
    ks_ = jax.random.split(jax.random.PRNGKey(5), 3)
    kd = jax.random.normal(ks_[0], (B, S, K, dh), jnp.float32)
    vd = jax.random.normal(ks_[1], (B, S, K, dh), jnp.float32)
    base = np.stack([window_page_positions(L, NPw, T) for L in lengths])
    pos = base[:, :, None] + np.arange(T)[None, None]         # [B, NPw, T]
    idx = np.clip(pos, 0, S - 1)
    rows = np.arange(B)[:, None, None]

    def ring(dense):          # [B, S, K, dh] -> [B, K, NPw, T, dh]
        return jnp.asarray(np.asarray(dense)[rows, idx]).transpose(
            0, 3, 1, 2, 4)
    kp, vp = ring(kd), ring(vd)
    kp, vp, ks, vs = _quantize(kp, vp, fmt)
    table = None
    if layout == "shared":
        kp, vp, ks, vs, table = _shared_pool(kp, vp, ks, vs)
    base = jnp.asarray(base, jnp.int32)
    length = jnp.asarray(lengths, jnp.int32)
    q = jax.random.normal(ks_[2], (B, K * G, dh))
    kw = dict(table=table, fmt=fmt, ks=ks, vs=vs, window=window)
    got = _kernel_walk(q, kp, vp, base, length, **kw)
    _check_walk(got, q, kp, vp, base, length, kd, vd, **kw)
