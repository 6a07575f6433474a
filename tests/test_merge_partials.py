"""Property tests for the N-partial LSE merge core (`merge_partials`).

The core's contract: merging any grouping/ordering of locally-normalized
partials equals the one-shot softmax over the union of their pages, and
empty partials (m = NEG_INF, l = 0) are the identity.  Runs under
`tests/_hypothesis_compat` (seeded sweeps when hypothesis is absent).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core.quant import quantize_kv_page
from repro.kernels.paged_attention import (
    merge_partials,
    paged_attention_partial,
    paged_attention_partial_ref,
    resolve_partitions,
)
from repro.kernels.paged_attention.merge import NEG_INF


def _partials(rng, n, shape=(2, 8)):
    """n random locally-normalized partials: o [n,*shape,dh], m/l [n,*shape]."""
    dh = 16
    o = jnp.asarray(rng.normal(size=(n, *shape, dh)), jnp.float32)
    m = jnp.asarray(rng.normal(size=(n, *shape)) * 3.0, jnp.float32)
    l = jnp.asarray(rng.uniform(0.1, 50.0, size=(n, *shape)), jnp.float32)
    return o, m, l


def _close(a, b, tol=1e-4):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=tol, atol=tol)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(2, 7), seed=st.integers(0, 1000))
def test_merge_associativity(n, seed):
    """Folding a prefix first, then merging its result with the rest,
    equals one flat N-way merge (re-bracketing invariance)."""
    rng = np.random.default_rng(seed)
    o, m, l = _partials(rng, n)
    flat = merge_partials(o, m, l, axis=0)
    k = max(1, n // 2)
    head = merge_partials(o[:k], m[:k], l[:k], axis=0)
    regrouped = tuple(
        jnp.concatenate([h[None], t], axis=0)
        for h, t in zip(head, (o[k:], m[k:], l[k:])))
    nested = merge_partials(*regrouped, axis=0)
    for a, b in zip(flat, nested):
        _close(a, b)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(2, 8), seed=st.integers(0, 1000))
def test_merge_permutation_invariance(n, seed):
    rng = np.random.default_rng(seed)
    o, m, l = _partials(rng, n)
    ref = merge_partials(o, m, l, axis=0)
    perm = rng.permutation(n)
    got = merge_partials(o[perm], m[perm], l[perm], axis=0)
    for a, b in zip(ref, got):
        _close(a, b)


@settings(max_examples=15, deadline=None)
@given(n=st.integers(1, 6), n_empty=st.integers(1, 4),
       seed=st.integers(0, 1000))
def test_empty_partition_is_identity(n, n_empty, seed):
    """Partials over zero valid tokens (m = NEG_INF, l = 0) contribute
    nothing, wherever they sit in the stack."""
    rng = np.random.default_rng(seed)
    o, m, l = _partials(rng, n)
    ref = merge_partials(o, m, l, axis=0)
    eo = jnp.zeros((n_empty,) + o.shape[1:], o.dtype)
    em = jnp.full((n_empty,) + m.shape[1:], NEG_INF, m.dtype)
    el = jnp.zeros((n_empty,) + l.shape[1:], l.dtype)
    perm = rng.permutation(n + n_empty)
    got = merge_partials(jnp.concatenate([o, eo])[perm],
                         jnp.concatenate([m, em])[perm],
                         jnp.concatenate([l, el])[perm], axis=0)
    for a, b in zip(ref, got):
        _close(a, b)
    assert np.all(np.isfinite(np.asarray(got[0])))


def test_all_empty_merge_is_empty():
    """Merging only empty partials returns the empty partial: zero
    output, zero mass, finite everywhere — same as a single walk over an
    empty page set."""
    shape = (3, 4)
    o = jnp.zeros((5, *shape, 8))
    m = jnp.full((5, *shape), NEG_INF)
    l = jnp.zeros((5, *shape))
    oo, mm, ll = merge_partials(o, m, l, axis=0)
    assert np.all(np.asarray(oo) == 0.0)
    assert np.all(np.asarray(ll) == 0.0)
    assert np.all(np.isfinite(np.asarray(oo)))


def test_merge_axis_argument():
    rng = np.random.default_rng(0)
    o, m, l = _partials(rng, 4)
    ref = merge_partials(o, m, l, axis=0)
    got = merge_partials(jnp.moveaxis(o, 0, 2), jnp.moveaxis(m, 0, 2),
                         jnp.moveaxis(l, 0, 2), axis=2)
    for a, b in zip(ref, got):
        _close(a, b)


@pytest.mark.parametrize("kv_quant", ["none", "kv8", "kv4"])
@pytest.mark.parametrize("window", [None, 37])
def test_nway_merge_matches_one_shot_softmax(kv_quant, window):
    """Per-partition ref partials, merged through the core, reproduce the
    monolithic walk — for every pool format and the windowed layout."""
    rng = np.random.default_rng(7)
    B, K, G, NP, T, dh = 2, 2, 2, 8, 8, 16
    H = K * G
    q = jnp.asarray(rng.normal(size=(B, H, dh)), jnp.float32)
    kp = jnp.asarray(rng.normal(size=(B, K, NP, T, dh)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(B, K, NP, T, dh)), jnp.float32)
    base = jnp.arange(NP)[None, :].repeat(B, 0) * T
    length = jnp.array([NP * T - 3, NP * T // 2 + 1])
    ks = vs = None
    if kv_quant != "none":
        kp, ks = quantize_kv_page(kp, kv_quant)
        vp, vs = quantize_kv_page(vp, kv_quant)
    one_shot = paged_attention_partial_ref(
        q, kp, vp, base, length, window=window,
        kv_quant=kv_quant, k_scale=ks, v_scale=vs)
    for P in (2, 4, NP):
        npp = NP // P
        parts = []
        for i in range(P):
            sl = slice(i * npp, (i + 1) * npp)
            parts.append(paged_attention_partial_ref(
                q, kp[:, :, sl], vp[:, :, sl], base[:, sl], length,
                window=window, kv_quant=kv_quant,
                k_scale=None if ks is None else ks[:, :, sl],
                v_scale=None if vs is None else vs[:, :, sl]))
        merged = merge_partials(*map(jnp.stack, zip(*parts)), axis=0)
        for a, b in zip(one_shot, merged):
            _close(a, b, tol=3e-4)
    # and the public op's partitioned walk is the same computation
    o, m, l = paged_attention_partial(
        q, kp, vp, base, length, window=window, impl="ref",
        kv_quant=kv_quant, k_scale=ks, v_scale=vs, partitions=4)
    _close(one_shot[0].reshape(B, H, dh), o, tol=3e-4)


def test_resolve_partitions_contract():
    assert resolve_partitions(4, 16) == 4
    assert resolve_partitions(0, 64) == 1       # short walk stays whole
    assert resolve_partitions(0, 1568) == 16    # long walk splits
    assert resolve_partitions(0, 300) == 4      # halved to a divisor
    with pytest.raises(ValueError):
        resolve_partitions(5, 16)               # non-divisor is loud
    with pytest.raises(ValueError):
        resolve_partitions(-1, 16)
    with pytest.raises(ValueError):
        resolve_partitions(0, 0)
