"""Paged-KV substrate: layout, ring recycling, fills — incl. hypothesis
property tests over the page-mapping invariants (paper §IV-D)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs import EngineConfig, get_config
from repro.core import paged_kv
from repro.kernels.paged_attention import paged_to_dense


def test_layer_pattern_uniform():
    cfg = get_config("qwen2.5-32b")
    period, pattern = paged_kv.layer_pattern(cfg)
    assert period == 1 and pattern == (True,)


def test_layer_pattern_gemma3():
    cfg = get_config("gemma3-12b")
    period, pattern = paged_kv.layer_pattern(cfg)
    assert period == 6
    assert pattern == (False, False, False, False, False, True)


def test_layer_pattern_hymba():
    cfg = get_config("hymba-1.5b")
    period, pattern = paged_kv.layer_pattern(cfg)
    assert period == 16 and sum(pattern) == 1


@settings(max_examples=40, deadline=None)
@given(s=st.integers(1, 300), np_=st.integers(2, 12), t=st.integers(2, 16))
def test_window_page_positions_properties(s, np_, t):
    """Ring invariants: bases are page-aligned, distinct, cover the newest
    min(NP, ceil(S/T)) pages, and the newest page base == last page start."""
    vals = paged_kv.window_page_positions(s, np_, t)
    live = vals[vals >= 0]
    n_src = -(-s // t)
    assert len(live) == min(np_, n_src)
    assert np.all(live % t == 0)
    assert len(np.unique(live)) == len(live)
    assert (n_src - 1) * t in live                 # newest page present


def test_fill_prefill_at_roundtrip():
    B, S, K, dh, T, NP, L = 2, 50, 3, 8, 16, 8, 4
    kv = jax.random.normal(jax.random.PRNGKey(0), (B, S, K, dh))
    pool = jnp.zeros((L, B, K, NP, T, dh))
    pool = paged_kv.fill_prefill_at(pool, kv, jnp.asarray(2))
    base = jnp.broadcast_to((jnp.arange(NP) * T)[None], (B, NP))
    dense = paged_to_dense(pool[2], base, S)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(kv), atol=1e-6)
    assert float(jnp.abs(pool[1]).max()) == 0.0    # other layers untouched


def test_fill_window_at_keeps_newest():
    B, S, K, dh, T, NP, L = 1, 100, 2, 4, 8, 4, 2
    kv = jax.random.normal(jax.random.PRNGKey(0), (B, S, K, dh))
    pool = jnp.zeros((L, B, K, NP, T, dh))
    pool = paged_kv.fill_window_at(pool, kv, jnp.asarray(0))
    vals = paged_kv.window_page_positions(S, NP, T)
    base = jnp.broadcast_to(jnp.asarray(vals)[None], (B, NP))
    dense = paged_to_dense(pool[0], base, S)
    # newest NP*T window must match; everything older is zero
    keep_from = (int(np.max(vals)) // T - NP + 1) * T
    np.testing.assert_allclose(np.asarray(dense[:, max(keep_from, 0):]),
                               np.asarray(kv[:, max(keep_from, 0):]),
                               atol=1e-6)


@settings(max_examples=20, deadline=None)
@given(ctx=st.integers(10, 200), t=st.sampled_from([8, 16, 32]),
       shards=st.sampled_from([1, 4, 16]))
def test_cache_spec_page_rounding(ctx, t, shards):
    cfg = get_config("qwen1.5-0.5b").reduced()
    spec = paged_kv.cache_spec(cfg, EngineConfig(page_tokens=t), 2, ctx,
                               page_shards_g=shards)
    NP = spec["k_pages_g"][0][3]
    assert NP % shards == 0
    assert NP * t >= ctx


@pytest.mark.parametrize("layout", ["stripe", "shared"])
def test_ragged_append_matches_drop_scatter(layout):
    """The per-row in-place append writes what a mode="drop" scatter
    writes: active rows land their token, and a row at the drop sentinel
    changes no page, not even the last one (where an unguarded
    dynamic_update_slice would clamp it to)."""
    L, B, K, NP, T, dh, P, layer = 3, 4, 2, 4, 8, 16, 10, 2
    lead = (L, B, K, NP) if layout == "stripe" else (L, K, P)
    drop = NP if layout == "stripe" else P
    pool = jax.random.normal(jax.random.PRNGKey(0), lead + (T, dh))
    val = jax.random.normal(jax.random.PRNGKey(1), (B, K, dh))
    # rows 1 and 3 sit at the sentinel, which clamps onto the last page;
    # row 2 writes that page, at the slot row 3 would clamp onto
    phys = jnp.asarray([0, drop, drop - 1, drop], jnp.int32)
    slot = jnp.asarray([3, T - 1, 5, 5], jnp.int32)
    if layout == "stripe":
        want = pool.at[layer, jnp.arange(B), :, phys, slot].set(
            val, mode="drop")
        got = jax.jit(lambda p, ph, sl, v: paged_kv.append_token_inplace(
            p, jnp.int32(layer), ph, sl, v))(pool, phys, slot, val)
        for b in (1, 3):
            np.testing.assert_array_equal(np.asarray(got[:, b]),
                                          np.asarray(pool[:, b]))
    else:
        want = pool.at[layer, :, phys, slot].set(val, mode="drop")
        got = jax.jit(lambda p, ph, sl, v: paged_kv.append_global_shared(
            p, jnp.int32(layer), ph, sl, v))(pool, phys, slot, val)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    changed = np.argwhere(np.asarray(got != pool).any(-1))
    assert len(changed) == 2 * K            # rows 0 and 2 only
