"""Trinity-Mini (AfMoE) through the serving path against the plain
reference `bench/reference_afmoe.py`, at a small seeded size on the CPU:
d 64, 6 layers (2 dense-FFN, then 4 MoE: layers 0-2 and 4-5 sliding,
3 global), 8 routed experts of which 4 are held (from index 2), top-2,
one shared expert, a 32-token window.

The engine runs with float32 KV and float32 activations, where every
matmul on the CPU is exact float32, so engine and reference differ only
by summation order: logits agree to 1e-4 (absolute, logits of order 1-5;
observed ~5e-6).  A wrong equation (a missing norm, gate, RoPE on a
global layer, a dropped or doubled expert) moves them by 1e-2 or more.
"""
from __future__ import annotations

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import reference_afmoe as ra  # noqa: E402
from repro.configs import EngineConfig, get_config  # noqa: E402
from repro.core.engine import KVNANDEngine  # noqa: E402
from repro.models import layers  # noqa: E402
from repro.models.transformer import Runtime, forward_train  # noqa: E402
from repro.serving.api import (KVNANDServer, SamplingParams,  # noqa: E402
                               ServerConfig)

TOL = 1e-4          # float32 on both sides: summation order only
DM = ra.Dims(d=64, H=4, K=2, dh=16, ff=32, ff_dense=96, V=256, Vp=256, L=6,
             L_pub=6, n_dense=2, E=8, E_held=4, off=2, top_k=2, n_shared=1,
             route_scale=2.826, window=32, global_every=4, theta=1e4,
             eps=1e-5)
RT = Runtime(moe_capacity=None)


def config(dm=DM):
    return dataclasses.replace(get_config("trinity-mini"),
                               **ra.program_fields(dm))


@pytest.fixture(scope="module")
def weights():
    return ra.make_weights(DM, 2**33 + 15)


@pytest.fixture(scope="module")
def tokens():
    return np.random.default_rng(7).integers(0, DM.V, 96)


@pytest.fixture(scope="module")
def ref_logits(weights, tokens):
    return ra.oneshot_logits(weights, DM, tokens)


def test_config_is_the_published_model():
    cfg = get_config("trinity-mini")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.d_head, cfg.n_experts, cfg.top_k, cfg.n_dense_layers,
            cfg.dense_d_ff, cfg.d_ff, cfg.window) == \
        (32, 2048, 32, 4, 128, 128, 8, 2, 6144, 1024, 2048)
    assert [i for i in range(32) if cfg.is_global_layer(i)] == \
        list(range(3, 32, 4))
    assert cfg.param_count() == pytest.approx(26.1e9, rel=0.005)
    assert cfg.experts_held == 128 and cfg.n_moe_layers == 30


def test_model_forward_matches_reference(weights, tokens, ref_logits):
    lg, _ = forward_train(weights, config(), {"tokens": jnp.asarray(
        tokens)[None]}, RT)
    np.testing.assert_allclose(np.asarray(lg[0, :, :DM.V]), ref_logits,
                               atol=TOL)


def test_prefill_then_decode_past_the_window(weights, tokens, ref_logits):
    """Prefill 40 tokens, then decode 56 through the cache: the 32-token
    window's ring (8-token pages) wraps several times."""
    eng = KVNANDEngine(config(), EngineConfig(page_tokens=8,
                                              kv_dtype="float32"), RT)
    s0 = 40
    lg, cache = jax.jit(eng.prefill, static_argnums=(2,))(
        weights, {"tokens": jnp.asarray(tokens[:s0])[None]}, 128)
    step = jax.jit(eng.decode_step, static_argnames=("route_counts",))
    got = [np.asarray(lg[0, :DM.V])]
    for i in range(s0, len(tokens) - 1):
        lg, cache = step(weights, cache, jnp.asarray(tokens[i:i + 1])[None])
        got.append(np.asarray(lg[0, :DM.V]))
    np.testing.assert_allclose(np.stack(got), ref_logits[s0 - 1:-1],
                               atol=TOL)


@pytest.mark.parametrize("chunk", [16, 80])
def test_chunked_prefill_matches_reference(weights, tokens, ref_logits,
                                           chunk):
    """Chunks of 16 rows (every held expert on every row) and 80 rows
    (pairs sorted into ragged expert groups), then decode."""
    eng = KVNANDEngine(config(), EngineConfig(
        page_tokens=8, kv_dtype="float32", uniform_lengths=False), RT)
    cache = eng.init_cache(2, 128)
    chunk_fn = jax.jit(eng.prefill_chunk, static_argnames=("first",))
    n = 88
    for start in range(0, n, chunk):
        m = min(chunk, n - start)
        buf = np.zeros(chunk, np.int32)
        buf[:m] = tokens[start:start + m]
        lg, cache = chunk_fn(weights, cache,
                             {"tokens": jnp.asarray(buf)[None]}, 1, start,
                             m, first=start == 0)
        np.testing.assert_allclose(np.asarray(lg[0, :DM.V]),
                                   ref_logits[start + m - 1], atol=TOL)
    active = jnp.asarray([False, True])
    step = jax.jit(eng.decode_step, static_argnames=("route_counts",))
    for i in range(n, len(tokens) - 1):
        lg, cache = step(weights, cache, jnp.asarray([[0], [tokens[i]]]),
                         active=active)
        np.testing.assert_allclose(np.asarray(lg[1, :DM.V]),
                                   ref_logits[i], atol=TOL)


def _serve(weights, prompts, max_new=6):
    srv = KVNANDServer(
        ServerConfig(arch="trinity-mini", batch_slots=4, max_context=128,
                     prefill_chunk_tokens=16,
                     engine=EngineConfig(page_tokens=8, kv_dtype="float32",
                                         uniform_lengths=False)),
        cfg=config(), params=weights)
    outs = srv.generate(prompts, SamplingParams(max_new_tokens=max_new,
                                                logprobs=True))
    return srv, outs


def test_served_request_does_not_depend_on_its_batch(weights, tokens):
    """Dropless: a request's tokens and logprobs are the same served alone
    and beside three others (exactly: its rows are computed alone)."""
    a = tokens[:37].tolist()
    others = [tokens[37:70].tolist(), tokens[10:60].tolist(),
              tokens[50:95].tolist()]
    _, (alone,) = _serve(weights, [a])
    srv, mixed = _serve(weights, [a] + others)
    assert mixed[0].token_ids == alone.token_ids
    np.testing.assert_array_equal(mixed[0].logprobs, alone.logprobs)
    st = srv.stats
    assert st["moe_pairs_routed"] > st["moe_pairs_held"] > 0
    assert st["moe_pairs_routed"] % (DM.top_k * (DM.L - DM.n_dense)) == 0
    assert st["decode_pages_walked_w"] > st["decode_pages_live_w"] > 0


def test_expert_shares_add_up_to_the_uncut_layer():
    """Each chip's layer (its held experts plus the shared expert) summed
    over the expert-parallel shards, the shared expert counted once, is
    the reference layer holding every expert."""
    full = DM._replace(E_held=DM.E, off=0)
    wfull = ra.make_weights(full, 11)
    m = jax.tree.map(lambda a: a[0], wfull["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(3), (40, DM.d))
    want = ra.moe_ffn(x, m, full)
    shared = ra.moe_ffn(x, {**m, "w_gate": m["w_gate"][:0],
                            "w_up": m["w_up"][:0],
                            "w_down": m["w_down"][:0]},
                        full._replace(E_held=0))
    total = shared
    for off in range(0, DM.E, DM.E_held):
        share = {**m, **{k: m[k][off:off + DM.E_held]
                         for k in ("w_gate", "w_up", "w_down")}}
        cfg = config(DM._replace(off=off))
        y, _ = layers.moe(share, x, cfg)
        total = total + (y - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-5)


@pytest.mark.parametrize("rows", [8, 96])
def test_dropless_paths_agree(rows):
    """Every held expert on every row (<= 64 rows), ragged groups (more
    rows) and the training dispatch at a capacity that drops nothing give
    the same layer."""
    w = ra.make_weights(DM, 5)
    m = jax.tree.map(lambda a: a[1], w["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(rows), (2, rows // 2, DM.d))
    y, held = layers.moe(m, x, config())
    yc, held_c = layers.moe(m, x, config(),
                            capacity_factor=DM.E_held / DM.top_k)
    want = ra.moe_ffn(x.reshape(rows, DM.d), m, DM)
    np.testing.assert_allclose(np.asarray(y).reshape(rows, DM.d),
                               np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(np.asarray(yc), np.asarray(y), atol=1e-5)
    top, _ = ra.route(x.reshape(rows, DM.d), m, DM)
    local = np.asarray(top) - DM.off
    assert int(held) == int(held_c) == int(((local >= 0)
                                            & (local < DM.E_held)).sum())


def test_expert_bias_changes_selection_only():
    """The bias picks the experts; the weights are the unbiased scores of
    the picked ones, normalized and scaled."""
    w = ra.make_weights(DM, 9)
    m = jax.tree.map(lambda a: a[0], w["layers"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(1), (32, DM.d))
    cfg = config()
    bias = jnp.zeros((DM.E,)).at[DM.off].set(10.0)     # always chosen
    idx, wt = layers.moe_route({**m, "expert_bias": bias}, x, cfg)
    assert (np.asarray(idx) == DM.off).any(-1).all()
    s = jax.nn.sigmoid(x @ m["router_w"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    np.testing.assert_allclose(
        np.asarray(wt), np.asarray(DM.route_scale * chosen
                                   / chosen.sum(-1, keepdims=True)),
        rtol=1e-5)
    idx0, _ = layers.moe_route({**m, "expert_bias": jnp.zeros((DM.E,))}, x,
                               cfg)
    assert not np.array_equal(np.asarray(idx0), np.asarray(idx))
    y, _ = layers.moe({**m, "expert_bias": bias}, x, cfg)
    np.testing.assert_allclose(
        np.asarray(y),
        np.asarray(ra.moe_ffn(x, {**m, "expert_bias": bias}, DM)),
        atol=1e-5)
