"""Token-identity parity for disaggregated prefill/decode (ISSUE 10).

A request that chunk-prefills on one replica, migrates as a
`KVEnvelope` (through the real wire bytes), and decodes on another
replica must emit EXACTLY the token stream and logprobs of the same
request run end-to-end on one server — across every paged-KV format
(fp/kv8/kv4) and both pool residencies (flat, tiered hot/capacity).
Bit-identity follows from PR 4's fold_in PRNG streams plus page-byte
equality; these tests are the enforcement.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import EngineConfig, get_config
from repro.models.registry import Model
from repro.models.transformer import Runtime
from repro.serving import replica as replica_mod
from repro.serving.api import KVNANDServer, ServerConfig
from repro.serving.replica import KVEnvelope, export_request
from repro.serving.router import ReplicaRouter
from repro.serving.sampler import SamplingParams

TOTAL_PAGES = 64
HOT_PAGES = 12


@pytest.fixture(scope="module")
def model():
    cfg = get_config("qwen1.5-0.5b").reduced()
    rt = Runtime()
    return cfg, rt, Model(cfg, rt).init(jax.random.PRNGKey(0))


def _server(model, kv_quant="none", hot_pages=0, slots=3):
    cfg, rt, params = model
    eng = EngineConfig(page_tokens=16, shared_pool=True,
                       total_pages=TOTAL_PAGES,
                       hot_pages=hot_pages, kv_quant=kv_quant)
    sc = ServerConfig(arch="qwen1.5-0.5b", reduced=True, engine=eng,
                      batch_slots=slots, max_context=64,
                      prefill_chunk_tokens=16, seed=7)
    return KVNANDServer(sc, cfg=cfg, params=params, rt=rt)


def _prompts(vocab):
    rng = np.random.default_rng(11)
    sysp = rng.integers(1, vocab, 18).tolist()
    return [sysp + rng.integers(1, vocab, k).tolist() for k in (3, 9, 1)]


PARAMS = [SamplingParams(max_new_tokens=8, temperature=0.0,
                         logprobs=True),
          SamplingParams(max_new_tokens=8, temperature=0.9, top_k=20,
                         logprobs=True),
          SamplingParams(max_new_tokens=6, temperature=0.7, top_p=0.9,
                         logprobs=True, seed=123)]


def _reference(model, kv_quant, hot_pages, prompts):
    srv = _server(model, kv_quant, hot_pages)
    uids = [srv.submit(p, sp, uid=100 + i)
            for i, (p, sp) in enumerate(zip(prompts, PARAMS))]
    srv.run()
    return {u: srv.output(u) for u in uids}


@pytest.mark.parametrize("hot_pages", [0, HOT_PAGES],
                         ids=["flat", "tiered"])
@pytest.mark.parametrize("kv_quant", ["none", "kv8", "kv4"])
def test_disaggregated_token_identity(model, kv_quant, hot_pages):
    prompts = _prompts(model[0].vocab_size)
    ref = _reference(model, kv_quant, hot_pages, prompts)

    servers = [_server(model, kv_quant, hot_pages) for _ in range(3)]
    router = ReplicaRouter(servers, disaggregate=True)
    uids = [router.submit(p, sp, uid=100 + i)
            for i, (p, sp) in enumerate(zip(prompts, PARAMS))]
    router.run()

    assert router.stats["migrations"] == len(uids)
    assert router.stats["migration_bytes"] > 0
    for u in uids:
        out, want = router.output(u), ref[u]
        assert router.replica_of(u) in (1, 2)      # decoded off-replica
        assert out.token_ids == want.token_ids
        assert out.logprobs == want.logprobs
        assert out.finish_reason == want.finish_reason
    # page conservation on every replica after drain
    for s in servers:
        b = s._batcher
        b.alloc.check()
        if b.alloc_w is not None:
            b.alloc_w.check()
        if b.tier is not None:
            b.tier.check()
            assert b.tier.pinned_count == 0


def test_envelope_wire_roundtrip(model):
    """from_bytes(to_bytes(env)) reproduces every leaf and the header;
    the envelope covers quantized pages + scales (kv8) so the scale
    leaves demonstrably travel."""
    prompts = _prompts(model[0].vocab_size)
    srv = _server(model, kv_quant="kv8")
    uid = srv.submit(prompts[1], PARAMS[1], uid=5)
    srv._requests[uid].hold = True
    steps = 0
    b = srv._batcher
    while not (b.slots and any(r is not None and r.output
                               for r in b.slots)):
        srv.step()
        steps += 1
        assert steps < 50
    env = export_request(b, uid)
    assert any(k.endswith("k_scale_g") for k in env.arrays), \
        "kv8 scales missing from envelope"
    env2 = KVEnvelope.from_bytes(env.to_bytes())
    assert env2.meta == env.meta
    assert set(env2.arrays) == set(env.arrays)
    for k in env.arrays:
        np.testing.assert_array_equal(env2.arrays[k], env.arrays[k])
    assert len(env.to_bytes()) >= env.nbytes()


def test_import_backpressure_retries_then_lands(model):
    """A decode replica with no free slot refuses the import (source
    keeps its pages); the migration lands once a slot frees."""
    prompts = _prompts(model[0].vocab_size)
    pre = _server(model, slots=2)
    dec = _server(model, slots=1)
    router = ReplicaRouter([pre, dec], disaggregate=True)
    sp = dataclasses.replace(PARAMS[0], max_new_tokens=12)
    uids = [router.submit(p, sp, uid=i) for i, p in enumerate(prompts)]
    router.run()
    assert router.stats["migrations"] == len(uids)
    assert router.stats["migration_retries"] > 0, \
        "1-slot decode replica never exerted backpressure"
    # baseline run with the same per-uid params
    srv = _server(model)
    base_uids = [srv.submit(p, sp, uid=i) for i, p in enumerate(prompts)]
    srv.run()
    for u in uids:
        assert router.output(u).token_ids == srv.output(u).token_ids
    pre._batcher.alloc.check()
    dec._batcher.alloc.check()


def test_abort_held_request_conserves_pages(model):
    """Aborting a request while it sits HELD awaiting migration frees
    its source pages; nothing ever reaches the decode replica."""
    prompts = _prompts(model[0].vocab_size)
    pre = _server(model, slots=2)
    dec = _server(model, slots=1)
    router = ReplicaRouter([pre, dec], disaggregate=True)
    uids = [router.submit(p, PARAMS[0], uid=i)
            for i, p in enumerate(prompts)]
    # step the prefill replica only, so handoffs complete but nothing
    # migrates; then abort one held request
    for _ in range(30):
        pre.step()
    held = [r.uid for r in pre._batcher.slots
            if r is not None and r.hold and r.output]
    assert held, "no request reached the held state"
    assert router.abort(held[0])
    router.run()
    assert router.output(held[0]).finish_reason == "aborted"
    for u in uids:
        if u != held[0]:
            assert router.output(u).finish_reason in ("stop", "length")
    pre._batcher.alloc.check()
    dec._batcher.alloc.check()
    assert dec._batcher.stats.get("migrations_in", 0) == len(uids) - 1


def test_cross_replica_prefix_index(model):
    """Routed mode: pages warmed on one replica admit as prefix hits on
    another via the PrefixPageIndex, token-identically."""
    cfg = model[0]
    rng = np.random.default_rng(3)
    sysp = rng.integers(1, cfg.vocab_size, 32).tolist()
    prompts = [sysp + rng.integers(1, cfg.vocab_size, 5).tolist()
               for _ in range(4)]
    sp = SamplingParams(max_new_tokens=6)

    ref = {}
    solo = _server(model)
    for i, p in enumerate(prompts):
        u = solo.submit(p, sp, uid=i)
        solo.run()
        ref[u] = solo.output(u).token_ids

    servers = [_server(model), _server(model)]
    router = ReplicaRouter(servers, share_prefix=True)
    assert router.index is not None
    # drain one prompt at a time so the finished prompt publishes its
    # chain before the next submit warms the other replica
    for i, p in enumerate(prompts):
        router.submit(p, sp, uid=i)
        router.run()
    for i in range(len(prompts)):
        assert router.output(i).token_ids == ref[i]
    assert router.stats["prefix_published_pages"] > 0
    assert router.stats["prefix_warmed_pages"] > 0, \
        "warm path never imported a page cross-replica"
    hits = sum(s.stats.get("prefix_hit_pages", 0) for s in servers)
    assert hits > 0, "warmed pages never produced a prefix hit"
    for s in servers:
        s._batcher.alloc.check()


def test_import_rejects_layout_mismatch(model):
    prompts = _prompts(model[0].vocab_size)
    pre = _server(model, kv_quant="kv8")
    dec = _server(model, kv_quant="kv4")
    uid = pre.submit(prompts[0], PARAMS[0], uid=0)
    pre._requests[uid].hold = True
    for _ in range(30):
        pre.step()
        if any(r is not None and r.output for r in pre._batcher.slots):
            break
    env = export_request(pre._batcher, uid)
    with pytest.raises(ValueError, match="kv_quant"):
        replica_mod.import_request(dec._batcher, env)
