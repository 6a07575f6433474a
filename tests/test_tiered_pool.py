"""Tiered flash KV hierarchy (DESIGN.md §13): hot/capacity page tiers.

A two-wave trace (drain a set of shared-prefix prompts, then re-submit
the same prompts after their cache pages were demoted) must produce
token output bit-identical to the single-tier shared pool — with the
prefetcher on AND off — while actually exercising demotion, demand
promotion, and the prefetch path.  Plus the admission guards: a prompt
whose pinned footprint cannot fit the hot tier is rejected at submit,
and one-shot engine prefill refuses tiered pools outright.
"""
import jax
import numpy as np
import pytest

from repro.configs import EngineConfig, get_config
from repro.core.engine import KVNANDEngine
from repro.models.registry import Model
from repro.models.transformer import Runtime
from repro.serving.scheduler import ContinuousBatcher, Request

N_UNIQ = 6
TOTAL_PAGES = 64
HOT_PAGES = 12


def _model(arch="qwen1.5-0.5b"):
    cfg = get_config(arch).reduced()
    rt = Runtime()
    return cfg, rt, Model(cfg, rt).init(jax.random.PRNGKey(0))


def _trace(vocab):
    """Shared 32-token system prompt + unique tails: pages out to more
    flash pages than HOT_PAGES, so wave 2 re-maps demoted pages."""
    rng = np.random.default_rng(23)
    sysp = rng.integers(1, vocab, 32).tolist()
    return [sysp + rng.integers(1, vocab, 9).tolist()
            for _ in range(N_UNIQ)]


def _eng(hot_pages=0):
    return EngineConfig(page_tokens=16, shared_pool=True,
                        total_pages=TOTAL_PAGES,
                        hot_pages=hot_pages)


def _drain_two_wave(cfg, params, eng, prompts, *, prefetch=True,
                    max_new=8):
    """One batcher, two submission waves of the SAME prompts: wave 1
    populates the prefix cache, its pages demote under slot pressure,
    wave 2's cached map-ins promote them back."""
    b = ContinuousBatcher(cfg, params, batch_slots=3, max_context=64,
                          temperature=0.0, eng=eng,
                          prefill_chunk_tokens=16,
                          tier_prefetch=prefetch)
    outs = {}
    for wave in range(2):
        for i, p in enumerate(prompts):
            b.submit(Request(wave * len(prompts) + i, list(p),
                             max_new=max_new))
        done = b.run_to_completion()
        outs.update({u: r.output for u, r in done.items()})
    return outs, b


# ---------------------------------------------------------------------------
# token parity with the single-tier pool, demotion actually exercised
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("prefetch", [True, False],
                         ids=["prefetch", "noprefetch"])
def test_tiered_matches_flat_pool(prefetch):
    cfg, rt, params = _model()
    prompts = _trace(cfg.vocab_size)
    o_flat, _ = _drain_two_wave(cfg, params, _eng(), prompts)
    o_tier, b = _drain_two_wave(cfg, params, _eng(HOT_PAGES), prompts,
                                prefetch=prefetch)
    assert o_tier == o_flat
    st = b.stats
    assert st["tier_demotes"] > 0, "trace never pressured the hot tier"
    assert st["tier_promotes"] > 0
    assert st["tier_hit_pages"] + st["tier_miss_pages"] > 0
    b.alloc.check()
    b.tier.check()
    # at drain no slot maps pages: every resident must be demotable
    assert b.tier.pinned_count == 0
    assert b.tier.resident_count <= HOT_PAGES


def test_prefetch_reduces_stall_tokens():
    """Identical outputs, strictly fewer demand faults with the
    queue-ahead prefetch stage enabled."""
    cfg, rt, params = _model()
    prompts = _trace(cfg.vocab_size)
    o_on, b_on = _drain_two_wave(cfg, params, _eng(HOT_PAGES), prompts)
    o_off, b_off = _drain_two_wave(cfg, params, _eng(HOT_PAGES), prompts,
                                   prefetch=False)
    assert o_on == o_off
    on, off = b_on.stats, b_off.stats
    assert on["tier_prefetch_pages"] > 0
    assert on["tier_stall_tokens"] < off["tier_stall_tokens"]
    assert off["tier_prefetch_pages"] == 0


def test_tiered_per_request_stats_through_server():
    """RequestOutput carries per-request hot-tier hit/stall counts."""
    from repro.serving.api import (KVNANDServer, SamplingParams,
                                   ServerConfig)
    cfg, rt, params = _model()
    prompts = _trace(cfg.vocab_size)
    server = KVNANDServer(
        ServerConfig(scheduler="interleaved", engine=_eng(HOT_PAGES),
                     batch_slots=3, max_context=64,
                     prefill_chunk_tokens=16),
        cfg=cfg, params=params)
    sp = SamplingParams(max_new_tokens=4)
    totals = [0, 0]
    for _ in range(2):
        uids = [server.submit(p, sp) for p in prompts]
        server.run()
        for u in uids:
            o = server.output(u)
            assert o.tier_hit_pages >= 0 and o.tier_stall_tokens >= 0
            totals[0] += o.tier_hit_pages
            totals[1] += o.tier_stall_tokens
            server.release(u)
    st = server.stats
    assert totals[0] == st["tier_hit_pages"]
    assert totals[1] == st["tier_stall_tokens"]
    assert st["tier_hit_pages"] + st["tier_miss_pages"] > 0


# ---------------------------------------------------------------------------
# admission guards
# ---------------------------------------------------------------------------

def test_submit_rejects_footprint_over_hot_tier():
    """A request whose pinned pages can never fit the hot tier must be
    rejected at submit, not deadlock in the admit loop."""
    cfg, rt, params = _model()
    b = ContinuousBatcher(cfg, params, batch_slots=1, max_context=128,
                          temperature=0.0, eng=_eng(2),
                          prefill_chunk_tokens=16)
    with pytest.raises(ValueError, match="hot tier"):
        b.submit(Request(0, list(range(1, 100)), max_new=4))


def test_oneshot_prefill_refuses_tiered_pool():
    cfg, rt, params = _model()
    engine = KVNANDEngine(cfg, _eng(HOT_PAGES), rt)
    toks = np.arange(1, 22, dtype=np.int32)[None, :]
    with pytest.raises(ValueError, match="TIERED"):
        engine.prefill(params, {"tokens": toks}, 64)
