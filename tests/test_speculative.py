"""Self-speculative decoding (DESIGN.md §11): prompt-lookup drafting,
the sampler's accept rule, token parity of greedy (and seeded
stochastic) spec-decode vs sequential decode across every KV layout, and
the shared-pool allocator's accept/rollback conservation invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs import EngineConfig, get_config
from repro.models.registry import Model
from repro.models.transformer import Runtime
from repro.serving.draft import propose_draft
from repro.serving.sampler import (SamplingParams, speculative_accept)
from repro.serving.scheduler import ContinuousBatcher, Request

ARCH = "qwen1.5-0.5b"
# repetitive + mixed prompts: lookup drafting must actually accept on the
# first, and must stay harmless on the random ones
REP = [7, 8, 9, 10] * 5
PROMPTS = [REP, list(range(1, 20)), [5, 4, 3]]


def _model(arch=ARCH):
    cfg = get_config(arch).reduced()
    rt = Runtime()
    return cfg, rt, Model(cfg, rt).init(jax.random.PRNGKey(0))


def _drain(cfg, params, eng, prompts, *, spec_k, max_new=8, slots=2,
           ctx=96, chunk=16, sp=None):
    b = ContinuousBatcher(cfg, params, batch_slots=slots, max_context=ctx,
                          temperature=0.0, eng=eng,
                          prefill_chunk_tokens=chunk,
                          speculation_k=spec_k)
    for uid, p in enumerate(prompts):
        r = Request(uid, list(p), max_new=max_new)
        if sp is not None:
            r.params = sp
        b.submit(r)
    done = b.run_to_completion()
    return {u: r.output for u, r in done.items()}, b


# ---------------------------------------------------------------------------
# drafter: prompt lookup
# ---------------------------------------------------------------------------

def test_propose_draft_lookup_and_fallback():
    # trailing [3, 4] recurs: the draft continues from its last earlier
    # occurrence
    assert propose_draft([1, 2, 3, 4, 9, 3, 4], 3) == [9, 3, 4]
    # no recurrence: repeat the last token
    assert propose_draft([1, 2, 3], 2) == [3, 3]
    # match near the end pads by repeating the last token
    assert propose_draft([5, 6, 5, 6], 4) == [5, 6, 6, 6]
    assert propose_draft([1], 0) == []
    assert propose_draft([], 3) == []


# ---------------------------------------------------------------------------
# sampler: accept rule (greedy-exact, allowed-gated)
# ---------------------------------------------------------------------------

def test_speculative_accept_greedy_counts_leading_matches():
    B, S, V = 2, 4, 11
    lg = jax.random.normal(jax.random.PRNGKey(0), (B, S, V))
    arg = np.asarray(jnp.argmax(lg, -1))
    drafts = arg[:, :-1].copy()
    drafts[0, 1] = (drafts[0, 1] + 1) % V        # row 0 mismatch at j=1
    toks, lps, acc = speculative_accept(
        jnp.asarray(lg), jnp.asarray(drafts),
        np.zeros(B, np.uint32), np.zeros(B, np.int32),
        np.full(B, S - 1, np.int32), true_vocab=V)
    np.testing.assert_array_equal(np.asarray(toks), arg)  # greedy == argmax
    assert list(np.asarray(acc)) == [1, S - 1]
    # allowed caps acceptance without changing the sampled tokens
    toks2, _, acc2 = speculative_accept(
        jnp.asarray(lg), jnp.asarray(drafts),
        np.zeros(B, np.uint32), np.zeros(B, np.int32),
        np.zeros(B, np.int32), true_vocab=V)
    np.testing.assert_array_equal(np.asarray(toks2), arg)
    assert list(np.asarray(acc2)) == [0, 0]


# ---------------------------------------------------------------------------
# token parity: speculative == sequential, every layout
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw", [dict(kv_dtype="float32"),
                                dict(kv_quant="kv8")],
                         ids=["f32", "kv8"])
def test_spec_matches_sequential_formats(kw):
    cfg, rt, params = _model()
    eng = EngineConfig(page_tokens=16, **kw)
    o0, _ = _drain(cfg, params, eng, PROMPTS, spec_k=0)
    o4, b4 = _drain(cfg, params, eng, PROMPTS, spec_k=4)
    assert o0 == o4
    assert b4.stats["spec_accepted"] > 0     # the repetitive prompt pays
    assert b4.stats["spec_steps"] < b4.stats["decode_tokens"]


def test_spec_matches_sequential_window_ring():
    """gemma3 local:global mix — span appends through the ring, accepted
    tokens only advance the ring bases."""
    cfg, rt, params = _model("gemma3-12b")
    prompts = PROMPTS + [list(range(1, 78))]     # > reduced window of 64
    eng = EngineConfig(page_tokens=16, kv_dtype="float32")
    o0, _ = _drain(cfg, params, eng, prompts, spec_k=4, max_new=4)
    o1, _ = _drain(cfg, params, eng, prompts, spec_k=0, max_new=4)
    assert o0 == o1


def test_spec_matches_sequential_shared_pool():
    cfg, rt, params = _model()
    eng = EngineConfig(page_tokens=16, shared_pool=True, kv_dtype="float32")
    o0, _ = _drain(cfg, params, eng, PROMPTS, spec_k=0)
    o4, b4 = _drain(cfg, params, eng, PROMPTS, spec_k=4)
    assert o0 == o4
    assert b4.stats["spec_accepted"] > 0
    b4.alloc.check()
    assert b4._outstanding == 0
    assert b4.alloc.live_count == b4.prefix_cache.evictable_pages()


def test_spec_seeded_stochastic_stream_parity():
    """Sampling rows draw every span position from the request's own
    fold_in(seed, position) stream, so seeded outputs are identical with
    speculation on or off."""
    cfg, rt, params = _model()
    eng = EngineConfig(page_tokens=16, kv_dtype="float32")
    sp = SamplingParams(temperature=0.9, top_k=8, seed=123,
                        max_new_tokens=8)
    o0, _ = _drain(cfg, params, eng, PROMPTS, spec_k=0, sp=sp)
    o4, _ = _drain(cfg, params, eng, PROMPTS, spec_k=4, sp=sp)
    assert o0 == o4


def test_spec_per_request_opt_out():
    """SamplingParams.speculation=0 keeps the request out of drafting
    (no drafts offered) without changing its tokens."""
    cfg, rt, params = _model()
    eng = EngineConfig(page_tokens=16, kv_dtype="float32")
    sp = SamplingParams(max_new_tokens=8, speculation=0)
    o0, _ = _drain(cfg, params, eng, [REP], spec_k=0)
    o4, b4 = _drain(cfg, params, eng, [REP], spec_k=4, sp=sp)
    assert o0 == o4
    assert b4.stats["spec_drafted"] == 0
    assert b4.stats["spec_accepted"] == 0
    # opted-out rows are not counted as verify steps either, so their
    # accepted_tokens_per_step stays None instead of a misleading 1.0
    assert b4.stats["spec_steps"] == 0
    assert all(r.spec_steps == 0 for r in b4.completed.values())


def test_spec_rejected_unsupported_archs():
    cfg, rt, params = _model("rwkv6-3b")
    with pytest.raises(ValueError, match="speculat"):
        ContinuousBatcher(cfg, params, batch_slots=2, max_context=96,
                          speculation_k=2)


# ---------------------------------------------------------------------------
# accepted-tokens-per-step surfaces through the API
# ---------------------------------------------------------------------------

def test_request_output_acceptance_stats():
    from repro.serving.api import KVNANDServer, ServerConfig
    cfg, rt, params = _model()
    server = KVNANDServer(
        ServerConfig(batch_slots=2, max_context=96,
                     prefill_chunk_tokens=16, speculation_k=4,
                     engine=EngineConfig(page_tokens=16,
                                         kv_dtype="float32")),
        cfg=cfg, params=params)
    [out] = server.generate([REP], SamplingParams(max_new_tokens=12))
    assert out.spec_steps > 0
    assert out.accepted_tokens_per_step is not None
    # the repetitive prompt must actually amortize: > 1 token per step
    assert out.accepted_tokens_per_step > 1.0
    # first token from the prefill handoff, then verify steps; steps
    # whose budget cannot accept anything (e.g. the last max_new token)
    # fall back to sequential decode and carry no spec counters
    assert len(out.token_ids) >= 1 + out.spec_accepted + out.spec_steps
    assert out.spec_drafted >= out.spec_accepted


def test_spec_stop_token_truncates_span_and_stats():
    """A stop token accepted mid-span truncates emission there, and the
    acceptance counters reflect EMITTED tokens only (every finish reason
    keeps len(output) == 1 + spec_accepted + spec_steps)."""
    cfg, rt, params = _model()
    eng = EngineConfig(page_tokens=16, kv_dtype="float32")
    # learn greedy continuation, then stop on its 3rd emitted token
    ref, _ = _drain(cfg, params, eng, [REP], spec_k=4, max_new=10)
    stop = ref[0][2]
    sp = SamplingParams(max_new_tokens=10, stop_token_ids=(stop,))
    out, b = _drain(cfg, params, eng, [REP], spec_k=4, sp=sp)
    req = b.completed[0]
    assert req.finish_reason == "stop"
    assert out[0] == ref[0][:out[0].index(stop) + 1]
    assert len(out[0]) == 1 + req.spec_accepted + req.spec_steps


# ---------------------------------------------------------------------------
# rollback: allocator conservation under arbitrary draft/accept traces
# ---------------------------------------------------------------------------

def _shared_eng(total_pages=0):
    return EngineConfig(page_tokens=4, kv_dtype="float32", shared_pool=True,
                        total_pages=total_pages)


def test_rollback_returns_speculated_pages():
    """A span that crosses into a freshly allocated page whose drafts
    are all rejected must hand the page straight back: free count,
    refcounts and reservations exactly as if it was never allocated."""
    cfg, rt, params = _model()
    eng = _shared_eng()
    b = ContinuousBatcher(cfg, params, batch_slots=1, max_context=32,
                          temperature=0.0, eng=eng,
                          prefill_chunk_tokens=4, speculation_k=6)
    # non-repetitive prompt: lookup drafts miss, so most steps accept 0
    b.submit(Request(0, list(range(1, 6)), max_new=6))
    while b.queue or any(r is not None for r in b.slots):
        b.step()
        b.alloc.check()
        assert b._outstanding == int(b._resv.sum())
        # pages taken for the span beyond the accepted extent came back:
        # a DECODING slot never keeps a mapping past its written length
        # (mid-prefill slots hold pages ahead of `_lengths` by design)
        if b.slots[0] is not None and 0 not in b._prefill_live:
            last = (int(b._lengths[0]) - 1) // 4
            assert all(lp <= last for lp in b._slot_pages[0])
    b.alloc.check()
    assert b._outstanding == 0


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 10_000), spec_k=st.integers(1, 5),
       total_pages=st.sampled_from([16, 24]))
def test_spec_shared_pool_conservation_property(seed, spec_k, total_pages):
    """Hypothesis: ANY draft/accept trace (prompts drawn from a small
    alphabet so acceptance varies organically) drains with exact
    refcounts — no orphaned pages, reservations fully released, and
    token outputs identical to sequential decode."""
    import random
    rng = random.Random(seed)
    cfg, rt, params = _model()
    prompts = [[rng.randrange(3, 9) for _ in range(rng.randrange(3, 14))]
               for _ in range(3)]
    eng = _shared_eng(total_pages=total_pages)
    o_seq, _ = _drain(cfg, params, eng, prompts, spec_k=0, ctx=48,
                      chunk=4, max_new=6)
    b = ContinuousBatcher(cfg, params, batch_slots=2, max_context=48,
                          temperature=0.0, eng=eng,
                          prefill_chunk_tokens=4, speculation_k=spec_k)
    for uid, p in enumerate(prompts):
        b.submit(Request(uid, list(p), max_new=6))
    while b.queue or any(r is not None for r in b.slots):
        b.step()
        b.alloc.check()                       # conservation every step
        assert b._outstanding == int(b._resv.sum()) >= 0
    assert {u: r.output for u, r in b.completed.items()} == o_seq
    b.alloc.check()
    assert b._outstanding == 0
    # every live page is a prefix-cache reference — nothing orphaned
    assert b.alloc.live_count == b.prefix_cache.evictable_pages()


def test_spec_abort_mid_flight_conserves_pages():
    cfg, rt, params = _model()
    b = ContinuousBatcher(cfg, params, batch_slots=2, max_context=48,
                          temperature=0.0, eng=_shared_eng(),
                          prefill_chunk_tokens=4, speculation_k=3)
    b.submit(Request(0, [2, 3, 4, 2, 3, 4, 2, 3], max_new=16))
    b.submit(Request(1, list(range(1, 9)), max_new=16))
    for _ in range(3):
        b.step()
    assert b.abort(0)
    b.alloc.check()
    assert b._outstanding == int(b._resv.sum())
    b.run_to_completion()
    b.alloc.check()
    assert b._outstanding == 0
