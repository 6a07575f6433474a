"""KVNANDServer facade: request-centric serving API.

Covers the PR's acceptance criteria: decode-step compile count invariant
to the number of distinct SamplingParams in flight (params are traced
arrays), streamed tokens concatenating exactly to the final
RequestOutput, per-request determinism independent of batch composition
/ admission order / scheduler, mixed-params batches leaving greedy rows
bit-identical, stop-token + capacity finish reasons, and abort()
restoring the shared-pool allocator conservation invariant from every
lifecycle stage."""
import pathlib
import re

import jax
import pytest

from repro.configs import EngineConfig, get_config
from repro.models.registry import Model
from repro.models.transformer import Runtime
from repro.serving.api import (KVNANDServer, RequestOutput, SamplingParams,
                               ServerConfig)

ARCH = "qwen1.5-0.5b"

_CACHE = {}


def _model():
    if "m" not in _CACHE:
        cfg = get_config(ARCH).reduced()
        _CACHE["m"] = (cfg, Model(cfg, Runtime()).init(jax.random.PRNGKey(0)))
    return _CACHE["m"]


def _server(scheduler="interleaved", eng_kw=None, slots=2, ctx=96,
            chunk=16, **kw):
    cfg, params = _model()
    eng = EngineConfig(page_tokens=16, **(eng_kw or {}))
    return KVNANDServer(
        ServerConfig(scheduler=scheduler, engine=eng, batch_slots=slots,
                     max_context=ctx, prefill_chunk_tokens=chunk, **kw),
        cfg=cfg, params=params)


PROMPTS = [list(range(1, 8)), list(range(3, 24)), list(range(2, 13)),
           [5, 4, 3]]


# ---------------------------------------------------------------------------
# basic lifecycle: generate(), finish reasons, timing counters
# ---------------------------------------------------------------------------

def test_generate_lengths_reasons_and_timing():
    srv = _server()
    outs = srv.generate(PROMPTS, SamplingParams(max_new_tokens=5))
    assert [o.uid for o in outs] == [0, 1, 2, 3]
    for o in outs:
        assert isinstance(o, RequestOutput)
        assert len(o.token_ids) == 5
        assert o.finish_reason == "length"
        assert o.submit_time <= o.first_token_time <= o.finish_time
        assert o.ttft > 0.0 and o.tpot > 0.0


def test_generate_per_prompt_params_and_logprobs():
    srv = _server()
    outs = srv.generate(
        PROMPTS[:2],
        [SamplingParams(max_new_tokens=3, logprobs=True),
         SamplingParams(max_new_tokens=6, temperature=0.8, seed=1)])
    assert len(outs[0].token_ids) == 3 and len(outs[1].token_ids) == 6
    assert len(outs[0].logprobs) == 3
    assert all(lp <= 0.0 for lp in outs[0].logprobs)
    assert outs[1].logprobs is None


def test_capacity_finish_reason():
    srv = _server(ctx=64)
    out = srv.generate([list(range(1, 41))],
                       SamplingParams(max_new_tokens=100))[0]
    assert out.finish_reason == "capacity"
    assert len(out.token_ids) == 64 - 40


def test_stop_tokens_finish_within_one_step():
    ref = _server().generate(PROMPTS[:1],
                             SamplingParams(max_new_tokens=8))[0]
    stop = ref.token_ids[2]
    j = ref.token_ids.index(stop)          # first occurrence
    out = _server().generate(
        PROMPTS[:1],
        SamplingParams(max_new_tokens=8, stop_token_ids=(stop,)))[0]
    assert out.finish_reason == "stop"
    assert out.token_ids == ref.token_ids[:j + 1]   # stop id included


# ---------------------------------------------------------------------------
# acceptance: decode compile count invariant to the SamplingParams mix
# ---------------------------------------------------------------------------

MIXED = [SamplingParams(max_new_tokens=5),
         SamplingParams(max_new_tokens=5, temperature=0.7, seed=3),
         SamplingParams(max_new_tokens=5, temperature=1.3, top_k=4,
                        seed=9),
         SamplingParams(max_new_tokens=5, temperature=0.9, top_p=0.8,
                        top_k=7, seed=11)]


def test_decode_compiles_invariant_to_params_mix():
    """Four distinct SamplingParams combinations in flight must compile
    exactly what a uniform all-greedy run compiles: the params enter the
    jitted step as traced per-slot arrays, never as static args."""
    uniform = _server()
    uniform.generate(PROMPTS, SamplingParams(max_new_tokens=5))
    mixed = _server()
    mixed.generate(PROMPTS, MIXED)
    assert mixed.stats["compiles"] == uniform.stats["compiles"]
    # the decode executable itself: ONE entry in the jit cache
    cache_size = mixed._batcher._decode._cache_size()
    assert cache_size == 1, cache_size


# ---------------------------------------------------------------------------
# acceptance: streamed tokens == final token_ids, token for token
# ---------------------------------------------------------------------------

def test_stream_concatenates_to_final_output():
    """Mixed interleaved-prefill/decode trace: the per-step events of
    each request concatenate exactly to its RequestOutput.token_ids."""
    srv = _server(slots=2, chunk=16)
    prompts = [list(range(1, 40)), list(range(2, 9)), list(range(3, 30)),
               [7, 8, 9], list(range(4, 20))]
    uids = [srv.submit(p, SamplingParams(max_new_tokens=4 + i))
            for i, p in enumerate(prompts)]
    got = {u: [] for u in uids}
    reasons = {}
    for ev in srv.stream():
        assert ev.index == len(got[ev.uid])     # in-order, gapless
        got[ev.uid].append(ev.token)
        if ev.finish_reason is not None:
            reasons[ev.uid] = ev.finish_reason
    for u in uids:
        out = srv.output(u)
        assert got[u] == out.token_ids
        assert reasons[u] == out.finish_reason == "length"


def test_stream_events_carry_logprobs_when_asked():
    srv = _server()
    srv.submit(PROMPTS[0], SamplingParams(max_new_tokens=3,
                                          logprobs=True))
    evs = list(srv.stream())
    assert len(evs) == 3
    assert all(ev.logprob is not None and ev.logprob <= 0.0 for ev in evs)


# ---------------------------------------------------------------------------
# mixed-params batches: greedy rows unperturbed by hot neighbors
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("eng_kw", [dict(kv_dtype="float32"),
                                    dict(kv_quant="kv8")],
                         ids=["f32", "kv8"])
def test_greedy_rows_identical_next_to_hot_neighbors(eng_kw):
    all_greedy = _server(eng_kw=eng_kw).generate(
        PROMPTS, SamplingParams(max_new_tokens=5))
    hot = [SamplingParams(max_new_tokens=5),
           SamplingParams(max_new_tokens=5, temperature=2.0, seed=5),
           SamplingParams(max_new_tokens=5),
           SamplingParams(max_new_tokens=5, temperature=1.5, top_k=3,
                          seed=8)]
    mixed = _server(eng_kw=eng_kw).generate(PROMPTS, hot)
    assert mixed[0].token_ids == all_greedy[0].token_ids
    assert mixed[2].token_ids == all_greedy[2].token_ids


# ---------------------------------------------------------------------------
# determinism: seeded output independent of batch / order / scheduler
# ---------------------------------------------------------------------------

def test_seeded_output_independent_of_everything():
    """SamplingParams(seed=s) pins the request's PRNG stream to
    (seed, position): the same prompt yields bit-identical tokens alone,
    crowded, admitted last, under the splice scheduler, and on the
    shared pool."""
    prompt = list(range(5, 26))
    sp = SamplingParams(max_new_tokens=6, temperature=1.0, top_k=8,
                        top_p=0.9, seed=123)
    alone = _server(slots=1).generate([prompt], sp)[0].token_ids

    crowd = _server(slots=2)
    for p in PROMPTS[:3]:           # admitted first, different neighbors
        crowd.submit(p, SamplingParams(max_new_tokens=7, temperature=0.6,
                                       seed=4))
    uid = crowd.submit(prompt, sp)
    crowd.run()
    assert crowd.output(uid).token_ids == alone

    splice = _server(scheduler="splice", slots=2)
    for p in PROMPTS[:2]:
        splice.submit(p, SamplingParams(max_new_tokens=5))
    uid = splice.submit(prompt, sp)
    splice.run()
    assert splice.output(uid).token_ids == alone

    shared = _server(eng_kw=dict(shared_pool=True), slots=2)
    uid = shared.submit(prompt, sp)
    shared.submit(PROMPTS[1], SamplingParams(max_new_tokens=5))
    shared.run()
    assert shared.output(uid).token_ids == alone


# ---------------------------------------------------------------------------
# abort(): every lifecycle stage, allocator conservation, cache floors
# ---------------------------------------------------------------------------

def _cache_refs(pc):
    """Pages the prefix cache references -> reference count."""
    refs = {}
    for p in pc._full.values():
        refs[p] = refs.get(p, 0) + 1
    for e in pc._exact.values():
        for p in e.pages:
            refs[p] = refs.get(p, 0) + 1
    return refs


def _assert_pool_clean(b):
    """All slots empty: conservation holds and the only live pages are
    the prefix cache's, each at/above its pinned floor."""
    b.alloc.check()
    refs = _cache_refs(b.prefix_cache) if b.prefix_cache else {}
    for p, r in refs.items():
        assert b.alloc.refcount[p] >= r, (p, int(b.alloc.refcount[p]), r)
    cache_live = sum(1 for p in refs)
    assert b.alloc.live_count == cache_live, \
        (b.alloc.live_count, cache_live)
    assert int(b._resv.sum()) == 0 and b._outstanding == 0


def test_abort_queued_request():
    srv = _server(slots=1)
    srv.submit(PROMPTS[0], SamplingParams(max_new_tokens=30))
    u = srv.submit(PROMPTS[1], SamplingParams(max_new_tokens=4))
    assert srv.abort(u)
    events = srv.run()
    out = srv.output(u)
    assert out.finish_reason == "aborted" and out.token_ids == []
    assert out.ttft is None and out.tpot is None
    assert len(srv.output(0).token_ids) == 30
    assert not srv.abort(u)                   # already finished
    # the aborted request still surfaced exactly one terminal event
    term = [ev for ev in events if ev.uid == u]
    assert len(term) == 1
    assert term[0].token is None and term[0].finish_reason == "aborted"


def test_every_request_gets_exactly_one_terminal_event():
    """Completion, mid-flight abort, and abort-after-drain all surface
    exactly one finish_reason-bearing event per request."""
    srv = _server(slots=2)
    u0 = srv.submit(PROMPTS[0], SamplingParams(max_new_tokens=3))
    u1 = srv.submit(list(range(1, 40)), SamplingParams(max_new_tokens=9))
    events = list(srv.step())
    srv.abort(u1)                             # mid-flight
    events += srv.run()
    terminals = {}
    for ev in events:
        if ev.finish_reason is not None:
            assert ev.uid not in terminals
            terminals[ev.uid] = ev.finish_reason
    assert terminals == {u0: "length", u1: "aborted"}


def test_release_bounds_host_bookkeeping():
    srv = _server()
    outs = srv.generate(PROMPTS, SamplingParams(max_new_tokens=3))
    assert len(outs) == 4
    # generate() released its own requests: nothing retained host-side
    assert not srv._requests and not srv._batcher.completed
    assert srv.outputs() == []
    # uids keep advancing, previous outputs unaffected
    more = srv.generate(PROMPTS[:1], SamplingParams(max_new_tokens=2))
    assert more[0].uid == 4
    u = srv.submit(PROMPTS[0], SamplingParams(max_new_tokens=50))
    with pytest.raises(ValueError, match="in flight"):
        srv.release(u)


def test_abort_mid_chunked_prefill_restores_shared_pool():
    srv = _server(eng_kw=dict(shared_pool=True), slots=2, chunk=16)
    b = srv._batcher
    u0 = srv.submit(list(range(1, 60)), SamplingParams(max_new_tokens=4))
    u1 = srv.submit(list(range(2, 40)), SamplingParams(max_new_tokens=4))
    srv.step()                                 # first chunks only
    assert any(ps.req.uid == u0 for ps in b._prefill_live.values())
    assert b.alloc.live_count > 0
    assert srv.abort(u0)
    b.alloc.check()                            # conservation mid-flight
    srv.run()                                  # survivor drains normally
    assert srv.output(u0).finish_reason == "aborted"
    assert srv.output(u1).finish_reason == "length"
    _assert_pool_clean(b)


def test_abort_mid_decode_restores_shared_pool_and_cache_floor():
    """Abort a decoding request whose prompt pages the prefix cache
    pinned: its refcounts drop by the slot's references ONLY — the cache
    keeps its floor — and conservation holds through the drain."""
    srv = _server(eng_kw=dict(shared_pool=True), slots=2, chunk=16)
    b = srv._batcher
    sysp = list(range(1, 33))                  # two full shared pages
    u0 = srv.submit(sysp + [40, 41], SamplingParams(max_new_tokens=20))
    while not srv._requests[u0].output:        # drive into decode
        srv.step()
    floor = _cache_refs(b.prefix_cache)
    assert floor                               # prompt pages registered
    # a second request maps the cached prefix read-only, then is aborted
    u1 = srv.submit(sysp + [50, 51], SamplingParams(max_new_tokens=20))
    while not srv._requests[u1].output:
        srv.step()
    srv.step()
    assert srv.abort(u1)
    b.alloc.check()
    for p, r in floor.items():
        assert b.alloc.refcount[p] >= r        # floor intact
    srv.run()
    assert srv.output(u1).finish_reason == "aborted"
    assert len(srv.output(u0).token_ids) == 20
    _assert_pool_clean(b)


def test_abort_unknown_uid_is_false():
    srv = _server()
    assert not srv.abort(99)


# ---------------------------------------------------------------------------
# facade is the sole front door
# ---------------------------------------------------------------------------

def test_no_direct_batcher_construction_outside_serving():
    """launch/, examples/ and benchmarks/ must build serving through
    KVNANDServer — never by hand-wiring the batchers."""
    root = pathlib.Path(__file__).resolve().parent.parent
    offenders = []
    for d in ("src/repro/launch", "examples", "benchmarks"):
        for f, text in ((f, f.read_text())
                        for f in (root / d).rglob("*.py")):
            if re.search(r"(ContinuousBatcher|SpliceBatcher)\s*\(", text):
                offenders.append(str(f))
    assert not offenders, offenders


def test_server_config_validates_scheduler():
    with pytest.raises(ValueError, match="unknown scheduler"):
        ServerConfig(scheduler="fifo")
