"""Overlapped host/device decode pipeline (DESIGN.md §14).

The pipelined schedule — dispatch step N+1 before collecting step N —
must be TOKEN-IDENTICAL to the synchronous loop: same per-request
fold_in PRNG streams, same emit order, same finish reasons, across
striped / shared / tiered pools, speculation on and off, and mixed
SamplingParams.  Plus the pipeline-specific hazards: phantom rows
(slots that finish or abort between dispatch and collect) are
discarded with shared-pool conservation intact, TTFT/TPOT timestamps
come from collect() (submit <= first <= finish in both modes), and
priority / deadline shape the admission order.
"""
import time

import jax
import pytest

from repro.configs import EngineConfig, get_config
from repro.models.registry import Model
from repro.models.transformer import Runtime
from repro.serving.api import KVNANDServer, SamplingParams, ServerConfig

ARCH = "qwen1.5-0.5b"

_CACHE = {}


def _model():
    if "m" not in _CACHE:
        cfg = get_config(ARCH).reduced()
        _CACHE["m"] = (cfg, Model(cfg, Runtime()).init(jax.random.PRNGKey(0)))
    return _CACHE["m"]


POOLS = {
    "striped": dict(),
    "shared": dict(shared_pool=True),
    "tiered": dict(shared_pool=True, total_pages=64, hot_pages=12),
}


def _server(pool="striped", *, overlap, spec_k=0, slots=2, ctx=96,
            chunk=16, **kw):
    cfg, params = _model()
    eng = EngineConfig(page_tokens=16, **POOLS[pool])
    return KVNANDServer(
        ServerConfig(engine=eng, batch_slots=slots, max_context=ctx,
                     prefill_chunk_tokens=chunk, overlap=overlap,
                     speculation_k=spec_k, **kw),
        cfg=cfg, params=params)


PROMPTS = [list(range(1, 8)), list(range(3, 24)), list(range(2, 13)),
           [5, 4, 3], list(range(4, 20))]

# mixed params: greedy, seeded-hot, top-k/p, stop tokens, logprobs
MIXED = [SamplingParams(max_new_tokens=6, logprobs=True),
         SamplingParams(max_new_tokens=8, temperature=0.9, seed=3),
         SamplingParams(max_new_tokens=7, temperature=1.2, top_k=5,
                        seed=9),
         SamplingParams(max_new_tokens=5, temperature=0.8, top_p=0.9,
                        top_k=7, seed=11),
         SamplingParams(max_new_tokens=9, stop_token_ids=(2, 7))]


def _signature(outs):
    return [(o.token_ids, o.logprobs, o.finish_reason) for o in outs]


# ---------------------------------------------------------------------------
# parity matrix: overlap == sync, every pool, spec on/off, mixed params
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pool", sorted(POOLS))
@pytest.mark.parametrize("spec_k", [0, 4], ids=["seq", "spec4"])
def test_overlap_matches_sync(pool, spec_k):
    sync = _server(pool, overlap=False, spec_k=spec_k)
    o_sync = sync.generate(PROMPTS, MIXED)
    over = _server(pool, overlap=True, spec_k=spec_k)
    o_over = over.generate(PROMPTS, MIXED)
    assert _signature(o_over) == _signature(o_sync)
    if spec_k == 0:
        # the pipelined drain really ran ahead of its collects
        assert over.stats["steps"] > 0
    else:
        # speculative steps are host-data-dependent: dispatch() degrades
        # to the synchronous schedule, but acceptance still fires
        assert over.stats["spec_accepted"] == sync.stats["spec_accepted"]


def test_overlap_stream_events_identical_per_request():
    """Not just final outputs: each request's event stream (token,
    index, finish_reason) matches event for event, in-order and
    gapless.  Only the cross-request interleaving may shift — a
    prefill-handoff token is host-sampled inside dispatch(N+1), so it
    can surface one collect earlier relative to other requests."""
    def trace(overlap):
        srv = _server("shared", overlap=overlap)
        uids = [srv.submit(p, sp) for p, sp in zip(PROMPTS, MIXED)]
        per = {u: [] for u in uids}
        for ev in srv.stream():
            assert ev.index == len(per[ev.uid])     # in-order, gapless
            per[ev.uid].append((ev.token, ev.index, ev.finish_reason))
        return per
    assert trace(True) == trace(False)


def test_overlap_capacity_finish_parity():
    """Capacity finishes are PREDICTED at dispatch (cap_finish) so the
    pipeline never dispatches a doomed row; tokens still match."""
    kw = dict(ctx=64, slots=1)
    prompts = [list(range(1, 41))]
    sp = SamplingParams(max_new_tokens=100)
    o_sync = _server("shared", overlap=False, **kw).generate(prompts, sp)
    o_over = _server("shared", overlap=True, **kw).generate(prompts, sp)
    assert _signature(o_over) == _signature(o_sync)
    assert o_over[0].finish_reason == "capacity"


# ---------------------------------------------------------------------------
# timing: timestamps taken at collect(), monotone in both modes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("overlap", [False, True], ids=["sync", "overlap"])
def test_timing_monotonic(overlap):
    """Regression for the pipelined path: first_token_time is stamped
    when the token MATERIALIZES at collect(), never at dispatch —
    submit <= first <= finish must hold in both modes."""
    srv = _server("shared", overlap=overlap)
    outs = srv.generate(PROMPTS[:3], SamplingParams(max_new_tokens=5))
    for o in outs:
        assert o.submit_time <= o.first_token_time <= o.finish_time
        assert o.ttft > 0.0 and o.tpot > 0.0


def test_device_idle_accounting():
    """The scheduler tracks host-observed device-idle time: a sync drain
    accumulates it (every collect empties the pipeline); it only ever
    grows and stays a float."""
    srv = _server("striped", overlap=False)
    srv.generate(PROMPTS[:2], SamplingParams(max_new_tokens=6))
    assert srv.stats["device_idle_s"] >= 0.0
    assert srv.stats["steps"] > 0


# ---------------------------------------------------------------------------
# phantom rows: abort between dispatch and collect, pages conserved
# ---------------------------------------------------------------------------

def _cache_refs(pc):
    refs = {}
    for p in pc._full.values():
        refs[p] = refs.get(p, 0) + 1
    for e in pc._exact.values():
        for p in e.pages:
            refs[p] = refs.get(p, 0) + 1
    return refs


def _assert_pool_clean(b):
    b.alloc.check()
    refs = _cache_refs(b.prefix_cache) if b.prefix_cache else {}
    for p, r in refs.items():
        assert b.alloc.refcount[p] >= r, (p, int(b.alloc.refcount[p]), r)
    assert b.alloc.live_count == len(refs), \
        (b.alloc.live_count, len(refs))
    assert int(b._resv.sum()) == 0 and b._outstanding == 0


def test_abort_between_dispatch_and_collect():
    """The hardest phantom: a slot aborted while its step is in flight.
    collect() must discard the stale row (no token credited to the dead
    request, no token credited to any successor in the slot) and the
    shared pool must balance through the drain."""
    srv = _server("shared", overlap=True, slots=2)
    b = srv._batcher
    u0 = srv.submit(list(range(1, 30)), SamplingParams(max_new_tokens=20))
    u1 = srv.submit(list(range(2, 12)), SamplingParams(max_new_tokens=6))
    # drive both into decode synchronously, then leave one step in flight
    while not (srv._requests[u0].output and srv._requests[u1].output):
        srv.step()
    srv.dispatch()
    assert srv.pending_steps() == 1
    n0 = len(srv._requests[u0].output)
    assert srv.abort(u0)                  # mid-flight: row becomes phantom
    b.alloc.check()                       # conservation before the collect
    events = srv.collect()
    assert srv.stats["phantom_tokens"] >= 1
    assert len(srv._requests[u0].output) == n0    # no post-abort token
    assert all(ev.uid != u0 or ev.token is None for ev in events)
    events += srv.run()
    out0, out1 = srv.output(u0), srv.output(u1)
    assert out0.finish_reason == "aborted"
    assert out1.finish_reason == "length" and len(out1.token_ids) == 6
    # exactly one terminal event each, aborted one token-free
    terms = {}
    for ev in events:
        if ev.finish_reason is not None:
            assert ev.uid not in terms
            terms[ev.uid] = ev
    assert terms[u0].token is None
    _assert_pool_clean(b)


def test_abort_whole_pipeline_then_resubmit():
    """Abort EVERY in-flight request, then reuse the same server: the
    phantom steps drain away and fresh traffic decodes normally."""
    srv = _server("shared", overlap=True, slots=2)
    us = [srv.submit(p, SamplingParams(max_new_tokens=30))
          for p in PROMPTS[:2]]
    while not all(srv._requests[u].output for u in us):
        srv.step()
    srv.dispatch()
    for u in us:
        srv.abort(u)
    srv.run()
    assert all(srv.output(u).finish_reason == "aborted" for u in us)
    ref = _server("shared", overlap=False).generate(
        PROMPTS[:1], SamplingParams(max_new_tokens=4))
    got = srv.generate(PROMPTS[:1], SamplingParams(max_new_tokens=4))
    assert _signature(got) == _signature(ref)
    _assert_pool_clean(srv._batcher)


def test_dispatch_depth_is_bounded():
    """Driver misuse — dispatch() hammered without collect() — must not
    grow the pipeline unboundedly: the scheduler self-collects past
    depth 2 (and speculation keeps depth <= 1 by auto-draining)."""
    srv = _server("striped", overlap=True, slots=1)
    srv.submit(PROMPTS[0], SamplingParams(max_new_tokens=20))
    for _ in range(6):
        srv.dispatch()
    assert srv.pending_steps() <= 2
    srv.run()
    assert len(srv.output(0).token_ids) == 20


# ---------------------------------------------------------------------------
# admission order: priority and deadlines
# ---------------------------------------------------------------------------

def test_priority_orders_admission():
    """With one slot occupied, the waiting queue admits by (priority,
    deadline, submit order) — a later high-priority submit overtakes an
    earlier low-priority one."""
    srv = _server("striped", overlap=False, slots=1)
    u_run = srv.submit(PROMPTS[0], SamplingParams(max_new_tokens=12))
    u_low = srv.submit(PROMPTS[1], SamplingParams(max_new_tokens=3),
                       priority=5)
    u_high = srv.submit(PROMPTS[2], SamplingParams(max_new_tokens=3),
                        priority=0)
    srv.run()
    o = {u: srv.output(u) for u in (u_run, u_low, u_high)}
    assert all(x.finish_reason == "length" for x in o.values())
    assert o[u_high].first_token_time < o[u_low].first_token_time


def test_ties_fall_back_to_submit_order():
    srv = _server("striped", overlap=False, slots=1)
    us = [srv.submit(p, SamplingParams(max_new_tokens=2))
          for p in PROMPTS[:3]]
    srv.run()
    firsts = [srv.output(u).first_token_time for u in us]
    assert firsts == sorted(firsts)


def test_deadline_expiry_drops_queued_request():
    """A request still queued past its deadline finishes as "deadline"
    without consuming pages or steps; the running request is untouched."""
    srv = _server("shared", overlap=True, slots=1)
    u0 = srv.submit(PROMPTS[0], SamplingParams(max_new_tokens=10))
    u1 = srv.submit(PROMPTS[1], SamplingParams(max_new_tokens=10),
                    deadline=1e-4)
    time.sleep(2e-3)                      # let the deadline lapse
    events = srv.run()
    out = srv.output(u1)
    assert out.finish_reason == "deadline"
    assert out.token_ids == [] and out.ttft is None
    assert srv.stats["deadline_drops"] == 1
    assert len(srv.output(u0).token_ids) == 10
    term = [ev for ev in events if ev.uid == u1]
    assert len(term) == 1 and term[0].token is None
    _assert_pool_clean(srv._batcher)


def test_deadline_validation():
    srv = _server("striped", overlap=False)
    with pytest.raises(ValueError, match="deadline"):
        srv.submit(PROMPTS[0], deadline=0.0)
