"""Spans and counters of the serving step, read back from a profiler
trace on the CPU: the host spans the scheduler and the engine loop
record (`kvnand.*`, one per boundary per step, leaves that never
overlap) and the decode-walk counters (`decode_pages_walked` /
`decode_pages_live`) against a hand count."""
import glob
import os
import threading

import jax
import pytest
from jax.profiler import ProfileData

from repro.configs import EngineConfig, get_config
from repro.models.registry import Model
from repro.models.transformer import Runtime
from repro.serving.api import ServerConfig
from repro.serving.async_server import AsyncServerConfig, BackgroundServer
from repro.serving.metrics import ServingMetrics
from repro.serving.scheduler import ContinuousBatcher, Request

HOST_WORK = ("kvnand.admit", "kvnand.prefill_enqueue",
             "kvnand.decode_enqueue", "kvnand.emit", "kvnand.route",
             "kvnand.commands")
WAITS = ("kvnand.first_token_wait", "kvnand.fetch")
SPANS = HOST_WORK + WAITS
T = 16


@pytest.fixture(scope="module")
def model():
    cfg = get_config("qwen1.5-0.5b").reduced()
    return cfg, Model(cfg, Runtime()).init(jax.random.PRNGKey(0))


def traced(fn, trace_dir):
    """Run fn() under the profiler; return the program's host spans as
    (line, name, start ns, end ns, args), sorted by start."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in SPANS:
                    out.append((line.name, ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda s: s[2])


def assert_leaves(spans):
    """No program span starts before the previous one on its thread
    ended: the spans are leaves, and never nest."""
    last = {}
    for line, name, a, b, _ in spans:
        prev = last.get(line)
        assert prev is None or a >= prev[1], (prev, name)
        last[line] = (name, b)


def counted(obj, attr, box):
    fn = getattr(obj, attr)

    def wrapped(*a, **k):
        box[attr] = box.get(attr, 0) + 1
        return fn(*a, **k)
    setattr(obj, attr, wrapped)


def test_batcher_spans_one_per_boundary_and_never_nest(model, tmp_path):
    """The overlapped schedule (dispatch N+1 before collect N): one
    decode_enqueue per decode dispatch, one fetch per collected step, one
    first_token_wait per prompt that finishes its prefill."""
    cfg, params = model
    b = ContinuousBatcher(cfg, params, batch_slots=2, max_context=64,
                          prefill_chunk_tokens=T)
    prompts = [list(range(1, 21)), list(range(5, 12)), list(range(3, 33))]
    for uid, p in enumerate(prompts):
        b.submit(Request(uid, p, max_new=5))
    calls = {}
    counted(b, "_dispatch_sequential", calls)
    counted(b, "_emit_decode", calls)
    counted(b, "_prefill_tick", calls)

    def drive():
        b.dispatch()
        while b.queue or any(s is not None for s in b.slots):
            b.dispatch()
            b.collect()
        while b.pending_steps:
            b.collect()

    spans = traced(drive, tmp_path)
    n = {s: sum(1 for x in spans if x[1] == s) for s in SPANS}
    assert all(len(r.output) == 5 for r in b.completed.values())
    assert n["kvnand.decode_enqueue"] == calls["_dispatch_sequential"] > 0
    assert n["kvnand.fetch"] == calls["_emit_decode"] \
        == calls["_dispatch_sequential"]
    assert n["kvnand.first_token_wait"] == len(prompts)
    assert n["kvnand.prefill_enqueue"] == calls["_prefill_tick"] == 5
    assert n["kvnand.admit"] > 0 and n["kvnand.emit"] >= n["kvnand.fetch"]
    assert_leaves(spans)
    enq = [x[4] for x in spans if x[1] == "kvnand.decode_enqueue"]
    assert all(1 <= a["rows"] <= 2 for a in enq)


def test_engine_loop_spans_route_and_commands(model, tmp_path):
    """The HTTP engine loop adds route (every iteration) and commands
    (only when a submission was applied); the loop's spans stay leaves."""
    import http.client
    import json
    cfg, params = model
    config = ServerConfig(engine=EngineConfig(page_tokens=T),
                          batch_slots=2, max_context=64,
                          prefill_chunk_tokens=T)
    with BackgroundServer(config, AsyncServerConfig(), cfg=cfg,
                          params=params) as srv:
        def post():
            conn = http.client.HTTPConnection(*srv.address, timeout=60)
            conn.request("POST", "/v1/completions", json.dumps(
                {"prompt": list(range(1, 12)), "max_tokens": 4}))
            assert conn.getresponse().status == 200
            conn.close()

        def two_clients():
            th = [threading.Thread(target=post) for _ in range(2)]
            for t in th:
                t.start()
            for t in th:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in th)

        spans = traced(two_clients, tmp_path)
    names = {x[1] for x in spans}
    assert set(SPANS) <= names
    assert 1 <= sum(1 for x in spans if x[1] == "kvnand.commands") <= 2
    assert_leaves(spans)


def kernel_lengths(b, box):
    """Record every sequential decode step's kernel lengths: each row's
    keys after the step's append, 0 for a row the step leaves alone."""
    fn = b._dispatch_sequential

    def wrapped(active):
        out = fn(active)
        box.append([int(b._lengths[i]) if i in active else 0
                    for i in range(b.B)])
        return out
    b._dispatch_sequential = wrapped


def fetched(n, pages, ppb):
    """Pages the kernel fetches for a row of n keys over `pages` pages
    per row in blocks of `ppb`: the blocks up to its last written page,
    and one block for a row with none."""
    live = min(-(-n // T), pages)
    return max(-(-live // ppb), 1) * ppb


def test_walk_counters_match_a_hand_count(model):
    """walked: every decode step fetches, per row, the 8-page blocks up
    to its last written page of 256 // 16 (one block for an idle slot);
    live: a request with an n-token prompt decodes max_new - 1 steps,
    the j-th over n + j tokens."""
    cfg, params = model
    b = ContinuousBatcher(cfg, params, batch_slots=2, max_context=256,
                          prefill_chunk_tokens=T)
    lens, max_new = (20, 120, 7), 14
    for uid, n in enumerate(lens):
        b.submit(Request(uid, list(range(1, n + 1)), max_new=max_new))
    steps = []
    kernel_lengths(b, steps)
    b.run_to_completion()
    live = sum(-(-(n + j) // T) for n in lens for j in range(1, max_new))
    assert b.stats["decode_pages_live"] == live
    walked = sum(fetched(n, 256 // T, 8) for row in steps for n in row)
    assert b.stats["decode_pages_walked"] == walked
    # the 120-token request crosses into its second block; no step walks
    # the whole grid
    assert max(max(row) for row in steps) > 8 * T
    assert walked < len(steps) * 2 * (256 // T)
    text = ServingMetrics().render(b.stats)
    assert f"kvnand_decode_pages_walked_total " \
        f"{b.stats['decode_pages_walked']}" in text
    assert f"kvnand_decode_pages_live_total {live}" in text


def test_walk_counters_shared_pool_use_the_table_width(model):
    """A shared pool's walk spans the page table's width per row,
    whatever the pool's physical size, one page a block: a decode step
    fetches each row's pages up to its last, and one page of an idle
    slot."""
    cfg, params = model
    eng = EngineConfig(page_tokens=T, shared_pool=True, total_pages=24)
    b = ContinuousBatcher(cfg, params, batch_slots=2, max_context=96,
                          eng=eng, prefill_chunk_tokens=T)
    assert b.engine.decode_page_visits(b.cache) == 2 * (96 // T)
    assert b.engine.decode_page_visits(b.cache, lengths=[96, 0]) == \
        96 // T + 1
    b.submit(Request(0, list(range(1, 18)), max_new=3))
    b.run_to_completion()
    assert b.stats["decode_pages_live"] == 2 + 2        # 18, 19 tokens
    assert b.stats["decode_pages_walked"] == (2 + 1) * 2


def test_walk_counters_under_speculation(model):
    """A verify step's jnp walk reads every page of every row; a
    sequential step (when no row may accept) fetches the kernel's live
    blocks.  walked counts every enqueued step once, whichever kind it
    was, and its live pages cover each row's span."""
    cfg, params = model
    b = ContinuousBatcher(cfg, params, batch_slots=2, max_context=256,
                          prefill_chunk_tokens=T, speculation_k=2)
    for uid, n in enumerate((20, 9)):
        b.submit(Request(uid, [1, 2, 3] * (n // 3) + [1] * (n % 3),
                         max_new=8))
    calls, steps = {}, []
    counted(b, "_dispatch_decode", calls)
    kernel_lengths(b, steps)
    b.run_to_completion()
    assert b.stats["spec_steps"] > 0
    verify = calls["_dispatch_decode"] - len(steps)
    assert b.stats["decode_pages_walked"] == \
        verify * 2 * (256 // T) + sum(fetched(n, 256 // T, 8)
                                      for row in steps for n in row)
    assert 0 < b.stats["decode_pages_live"] <= b.stats["decode_pages_walked"]
