"""Asyncio HTTP front door (serving/async_server.py, DESIGN.md §14).

End-to-end over a real socket via `BackgroundServer`: one-shot and SSE
``POST /v1/completions`` (tokens must match what `KVNANDServer` decodes
for the same prompt), request validation, ``GET /healthz`` and
``GET /metrics`` (Prometheus text with live latency/lifecycle series),
admission backpressure at ``max_queue`` (HTTP 429 + Retry-After), and
priority/deadline fields passing through to the scheduler.
"""
import http.client
import json
import threading
import time

import jax
import pytest

from repro.configs import EngineConfig, get_config
from repro.models.registry import Model
from repro.models.transformer import Runtime
from repro.serving.api import KVNANDServer, SamplingParams, ServerConfig
from repro.serving.async_server import AsyncServerConfig, BackgroundServer

ARCH = "qwen1.5-0.5b"

_CACHE = {}


def _model():
    if "m" not in _CACHE:
        cfg = get_config(ARCH).reduced()
        _CACHE["m"] = (cfg, Model(cfg, Runtime()).init(jax.random.PRNGKey(0)))
    return _CACHE["m"]


def _configs(slots=2, max_queue=32, overlap=True):
    cfg, params = _model()
    return dict(
        config=ServerConfig(
            engine=EngineConfig(page_tokens=16, shared_pool=True,
                                total_pages=64),
            batch_slots=slots, max_context=96, prefill_chunk_tokens=16),
        async_config=AsyncServerConfig(max_queue=max_queue,
                                       overlap=overlap),
        cfg=cfg, params=params)


def _post(addr, payload, timeout=60):
    conn = http.client.HTTPConnection(*addr, timeout=timeout)
    try:
        conn.request("POST", "/v1/completions", json.dumps(payload),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        return r.status, dict(r.getheaders()), r.read()
    finally:
        conn.close()


def _get(addr, path, timeout=30):
    conn = http.client.HTTPConnection(*addr, timeout=timeout)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        conn.close()


PROMPT = list(range(1, 12))


@pytest.fixture(scope="module")
def srv():
    with BackgroundServer(**_configs()) as s:
        yield s


# ---------------------------------------------------------------------------
# completions: one-shot and SSE, token-identical to the facade
# ---------------------------------------------------------------------------

def test_oneshot_completion_matches_facade(srv):
    cfg, params = _model()
    ref = KVNANDServer(_configs()["config"], cfg=cfg, params=params) \
        .generate([PROMPT], SamplingParams(max_new_tokens=6))[0]
    status, _, body = _post(srv.address,
                            {"prompt": PROMPT, "max_tokens": 6})
    assert status == 200
    out = json.loads(body)
    assert out["object"] == "text_completion"
    choice = out["choices"][0]
    assert choice["token_ids"] == ref.token_ids
    assert choice["finish_reason"] == "length"
    assert out["usage"] == {"prompt_tokens": len(PROMPT),
                            "completion_tokens": 6,
                            "total_tokens": len(PROMPT) + 6}


def test_sse_stream_concatenates_to_oneshot(srv):
    status, _, body = _post(srv.address,
                            {"prompt": PROMPT, "max_tokens": 5})
    oneshot = json.loads(body)["choices"][0]["token_ids"]
    conn = http.client.HTTPConnection(*srv.address, timeout=60)
    try:
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt": PROMPT, "max_tokens": 5,
                                 "stream": True}),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        assert r.status == 200
        assert r.getheader("Content-Type") == "text/event-stream"
        raw = r.read().decode()
    finally:
        conn.close()
    frames = [f for f in raw.split("\n\n") if f.startswith("data: ")]
    assert frames[-1] == "data: [DONE]"
    chunks = [json.loads(f[len("data: "):])["choices"][0]
              for f in frames[:-1]]
    assert [c["token"] for c in chunks] == oneshot
    assert [c["position"] for c in chunks] == list(range(5))
    assert [c["finish_reason"] for c in chunks] == \
        [None] * 4 + ["length"]


def test_sampling_params_pass_through(srv):
    status, _, body = _post(srv.address, {
        "prompt": PROMPT, "max_tokens": 4, "temperature": 0.8,
        "top_k": 5, "seed": 7, "logprobs": True})
    assert status == 200
    choice = json.loads(body)["choices"][0]
    assert len(choice["token_ids"]) == 4
    assert len(choice["logprobs"]) == 4
    assert all(lp <= 0.0 for lp in choice["logprobs"])


def test_stop_token_finish_over_http(srv):
    status, _, body = _post(srv.address,
                            {"prompt": PROMPT, "max_tokens": 8})
    toks = json.loads(body)["choices"][0]["token_ids"]
    # stop on the first token that the greedy stream has not emitted
    # before it (it may repeat a token)
    j = next((j for j in range(1, len(toks)) if toks[j] not in toks[:j]),
             0)
    status, _, body = _post(srv.address, {
        "prompt": PROMPT, "max_tokens": 8, "stop_token_ids": [toks[j]]})
    choice = json.loads(body)["choices"][0]
    assert choice["finish_reason"] == "stop"
    assert choice["token_ids"] == toks[:j + 1]


# ---------------------------------------------------------------------------
# validation and routing
# ---------------------------------------------------------------------------

def test_bad_requests(srv):
    status, _, body = _post(srv.address, {"prompt": "not tokens"})
    assert status == 400 and b"token ids" in body
    status, _, body = _post(srv.address, {"prompt": [1, True, 3]})
    assert status == 400
    status, _, body = _post(srv.address, {"prompt": []})
    assert status == 400                  # facade rejects empty prompts
    conn = http.client.HTTPConnection(*srv.address, timeout=30)
    try:
        conn.request("POST", "/v1/completions", b"{nope",
                     {"Content-Type": "application/json"})
        assert conn.getresponse().status == 400
    finally:
        conn.close()


def test_healthz_and_unknown_route(srv):
    status, body = _get(srv.address, "/healthz")
    assert (status, body) == (200, b"ok\n")
    status, _ = _get(srv.address, "/nope")
    assert status == 404


# ---------------------------------------------------------------------------
# metrics: live Prometheus text after real traffic
# ---------------------------------------------------------------------------

def test_metrics_exposition(srv):
    _post(srv.address, {"prompt": PROMPT, "max_tokens": 3})
    status, body = _get(srv.address, "/metrics")
    assert status == 200
    text = body.decode()
    for name in ("kvnand_ttft_seconds", "kvnand_tpot_seconds",
                 "kvnand_requests_finished_total",
                 "kvnand_rejected_total",
                 "kvnand_scheduler_steps_total",
                 "kvnand_decode_tokens_total",
                 "kvnand_device_idle_fraction",
                 "kvnand_queue_depth", "kvnand_pending_steps",
                 "kvnand_pool_util"):
        assert name in text, name
    assert 'kvnand_requests_finished_total{reason="length"}' in text
    counts = {line.split()[0]: float(line.split()[1])
              for line in text.splitlines()
              if line and not line.startswith("#")
              and "{" not in line.split()[0]}
    assert counts["kvnand_ttft_seconds_count"] >= 1
    assert counts["kvnand_decode_tokens_total"] >= 3
    assert 0.0 <= counts["kvnand_device_idle_fraction"] <= 1.0


# ---------------------------------------------------------------------------
# backpressure: saturation answers 429, never queues unboundedly
# ---------------------------------------------------------------------------

def test_zero_queue_rejects_everything():
    with BackgroundServer(**_configs(max_queue=0)) as s:
        status, headers, body = _post(s.address,
                                      {"prompt": PROMPT, "max_tokens": 2})
        assert status == 429
        assert headers.get("Retry-After") == "1"
        assert b"retry" in body.lower()
        _, text = _get(s.address, "/metrics")
        assert b"kvnand_rejected_total 1" in text


def test_saturation_mixes_429_and_service():
    """A burst far past slots + max_queue: some requests serve, the
    overflow is rejected with 429 — nothing hangs or errors out."""
    with BackgroundServer(**_configs(slots=1, max_queue=2)) as s:
        results = []
        lock = threading.Lock()

        def fire():
            st, _, _ = _post(s.address,
                             {"prompt": PROMPT, "max_tokens": 24})
            with lock:
                results.append(st)

        threads = [threading.Thread(target=fire) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert len(results) == 8
        assert set(results) <= {200, 429}
        assert 200 in results
        assert 429 in results


# ---------------------------------------------------------------------------
# priority / deadline pass-through
# ---------------------------------------------------------------------------

def test_deadline_expires_queued_request_over_http():
    with BackgroundServer(**_configs(slots=1)) as s:
        done = threading.Event()

        def long_one():
            _post(s.address, {"prompt": PROMPT, "max_tokens": 48})
            done.set()

        t = threading.Thread(target=long_one)
        t.start()
        time.sleep(0.3)                   # let it occupy the only slot
        status, _, body = _post(s.address, {
            "prompt": list(range(2, 9)), "max_tokens": 8,
            "deadline_s": 0.001})
        assert status == 200
        choice = json.loads(body)["choices"][0]
        assert choice["finish_reason"] == "deadline"
        assert choice["token_ids"] == []
        done.wait(timeout=120)
        t.join(timeout=5)
        _, text = _get(s.address, "/metrics")
        assert b'kvnand_requests_finished_total{reason="deadline"} 1' \
            in text


def test_bad_deadline_is_400(srv):
    status, _, _ = _post(srv.address, {
        "prompt": PROMPT, "max_tokens": 2, "deadline_s": -1})
    assert status == 400
