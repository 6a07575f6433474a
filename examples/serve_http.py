"""Async HTTP serving example: the OpenAI-style front door
(`repro.serving.async_server`, DESIGN.md §14) end-to-end over a real
socket with nothing but the standard library on the client side —
one-shot and SSE-streamed `POST /v1/completions`, a saturated queue
answering 429, and a live Prometheus `/metrics` scrape.

    PYTHONPATH=src python examples/serve_http.py
"""
import http.client
import json

from repro.configs import EngineConfig
from repro.serving.api import ServerConfig
from repro.serving.async_server import AsyncServerConfig, BackgroundServer

PROMPT = list(range(1, 14))


def post(addr, payload):
    conn = http.client.HTTPConnection(*addr, timeout=120)
    try:
        conn.request("POST", "/v1/completions", json.dumps(payload),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        conn.close()


def main():
    config = ServerConfig(
        arch="qwen1.5-0.5b", reduced=True,
        engine=EngineConfig(page_tokens=16, shared_pool=True),
        batch_slots=2, max_context=96, prefill_chunk_tokens=16)
    with BackgroundServer(config,
                          AsyncServerConfig(max_queue=8)) as srv:
        host, port = srv.address
        print(f"serving on http://{host}:{port} (overlap on)")

        # one-shot completion
        status, body = post(srv.address, {"prompt": PROMPT,
                                          "max_tokens": 8, "seed": 3})
        assert status == 200, status
        choice = json.loads(body)["choices"][0]
        print(f"one-shot: {len(choice['token_ids'])} tokens "
              f"({choice['finish_reason']}) -> {choice['token_ids']}")

        # the same request streamed over SSE: frames concatenate to the
        # one-shot answer (per-request determinism via the seed)
        conn = http.client.HTTPConnection(host, port, timeout=120)
        try:
            conn.request("POST", "/v1/completions",
                         json.dumps({"prompt": PROMPT, "max_tokens": 8,
                                     "seed": 3, "stream": True}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            assert resp.status == 200
            assert resp.getheader("Content-Type") == "text/event-stream"
            frames = [f for f in resp.read().decode().split("\n\n")
                      if f.startswith("data: ")]
        finally:
            conn.close()
        assert frames[-1] == "data: [DONE]"
        streamed = [json.loads(f[len("data: "):])["choices"][0]["token"]
                    for f in frames[:-1]]
        assert streamed == choice["token_ids"], (streamed, choice)
        print(f"SSE stream: {len(frames) - 1} frames + [DONE], "
              "tokens match the one-shot answer")

    # saturation: with no queue at all, excess load answers 429 with
    # Retry-After instead of queuing unboundedly
    with BackgroundServer(config,
                          AsyncServerConfig(max_queue=0)) as srv:
        status, body = post(srv.address, {"prompt": PROMPT,
                                          "max_tokens": 4})
        assert status == 429, status
        print(f"saturated queue -> HTTP {status} ({body.decode().strip()})")

        status, metrics = get(srv.address, "/metrics")
        assert status == 200
        text = metrics.decode()
        for name in ("kvnand_ttft_seconds", "kvnand_rejected_total",
                     "kvnand_pool_util", "kvnand_device_idle_fraction"):
            assert name in text, name
        rejected = [line for line in text.splitlines()
                    if line.startswith("kvnand_rejected_total")]
        print(f"/metrics live: {rejected[0]}")
    print("serve_http example complete")


def get(addr, path):
    conn = http.client.HTTPConnection(*addr, timeout=30)
    try:
        conn.request("GET", path)
        r = conn.getresponse()
        return r.status, r.read()
    finally:
        conn.close()


if __name__ == "__main__":
    main()
