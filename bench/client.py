"""The benchmark's own HTTP client: streamed completions over asyncio.

One connection per request, as the front door serves them: `POST
/v1/completions` with `stream: true`, `logprobs: true`, greedy decoding
and a token-id prompt.  Every SSE `data:` chunk is stamped with the host
clock (`time.perf_counter`) as it is read, so TTFT and inter-token gaps
are what a client on the same host sees.
"""
from __future__ import annotations

import asyncio
import dataclasses
import json
import time
from typing import List, Optional


@dataclasses.dataclass
class Record:
    """What the client saw of one request (times: perf_counter s)."""
    rid: int
    n_prompt: int
    max_tokens: int
    due: float = 0.0                 # when it was due to be sent
    sent: Optional[float] = None     # when its bytes went out
    status: Optional[int] = None
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)
    positions: List[int] = dataclasses.field(default_factory=list)
    logprobs: List[float] = dataclasses.field(default_factory=list)
    finish_reason: Optional[str] = None
    error: Optional[str] = None
    ended: Optional[float] = None    # when the stream ended, either way
    cancelled: bool = False          # cut by the end of the run

    @property
    def ok(self) -> bool:
        return (self.error is None and self.status == 200
                and self.finish_reason == "length"
                and len(self.tokens) == self.max_tokens
                and self.positions == list(range(len(self.tokens))))

    @property
    def failed(self) -> bool:
        return self.ended is not None and not self.cancelled and not self.ok


async def complete(host: str, port: int, prompt: List[int], max_tokens: int,
                   rec: Record) -> Record:
    """Send one streamed completion and fill `rec` as chunks arrive."""
    body = json.dumps({"prompt": prompt, "max_tokens": max_tokens,
                       "temperature": 0.0, "stream": True,
                       "logprobs": True}).encode()
    head = (f"POST /v1/completions HTTP/1.1\r\nHost: {host}\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\n\r\n").encode()
    writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(head + body)
        rec.sent = time.perf_counter()
        await writer.drain()
        status = await reader.readline()
        rec.status = int(status.split()[1]) if status else None
        while (await reader.readline()) not in (b"\r\n", b"\n", b""):
            pass                                   # response headers
        if rec.status != 200:
            rec.error = (await reader.read()).decode("utf-8", "replace")
            return rec
        while True:
            line = await reader.readline()
            if not line:
                rec.error = rec.error or "stream closed before [DONE]"
                return rec
            if not line.startswith(b"data: "):
                continue
            data = line[6:].strip()
            if data == b"[DONE]":
                return rec
            t = time.perf_counter()
            ch = json.loads(data)["choices"][0]
            if ch["token"] is not None:
                rec.times.append(t)
                rec.tokens.append(ch["token"])
                rec.positions.append(ch["position"])
                rec.logprobs.append(ch["logprob"])
            if ch["finish_reason"] is not None:
                rec.finish_reason = ch["finish_reason"]
    except asyncio.CancelledError:
        rec.cancelled = True
        raise
    except (OSError, ValueError, KeyError, IndexError) as e:
        rec.error = f"{type(e).__name__}: {e}"
        return rec
    finally:
        rec.ended = time.perf_counter()
        if writer is not None:
            writer.close()
