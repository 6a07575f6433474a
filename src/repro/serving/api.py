"""Request-centric serving API — the single front door to the engine.

Everything a caller needs to serve mixed multi-user traffic lives here:

  * `SamplingParams` (re-exported from `serving/sampler.py`) — frozen
    per-request knobs: temperature / top-k / top-p / seed /
    max_new_tokens / stop_token_ids / logprobs;
  * `RequestOutput` — the finished request: token ids, optional
    per-token logprobs, a `finish_reason` in {stop, length, capacity,
    aborted, deadline}, and submit/first-token/finish timestamps with
    derived TTFT (time to first token) and TPOT (time per output token);
  * `StreamEvent` — one incrementally generated token, as yielded by
    `KVNANDServer.step()` / `stream()`; the events of a request
    concatenate exactly to its final `RequestOutput.token_ids`;
  * `ServerConfig` + `KVNANDServer` — the facade.  Constructing the
    server is the ONLY supported way to stand up serving: it builds the
    model, the engine and the scheduler (`interleaved` chunked-prefill
    continuous batching, or the `splice` baseline; shared-pool paged KV
    comes from `ServerConfig.engine`), and offers `generate()` for
    batch-synchronous use, `submit()`/`step()`/`stream()` for
    incremental use, and `abort()` for cancellation at any stage —
    queued, mid-chunked-prefill, or decoding — with shared-pool pages
    returned through the allocator, refcounts intact.
    `ServerConfig.speculation_k` turns decode steps into draft-and-
    verify steps (DESIGN.md §11) with per-request acceptance stats on
    `RequestOutput`.

The deep half of the design — per-slot sampling params consumed as
traced arrays INSIDE the jitted decode step, so a batch mixing any
number of distinct `SamplingParams` costs exactly one compile — lives in
`serving/sampler.py` and `serving/scheduler.py`; see DESIGN.md §10.
The full reference for this surface is docs/api.md.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Iterator, List, Optional, Sequence, Union

import jax
import numpy as np

from repro.configs import EngineConfig, get_config
from repro.configs.base import ModelConfig
from repro.models.registry import Model
from repro.models.transformer import Runtime
from repro.serving.sampler import SamplingParams
from repro.serving.scheduler import (ContinuousBatcher, Request,
                                     SpliceBatcher)

__all__ = ["SamplingParams", "RequestOutput", "StreamEvent",
           "ServerConfig", "KVNANDServer", "latency_percentile",
           "accepted_tokens_per_step"]


def accepted_tokens_per_step(accepted: int, steps: int) -> Optional[float]:
    """Mean tokens emitted per verify step: `steps` spans each emitted
    their accepted drafts plus the correction/bonus token.  None when
    nothing decoded speculatively — the ONE definition shared by
    `RequestOutput`, `launch/serve.py`'s report, and the
    `serving/spec/accepted_per_step` bench row."""
    if steps == 0:
        return None
    return (accepted + steps) / steps

_SCHEDULERS = {"interleaved": ContinuousBatcher, "splice": SpliceBatcher}


@dataclasses.dataclass(frozen=True)
class ServerConfig:
    """Everything needed to stand up a `KVNANDServer`.

    ``speculation_k`` turns every decode step into a draft-and-verify
    step: each running request drafts up to k tokens by prompt lookup
    over its own history and the engine verifies the span in one
    forward pass (DESIGN.md §11).  Outputs are token-identical to
    sequential decoding (greedy and seeded sampling alike); only the
    tokens-per-step changes.  (For quantized kv8/kv4 pools the span
    logits match sequential decode up to the format's own quantization
    noise — DESIGN.md §11 — so identity there is empirical, not a
    floating-point guarantee.)  ``None`` defers to
    ``EngineConfig.speculation_k`` (which `core.dse
    .recommend_engine_config` can set from the flash model); ``0``
    forces sequential decode.  Per-request opt-out / tighter caps:
    `SamplingParams.speculation`.
    """
    arch: str = "qwen1.5-0.5b"
    reduced: bool = False           # paper-scale vs CI-scale model dims
    engine: Optional[EngineConfig] = None   # None -> paged ragged default
    scheduler: str = "interleaved"  # "interleaved" | "splice" (baseline)
    batch_slots: int = 4
    max_context: int = 256
    prefill_chunk_tokens: int = 64
    step_token_budget: Optional[int] = None
    seed: int = 0                   # params init + default request streams
    max_steps: int = 100_000        # drain guard for generate()/run()
    speculation_k: Optional[int] = None     # None -> engine.speculation_k
    # tiered pool (EngineConfig.hot_pages > 0, DESIGN.md §13): promote
    # the next admission's prefix-hit pages at the end of each step so
    # the admission pins warm pages instead of demand-faulting; off =
    # every capacity-tier map-in stalls (the ablation serving_bench
    # measures).  Ignored by single-tier pools.
    tier_prefetch: bool = True
    # overlapped host/device pipeline (DESIGN.md §14): stream()/run()/
    # generate() dispatch step N+1 before collecting step N, so host
    # token emission and bookkeeping hide behind device compute.
    # Outputs are token-identical to the synchronous schedule (same
    # per-request PRNG streams, same in-order per-request emission);
    # speculative decoding degrades to the synchronous schedule
    # automatically.
    overlap: bool = False

    def __post_init__(self):
        if self.scheduler not in _SCHEDULERS:
            raise ValueError(
                f"unknown scheduler {self.scheduler!r}; pick one of "
                f"{sorted(_SCHEDULERS)}")
        if self.speculation_k is not None and self.speculation_k < 0:
            raise ValueError(f"speculation_k must be >= 0, "
                             f"got {self.speculation_k}")


@dataclasses.dataclass(frozen=True)
class StreamEvent:
    """One incrementally generated token of one request.  Every request
    ends with exactly one event carrying `finish_reason`: normally its
    last token; a request aborted without a fresh token gets a terminal
    marker event with `token=None` (and `index` = tokens emitted)."""
    uid: int
    token: Optional[int]
    index: int                      # position within the request's output
    logprob: Optional[float] = None         # when the request asked
    finish_reason: Optional[str] = None     # set on the terminal event


@dataclasses.dataclass
class RequestOutput:
    """A finished request, with timing counters for serving metrics and
    — when the server ran speculative decoding — per-request acceptance
    stats (`spec_steps` verify steps, `spec_drafted` offered drafts,
    `spec_accepted` accepted drafts; all 0 under sequential decode).

    Under a TIERED pool (DESIGN.md §13), `tier_hit_pages` counts cached
    pages this request mapped while they were hot-resident and
    `tier_stall_tokens` the pages it had to demand-promote from the
    capacity tier at admission (its share of the fleet's stall tokens);
    both stay 0 for single-tier pools and cache-miss prompts."""
    uid: int
    prompt: List[int]
    token_ids: List[int]
    logprobs: Optional[List[float]]
    finish_reason: str      # stop | length | capacity | aborted | deadline
    submit_time: float
    first_token_time: Optional[float]   # None: aborted before any token
    finish_time: float
    spec_steps: int = 0
    spec_drafted: int = 0
    spec_accepted: int = 0
    tier_hit_pages: int = 0
    tier_stall_tokens: int = 0

    @property
    def ttft(self) -> Optional[float]:
        """Time to first token (seconds), None if none was generated."""
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.submit_time

    @property
    def accepted_tokens_per_step(self) -> Optional[float]:
        """Mean tokens emitted per verify step (accepted drafts + the
        correction/bonus token); 1.0 means drafting never helped, None
        when the request never decoded speculatively (steps where the
        request could offer no draft — opt-out, last-token budget —
        run sequentially and are not counted)."""
        return accepted_tokens_per_step(self.spec_accepted,
                                        self.spec_steps)

    @property
    def tpot(self) -> Optional[float]:
        """Mean time per output token AFTER the first (seconds); None
        for zero- or one-token outputs."""
        if self.first_token_time is None or len(self.token_ids) < 2:
            return None
        return ((self.finish_time - self.first_token_time)
                / (len(self.token_ids) - 1))


class KVNANDServer:
    """Facade over engine + runtime + scheduler construction and the
    request lifecycle.  `cfg`/`params`/`rt` overrides let callers serve
    a model they already built (e.g. freshly trained weights);
    `device` places the weights, the KV cache and every step's inputs on
    one device (one replica per accelerator, `serving.replica`)."""

    def __init__(self, config: Optional[ServerConfig] = None, *,
                 cfg: Optional[ModelConfig] = None, params=None,
                 rt: Optional[Runtime] = None,
                 device: Optional[jax.Device] = None):
        self.config = config = config or ServerConfig()
        if cfg is None:
            cfg = get_config(config.arch)
            if config.reduced:
                cfg = cfg.reduced()
        self.cfg = cfg
        rt = rt or Runtime()
        if params is None:
            with jax.default_device(device):
                params = Model(cfg, rt).init(
                    jax.random.PRNGKey(config.seed))
        spec_k = config.speculation_k
        if spec_k is None:
            spec_k = (config.engine.speculation_k
                      if config.engine is not None else 0)
        self._batcher = _SCHEDULERS[config.scheduler](
            cfg, params, batch_slots=config.batch_slots,
            max_context=config.max_context, eng=config.engine, rt=rt,
            seed=config.seed,
            prefill_chunk_tokens=config.prefill_chunk_tokens,
            step_token_budget=config.step_token_budget,
            speculation_k=spec_k, tier_prefetch=config.tier_prefetch,
            device=device)
        self._requests: Dict[int, Request] = {}
        self._streamed: Dict[int, int] = {}
        self._done_emitted: set = set()
        self._next_uid = 0

    # -- introspection --------------------------------------------------
    @property
    def stats(self) -> Dict[str, int]:
        return self._batcher.stats

    @property
    def engine(self):
        return self._batcher.engine

    def _busy(self) -> bool:
        b = self._batcher
        return bool(b.queue) or any(r is not None for r in b.slots)

    # -- request lifecycle ----------------------------------------------
    def submit(self, prompt: Sequence[int],
               params: Optional[SamplingParams] = None, *,
               uid: Optional[int] = None, priority: int = 0,
               deadline: Optional[float] = None) -> int:
        """Queue one prompt; returns its uid.  Raises (and records
        nothing) on invalid prompts — empty, over slot/pool capacity.

        `priority` (lower admits first; default class 0) and `deadline`
        (seconds from now) shape the scheduler's ADMISSION order:
        waiting requests admit by (priority, nearest deadline, submit
        order), and a request still queued when its deadline passes
        finishes as ``"deadline"`` without consuming pages or steps.
        Neither preempts already-running requests."""
        if uid is None:
            uid = self._next_uid
        if uid in self._requests:
            raise ValueError(f"uid {uid} already submitted")
        if deadline is not None and deadline <= 0:
            raise ValueError(f"deadline must be > 0 seconds, "
                             f"got {deadline}")
        params = params or SamplingParams()
        req = Request(uid=uid, prompt=list(prompt),
                      max_new=params.max_new_tokens, params=params,
                      priority=priority,
                      deadline_ts=(time.monotonic() + deadline
                                   if deadline is not None else None))
        self._batcher.submit(req)
        self._requests[uid] = req
        self._streamed[uid] = 0
        self._next_uid = max(self._next_uid, uid + 1)
        return uid

    def abort(self, uid: int) -> bool:
        """Cancel a queued or running request (`finish_reason="aborted"`,
        shared-pool pages returned).  False for unknown/finished uids."""
        req = self._requests.get(uid)
        if req is None or req.done:
            return False
        return self._batcher.abort(uid)

    def step(self) -> List[StreamEvent]:
        """One scheduler step (admissions + prefill chunks + the decode
        batch); returns the tokens that became available, in request
        submission order, plus terminal marker events for requests that
        finished WITHOUT a fresh token (aborts)."""
        self._batcher.step()
        return self._drain_events()

    def dispatch(self) -> int:
        """Pipelined driver surface (DESIGN.md §14): enqueue the next
        step's device work without materializing its tokens.  Pair every
        dispatch with a later `collect()`; `step()` is the synchronous
        composition of the two."""
        return self._batcher.dispatch()

    def collect(self) -> List[StreamEvent]:
        """Materialize the oldest dispatched step and return its events
        (same shape as `step()`'s)."""
        self._batcher.collect()
        return self._drain_events()

    def pending_steps(self) -> int:
        """Dispatched-but-uncollected scheduler steps (0 outside the
        pipelined driver)."""
        return self._batcher.pending_steps

    def _drain_events(self) -> List[StreamEvent]:
        events: List[StreamEvent] = []
        for uid, req in self._requests.items():
            n0 = self._streamed[uid]
            out = req.output
            done_now = req.done and uid not in self._done_emitted
            if n0 == len(out) and not done_now:
                continue
            want_lp = req.params.logprobs
            for j in range(n0, len(out)):
                last = done_now and j == len(out) - 1
                events.append(StreamEvent(
                    uid=uid, token=out[j], index=j,
                    logprob=req.logprobs[j] if want_lp else None,
                    finish_reason=req.finish_reason if last else None))
            self._streamed[uid] = len(out)
            if done_now:
                if n0 == len(out):      # finished with no fresh token:
                    events.append(StreamEvent(  # aborted -> marker event
                        uid=uid, token=None, index=len(out),
                        finish_reason=req.finish_reason))
                self._done_emitted.add(uid)
        return events

    def stream(self) -> Iterator[StreamEvent]:
        """Iterate stepwise until every submitted request finishes,
        yielding each new token as its step produces it.  With
        ``ServerConfig.overlap`` the loop software-pipelines the
        scheduler — dispatch step N+1, then collect step N — so the
        host-side emission each iteration yields from overlaps the
        device compute already in flight; each request's token stream
        is identical either way (only the cross-request interleaving
        may shift by one step around prefill handoffs)."""
        steps = 0
        if not self.config.overlap:
            while self._busy():
                if steps >= self.config.max_steps:
                    raise RuntimeError(
                        f"stream: max_steps={self.config.max_steps} "
                        "exhausted with requests still pending")
                yield from self.step()
                steps += 1
            yield from self._drain_events()
            return
        if self._busy():
            self._batcher.dispatch()    # prime the pipeline (step 0)
        while self._busy() or self._batcher.pending_steps:
            if steps >= self.config.max_steps:
                raise RuntimeError(
                    f"stream: max_steps={self.config.max_steps} exhausted "
                    "with requests still pending")
            if self._busy():
                self._batcher.dispatch()    # step N+1 onto the device
            yield from self.collect()       # step N's tokens (host sync)
            steps += 1
        # aborts between steps retire requests without a scheduler step:
        # flush their terminal marker events
        yield from self._drain_events()

    def run(self) -> List[StreamEvent]:
        """Drain every pending request; returns all events (generate()
        without the per-uid bookkeeping)."""
        return list(self.stream())

    def generate(self, prompts: Sequence[Sequence[int]],
                 params: Union[SamplingParams, Sequence[SamplingParams],
                               None] = None) -> List[RequestOutput]:
        """Submit `prompts` (each a token-id list) and drain to
        completion.  `params`: one SamplingParams for all, a list
        (paired with prompts), or None (greedy defaults).  Returns
        outputs in prompt order."""
        if isinstance(params, SamplingParams) or params is None:
            plist = [params] * len(prompts)
        else:
            plist = list(params)
            if len(plist) != len(prompts):
                raise ValueError(
                    f"{len(plist)} SamplingParams for "
                    f"{len(prompts)} prompts")
        uids = [self.submit(p, sp) for p, sp in zip(prompts, plist)]
        self.run()
        outs = [self.output(u) for u in uids]
        for u in uids:                 # batch-synchronous callers never
            self.release(u)            # re-read: keep the server bounded
        return outs

    def output(self, uid: int) -> RequestOutput:
        """The finished request's RequestOutput (raises if unknown or
        still in flight)."""
        req = self._requests.get(uid)
        if req is None:
            raise KeyError(f"unknown uid {uid}")
        if not req.done:
            raise ValueError(f"request {uid} still in flight")
        return RequestOutput(
            uid=uid, prompt=list(req.prompt), token_ids=list(req.output),
            logprobs=list(req.logprobs) if req.params.logprobs else None,
            finish_reason=req.finish_reason, submit_time=req.submit_ts,
            first_token_time=req.first_ts, finish_time=req.finish_ts,
            spec_steps=req.spec_steps, spec_drafted=req.spec_drafted,
            spec_accepted=req.spec_accepted,
            tier_hit_pages=req.tier_hits,
            tier_stall_tokens=req.tier_stalls)

    def outputs(self) -> List[RequestOutput]:
        """Every finished, unreleased request, in uid order."""
        return [self.output(u) for u in sorted(self._requests)
                if self._requests[u].done]

    def release(self, uid: int) -> None:
        """Drop a FINISHED request's host bookkeeping (server and
        scheduler).  Incremental (`submit`/`step`) callers serving
        long-lived traffic should release requests once consumed, or
        per-step event scans and completed-request maps grow with the
        server's lifetime; `generate()` releases its own."""
        req = self._requests.get(uid)
        if req is None:
            return
        if not req.done:
            raise ValueError(f"request {uid} still in flight")
        del self._requests[uid]
        del self._streamed[uid]
        self._done_emitted.discard(uid)
        self._batcher.completed.pop(uid, None)


def latency_percentile(vals: Sequence[float], q: float) -> float:
    """Percentile over TTFT/TPOT samples (NaN when none exist — e.g.
    every request aborted before its first token)."""
    vals = [v for v in vals if v is not None]
    if not vals:
        return float("nan")
    return float(np.percentile(np.asarray(vals, np.float64), q))
