"""Continuous-batching scheduler over the KVNAND engine.

The batchers here are INTERNAL engines behind the `KVNANDServer` facade
(`serving/api.py`) — launch/examples/benchmarks construct the facade,
not these classes.  Each request carries its own `SamplingParams`; the
per-slot temperature/top-k/top-p/seed arrays enter the jitted decode
step as traced arguments (one compile for any mix of combinations), and
each request draws from its own `(seed, position)` PRNG stream — see
DESIGN.md §10.

Chunked prefill interleaved with batched decode:

  * fixed decode batch of B slots; finished/empty slots are refilled from
    the queue between steps;
  * an admitted prompt is prefilled CHUNK BY CHUNK (page-aligned chunks of
    `prefill_chunk_tokens`) straight into its slot's stripe of the shared
    paged pool (`engine.prefill_chunk`) — no one-sequence side cache and
    no splice copy, so admission costs O(chunk) instead of O(prompt);
  * every step spends a token budget: the decode batch (one token per
    active slot) is reserved first, the remainder funds prefill chunks —
    so a steady stream of admits can never starve the decoders, and an
    idle decode batch drains the admission queue at full tilt;
  * decode steps carry an `active` mask so slots that are empty or still
    mid-prefill get no append / length advance (the ragged scatter path,
    `uniform_lengths=False`);
  * per-slot prefill progress (cursor into the prompt, sampled-token
    handoff; ring base positions live in the cache) is host bookkeeping —
    `_PrefillState`;
  * recurrent (ssm/hybrid) and prefix-carrying archs (hymba meta tokens
    would break page alignment of later chunks) prefill as ONE exact-
    length whole-prompt chunk — still in place, still spliceless;
  * slot eviction = clearing host bookkeeping — its pages are simply
    overwritten by the next occupant (per-sequence page stripes, the
    access-aware reuse story of §IV-D); the next occupant's first chunk
    rewrites the window-ring base row, so stale pages can never alias.

Shared-pool mode (``EngineConfig.shared_pool``, the §IV-D FTL mapping
proper) replaces the per-slot stripes with ONE physical page pool per
layer-group and moves allocation policy to this host scheduler:

  * admission is by FREE-PAGE COUNT, not free slots: a request is admitted
    when its worst-case footprint ceil((prompt + max_new)/T) pages (plus a
    window-ring allocation for local-attention archs) fits the pool's
    free + cache-evictable pages net of outstanding reservations — so many
    short requests share a pool that could hold only a few max_context
    stripes;
  * global-pool pages are allocated LAZILY as prefill chunks and decode
    appends land; window-ring pages are allocated eagerly at admission
    (the ring is bounded and recycled in place);
  * a radix-style PREFIX CACHE (`core/page_alloc.PrefixCache`) maps a new
    prompt's already-computed full-page prefixes read-only into its table
    (refcount++), and whole-prompt repeats skip prefill entirely (cached
    last-token logits); the first DECODE append into a shared partial
    page triggers COPY-ON-WRITE — the allocator hands the slot a private
    page, the device copies the page bytes, and the table repoints;
  * completion decrements refcounts and returns exclusive pages to the
    free list; pages referenced by the prefix cache survive until LRU
    eviction reclaims them under pressure.

Tiered mode (``EngineConfig.hot_pages``, DESIGN.md §13) splits that pool
into a device-resident HOT tier and a flash-resident CAPACITY tier: the
allocator keeps stable flash page ids, a `HotTier` maps resident ids to
hot slots (the values the page tables actually carry), demoted pages
park their bytes in a host-side store, and a queue-ahead prefetch stage
promotes the next admission's prefix-hit pages at the end of each step
so admissions pin warm pages instead of demand-faulting (faults =
`tier_stall_tokens`).  Pages mapped by a live slot are pinned hot and
never demoted, so decode/chunked-prefill/verify walks cannot fault.

Pipelined stepping (DESIGN.md §14): `step()` is now the back-to-back
composition of two halves —

  * `dispatch()` runs every piece of host bookkeeping step N+1 needs
    BEFORE its device work (admission, prefill chunks, page ensures /
    COWs, table pushes, tier promotions) and then ENQUEUES the jitted
    decode/verify step, keeping the returned token/logprob arrays as
    un-materialized device futures in an `_Inflight` record;
  * `collect()` materializes the OLDEST in-flight step with one
    `jax.device_get` round-trip, emits its tokens (TTFT/TPOT stamps are
    taken here, when tokens are host-visible), sweeps finishes, and
    runs the queue-ahead tier prefetch.

The synchronous schedule (`step()` = dispatch; collect) is bit-identical
to the pre-split loop.  An overlapped driver (serving/api.py `stream()`
with ``ServerConfig.overlap``, serving/async_server.py) instead calls
dispatch(N+1) BEFORE collect(N): the host emission/bookkeeping of step N
then runs concurrently with the device compute of step N+1, because the
dispatch feeds step N+1's token inputs straight from step N's on-device
`toks` array (a `jnp.where` merge against the host staging buffer — the
double-buffered token/mask path) and never blocks.  Stop-token finishes
are host-unpredictable at dispatch time, so an overlapped step may carry
PHANTOM rows for slots that turn out to have finished; collect discards
them by request identity (`_Inflight.reqs`), and the appended garbage
token is memory-safe because appends only land in slot-private pages
within the slot's reservation.  Length/capacity finishes ARE predictable
from host state, and such slots are excluded from the next dispatch.
Verify (speculative) steps consume host-visible history for drafts, so
`dispatch()` drains the pipeline first — speculation runs unoverlapped
but token-identical.

Admission order: `_queue_pick` admits by (priority, deadline, submit
order) — the default priority=0 / no-deadline case degrades to plain
FIFO, and queued requests whose deadline has already passed finish as
``"deadline"`` without costing pages or steps.

`SpliceBatcher` keeps the old admit-time full prefill + jit'd slot splice
as the measured baseline (benchmarks/serving_bench.py) and for parity
tests; the interleaved step never touches the splice path.  The splice
operation is meaningless against a shared pool (a B=1 cache owns a
different pool, and slot stripes no longer exist), so SpliceBatcher
fails fast when handed a shared-pool EngineConfig.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Deque, Dict, List, Optional, Set

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import EngineConfig, ModelConfig
from repro.core import paged_kv
from repro.core.engine import KVNANDEngine
from repro.core.page_alloc import (CacheHit, HotTier, OutOfHotSlots,
                                   OutOfPages, PageAllocator, PrefixCache)
from repro.models.transformer import Runtime
from repro.serving.draft import propose_draft
from repro.serving.sampler import (SamplingParams, request_keys,
                                   sample_with_logprobs,
                                   speculative_accept)

MIN_PROMPT_BUCKET = 16

# One-compiled-signature invariant (DESIGN.md §10/§15): when the test
# suite points this at a list, every batcher registers its decode/verify
# jitted callables here and tests/conftest.py asserts `_cache_size() <= 1`
# after each test — a silent recompile (second traced signature) fails
# the test that triggered it.  `None` (the default) keeps production
# servers free of the bookkeeping.
JIT_WATCH = None


def _watch_jit(name: str, fn) -> None:
    if JIT_WATCH is not None and fn is not None:
        JIT_WATCH.append((name, fn))


@functools.partial(jax.jit, static_argnames=("true_vocab",))
def _sample_one(lg, seeds, pos, t, k, p, *, true_vocab):
    """One-row sampler for the prefill handoff / exact-hit first token.
    Module-level so every batcher in the process shares ONE compile per
    (vocab, shape) — a fresh server does not re-pay the RNG lowering."""
    return sample_with_logprobs(lg, request_keys(seeds, pos),
                                true_vocab=true_vocab, temperature=t,
                                top_k=k, top_p=p)


@dataclasses.dataclass
class Request:
    """One in-flight request.  `params` carries the per-request sampling
    knobs (defaulted from the batcher's `temperature`/`max_new` at submit
    for legacy callers); timing marks feed `RequestOutput`'s TTFT/TPOT;
    the `spec_*` counters feed its acceptance stats when the scheduler
    runs speculative decoding.
    """
    uid: int
    prompt: List[int]
    max_new: int
    output: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    params: Optional[SamplingParams] = None
    logprobs: List[float] = dataclasses.field(default_factory=list)
    # stop|length|capacity|aborted|deadline|migrated
    finish_reason: Optional[str] = None
    # disaggregated prefill (serving/replica.py): a held slot prefills
    # normally but is excluded from decode dispatch, so its KV state can
    # migrate to a decode replica with exactly the prefill handoff token
    # emitted — the decode replica resumes the PRNG stream at position 1
    hold: bool = False
    priority: int = 0         # lower admits first (0 = default class)
    deadline_ts: Optional[float] = None   # monotonic; expired queued
    order: int = 0            # submit sequence (admission tiebreak)
    submit_ts: Optional[float] = None
    first_ts: Optional[float] = None
    finish_ts: Optional[float] = None
    spec_steps: int = 0       # verify steps this request decoded in
    spec_drafted: int = 0     # draft tokens offered for verification
    spec_accepted: int = 0    # draft tokens accepted
    tier_hits: int = 0        # cached pages mapped while hot-resident
    tier_stalls: int = 0      # cached pages demand-promoted from capacity


def bucket_length(n: int, lo: int = MIN_PROMPT_BUCKET,
                  hi: Optional[int] = None) -> int:
    """Smallest power-of-two bucket (≥ lo) holding n tokens, clamped to
    `hi` — near-capacity prompts must not round up past the slot stripe
    (the caller rejects n > hi at submit)."""
    b = lo
    while b < n:
        b *= 2
    if hi is not None:
        b = min(b, hi)
    return b


@dataclasses.dataclass
class _PrefillState:
    """Host-side carry-over of one slot's in-progress chunked prefill."""
    req: Request
    tokens: np.ndarray      # prompt, padded to the chunk grid
    n: int                  # true prompt length
    pos: int = 0            # next chunk's first token (prompt-relative)
    order: int = 0          # admission order (FIFO chunk scheduling)


@dataclasses.dataclass
class _Inflight:
    """One dispatched, not-yet-collected decode/verify step (§14).

    Carries the jitted step's un-materialized device arrays plus the
    host snapshot `collect()` needs to emit without consulting mutable
    scheduler state: the per-slot Request identities at dispatch time
    (a slot whose occupant changed between dispatch and collect — stop
    finish, abort — marks that row a discarded PHANTOM) and the slots
    whose capacity finish was already length-predictable at dispatch."""
    kind: str                       # "decode" | "verify"
    active: List[int]
    reqs: Dict[int, Request]
    toks: jax.Array                 # device future until collect()
    lps: jax.Array
    acc: Optional[jax.Array] = None          # verify: accepted counts
    allowed: Optional[np.ndarray] = None     # verify: per-row draft cap
    cap_finish: Set[int] = dataclasses.field(default_factory=set)


class ContinuousBatcher:
    def __init__(self, cfg: ModelConfig, params, *, batch_slots: int = 4,
                 max_context: int = 512, eng: Optional[EngineConfig] = None,
                 rt: Optional[Runtime] = None, temperature: float = 0.0,
                 seed: int = 0, bucket_prompts: bool = True,
                 prefill_chunk_tokens: int = 64,
                 step_token_budget: Optional[int] = None,
                 speculation_k: int = 0, tier_prefetch: bool = True,
                 device: Optional[jax.Device] = None):
        eng = eng or EngineConfig(page_tokens=16, uniform_lengths=False)
        if eng.uniform_lengths:
            raise ValueError(
                "continuous batching needs the ragged append path: pass "
                "an EngineConfig with uniform_lengths=False (slots advance "
                "out of lockstep, and masked decode steps require the "
                "per-sequence scatter)")
        if prefill_chunk_tokens % eng.page_tokens:
            raise ValueError(
                f"prefill_chunk_tokens={prefill_chunk_tokens} must be a "
                f"multiple of page_tokens={eng.page_tokens} so chunk "
                "starts stay page-aligned")
        self.cfg = cfg
        self.engine = KVNANDEngine(cfg, eng, rt or Runtime())
        # replica placement: params, cache and every per-step input are
        # committed to `device`, so each jitted step compiles once (None:
        # the default device, uncommitted)
        self.device = device
        self.params = self._put(params)
        self.B = batch_slots
        self.max_context = max_context
        self.temperature = temperature
        # recurrent prefill folds padding into carried state → exact-length
        self.bucket_prompts = (bucket_prompts
                               and cfg.family not in ("ssm", "hybrid"))
        self.chunk_tokens = prefill_chunk_tokens
        # ssm/hybrid carry state (padding pollutes it) and meta-token
        # prefixes break page alignment of later chunks → one exact chunk
        self._whole_prompt = (cfg.family in ("ssm", "hybrid")
                              or cfg.n_meta_tokens > 0)
        self._prefix = cfg.n_meta_tokens
        self.step_token_budget = (step_token_budget
                                  or prefill_chunk_tokens + batch_slots)
        self.seed = seed
        self.queue: Deque[Request] = deque()
        self.slots: List[Optional[Request]] = [None] * batch_slots
        with jax.default_device(device):
            self.cache = self._put(
                self.engine.init_cache(batch_slots, max_context))
        self._lengths = np.zeros(batch_slots, np.int64)
        self._prefill_live: Dict[int, _PrefillState] = {}
        self._admit_seq = 0
        self._submit_seq = 0
        # dispatched-but-uncollected steps (DESIGN.md §14): depth 0 in
        # the synchronous schedule, briefly 2 in the overlapped one
        # (dispatch N+1 lands before collect N pops)
        self._inflight: Deque[_Inflight] = deque()
        # host-observed device idleness: set when a collect leaves no
        # step in flight, cleared (and accumulated) at the next device
        # enqueue — exact in the synchronous schedule, ~0 when overlapped
        self._idle_since: Optional[float] = None
        self.shared = eng.shared_pool
        self.alloc: Optional[PageAllocator] = None
        self.alloc_w: Optional[PageAllocator] = None
        self.prefix_cache: Optional[PrefixCache] = None
        # tiered flash KV hierarchy (DESIGN.md §13): hot-tier residency
        # map + host-side capacity store, built by _init_shared_pool
        # when EngineConfig.hot_pages > 0
        self.tier: Optional[HotTier] = None
        self.tier_prefetch = tier_prefetch
        # per-slot sampling params, consumed as TRACED arrays inside the
        # jitted decode step: any mix of per-request temperatures / top-k /
        # top-p / seeds shares the one compiled signature
        self._temps = np.zeros(batch_slots, np.float32)
        self._topk = np.zeros(batch_slots, np.int32)
        self._topp = np.ones(batch_slots, np.float32)
        self._seeds = np.zeros(batch_slots, np.uint32)
        # draft-and-verify speculative decoding (DESIGN.md §11): every
        # decode step becomes a k-token prompt-lookup draft + one-pass
        # verification; 0 keeps the sequential decode path
        if speculation_k < 0:
            raise ValueError(f"speculation_k must be >= 0, "
                             f"got {speculation_k}")
        if speculation_k > 0 and (cfg.family in ("ssm", "hybrid")
                                  or cfg.is_encoder_decoder):
            raise ValueError(
                f"{cfg.name}: speculative decoding needs rollback-able "
                "paged KV; recurrent/encoder-decoder state cannot roll "
                "back — run with speculation_k=0")
        self.spec_k = speculation_k

        def _decode_fn(p, c, t, chain, prev_t, a, temps, tk, tp, seeds,
                       pos):
            # double-buffered feed merge (DESIGN.md §14): rows chained
            # on an uncollected step take that step's device token;
            # folding the select into the step keeps the overlapped
            # dispatch free of eager per-step ops on the host path
            t = jnp.where(chain[:, None], prev_t[:, None], t)
            logits, c = self.engine.decode_step(p, c, t, active=a)
            toks, lps = sample_with_logprobs(
                logits, request_keys(seeds, pos),
                true_vocab=self.cfg.vocab_size, temperature=temps,
                top_k=tk, top_p=tp)
            return toks, lps, c

        self._decode = jax.jit(_decode_fn, donate_argnums=(1,))
        self._no_chain = self._put((np.zeros(self.B, bool),
                                    np.zeros(self.B, np.int32)))

        def _verify_fn(p, c, t, a, allowed, temps, tk, tp, seeds, pos):
            # sampling stays a scheduler concern: the engine calls back
            # into `speculative_accept` with the span logits, so the one
            # jitted step covers forward + accept + gated span append
            def _accept(logits):
                toks, lps, acc = speculative_accept(
                    logits, t[:, 1:], seeds, pos, allowed,
                    true_vocab=self.cfg.vocab_size, temperature=temps,
                    top_k=tk, top_p=tp)
                return acc, (toks, lps, acc)

            aux, c = self.engine.verify_step(p, c, t, accept=_accept,
                                             active=a)
            return aux, c

        self._verify = (jax.jit(_verify_fn, donate_argnums=(1,))
                        if speculation_k > 0 else None)
        _watch_jit(f"{type(self).__name__}._decode", self._decode)
        _watch_jit(f"{type(self).__name__}._verify", self._verify)
        self._chunk_first = jax.jit(
            lambda p, c, t, s, st, n: self.engine.prefill_chunk(
                p, c, {"tokens": t}, s, st, n, first=True),
            donate_argnums=(1,))
        self._chunk_cont = jax.jit(
            lambda p, c, t, s, st, n: self.engine.prefill_chunk(
                p, c, {"tokens": t}, s, st, n, first=False),
            donate_argnums=(1,))
        self.completed: Dict[int, Request] = {}
        self.stats = {"steps": 0, "admits": 0, "prefill_chunks": 0,
                      "decode_tokens": 0, "decode_stall_tokens": 0,
                      "compiles": 0, "prefix_hit_pages": 0,
                      "prompt_pages": 0, "cow_copies": 0,
                      "pool_peak_pages": 0, "pool_total_pages": 0,
                      "spec_steps": 0, "spec_drafted": 0,
                      "spec_accepted": 0,
                      "tier_hot_slots": 0, "tier_hit_pages": 0,
                      "tier_miss_pages": 0, "tier_stall_tokens": 0,
                      "tier_promotes": 0, "tier_demotes": 0,
                      "tier_prefetch_pages": 0, "tier_peak_hot": 0,
                      "phantom_tokens": 0, "deadline_drops": 0,
                      "device_idle_s": 0.0}
        self._compile_keys = set()
        if self.shared:
            self._init_shared_pool(eng)

    def _put(self, x):
        """Host values (pytrees) -> device arrays on this batcher's
        device."""
        return jax.device_put(x, self.device)

    # -- shared-pool bookkeeping (allocator, tables, prefix cache) -----
    def _init_shared_pool(self, eng: EngineConfig):
        cfg, T = self.cfg, eng.page_tokens
        c = self.cache
        if c.k_pages_g is not None:
            self._NPg = c.page_table_g.shape[1]
            H = c.k_pages_g.shape[2]        # device-resident pages
            if eng.hot_pages > 0:
                # tiered hierarchy (DESIGN.md §13): the allocator spans
                # the FLASH page space (stable ids for tables/caches);
                # only H of those pages are device-resident at a time
                total_flash = eng.total_pages or self.B * self._NPg
                if H > total_flash:
                    raise ValueError(
                        f"hot_pages={eng.hot_pages} (rounded to {H}) "
                        f"exceeds the flash pool of {total_flash} pages; "
                        "shrink hot_pages or grow total_pages")
                if c.k_pages_w is not None:
                    raise ValueError(
                        f"{cfg.name}: tiered pools cover the GLOBAL layer "
                        "group only — window rings recycle their pages in "
                        "place and never cool down; run local-attention "
                        "archs with hot_pages=0")
                self.alloc = PageAllocator(total_flash)
                self.tier = HotTier(H, total_flash)
                # capacity tier: demoted pages' bytes, flash id -> one
                # host array per pool leaf
                self._store: Dict[int, Dict[str, np.ndarray]] = {}
                self.alloc.add_release_hook(self._tier_release)
                self._hot_resv = np.zeros(self.B, np.int64)
                self._hot_out = 0           # sum of per-slot hot footprints
                self.stats["tier_hot_slots"] = H
            else:
                self.alloc = PageAllocator(H)
            self._table_np = np.zeros((self.B, self._NPg), np.int32)
            self.stats["pool_total_pages"] = self.alloc.total
        if c.k_pages_w is not None:
            self._NPw = c.page_table_w.shape[1]
            self.alloc_w = PageAllocator(c.k_pages_w.shape[2])
            self._table_w_np = np.zeros((self.B, self._NPw), np.int32)
        # per-slot maps: logical page -> physical; shared = mapped with
        # refcount > 1 (read-only until COW); ring pages owned outright
        self._slot_pages: List[Dict[int, int]] = [dict()
                                                  for _ in range(self.B)]
        self._slot_shared: List[Set[int]] = [set() for _ in range(self.B)]
        self._slot_ring: List[List[int]] = [[] for _ in range(self.B)]
        self._resv = np.zeros(self.B, np.int64)   # reserved, not yet alloc'd
        self._outstanding = 0
        # prefix sharing needs a pure global-pool arch with no frontend
        # prefix and no recurrent state (window rings recycle pages; meta
        # tokens shift page alignment; ssm/hybrid carry state)
        if (self.alloc is not None and self.alloc_w is None
                and not self._whole_prompt and self._prefix == 0
                and not cfg.is_encoder_decoder):
            self.prefix_cache = PrefixCache(self.alloc, T)
        self._tables_dirty = True
        self._push_tables()

        def cow_copy(cache, src, dst):
            upd = {}
            for name in ("k_pages_g", "v_pages_g", "k_scale_g",
                         "v_scale_g"):
                leaf = getattr(cache, name)
                if leaf is not None:
                    upd[name] = paged_kv.copy_page_shared(leaf, src, dst)
            return dataclasses.replace(cache, **upd)

        self._cow_jit = jax.jit(cow_copy, donate_argnums=(0,))

        # tiered staging: one donated dynamic_update_slice per pool leaf
        # writes a promoted page's bytes into its freshly bound hot slot
        # (the jax.device_put-style upload of DESIGN.md §13); the writer
        # itself lives with the rest of the pool-leaf writers (KV004)
        self._pool_leaves = [n for n in ("k_pages_g", "v_pages_g",
                                         "k_scale_g", "v_scale_g")
                             if getattr(c, n) is not None]
        self._stage_jit = jax.jit(paged_kv.stage_hot_slot,
                                  donate_argnums=(0,))

    # -- tiered flash KV hierarchy (DESIGN.md §13) ---------------------
    def _read_hot(self, slot: int) -> Dict[str, np.ndarray]:
        """Pull one hot slot's bytes to the host (demotion / COW save)."""
        return {n: np.asarray(getattr(self.cache, n)[:, :, slot])
                for n in self._pool_leaves}

    def _tier_release(self, page: int):
        """Allocator release hook: flash page `page` hit refcount 0 on
        ANY free path (slot teardown, cache eviction, speculative
        rollback) — retire its hot slot and capacity-store bytes."""
        self.tier.release(int(page))
        self._store.pop(int(page), None)

    def _bind_slot(self, page: int, avoid: frozenset = frozenset()) -> int:
        """Acquire a hot slot for flash page `page`, demoting the LRU
        unpinned resident to the capacity store when the tier is full
        (its bytes are read back BEFORE the slot is overwritten)."""
        slot, victim = self.tier.bind(page, avoid=avoid)
        if victim is not None:
            self._store[victim] = self._read_hot(slot)
            self.stats["tier_demotes"] += 1
        self.stats["tier_peak_hot"] = max(self.stats["tier_peak_hot"],
                                          self.tier.resident_count)
        return slot

    def _promote(self, page: int, avoid: frozenset = frozenset()) -> int:
        """Stage a capacity-tier page's bytes into a hot slot.  Every
        live non-resident page has bytes in the store (pages leave
        residency only by demotion); fresh allocations bind without a
        copy and never come through here."""
        slot = self._bind_slot(page, avoid=avoid)
        vals = self._store.pop(int(page))
        self._count_compile("tier_stage")
        self.cache = self._stage_jit(
            self.cache, self._put(np.int32(slot)), self._put(vals))
        self.stats["tier_promotes"] += 1
        return slot

    def _tier_prefetch_tick(self):
        """Queue-ahead async prefetch: at the END of a step, promote the
        capacity-tier pages the next admission's prefix hit will map, so
        the admission pins already-resident pages instead of demand-
        faulting.  The staging overlaps the in-flight step's compute
        (flashsim charges it as hidden — DESIGN.md §13); only demand
        promotions count as stall tokens.  Uses the side-effect-free
        cache PEEK and binds around the working set being staged, and
        backs off when every remaining slot is pinned."""
        if (self.tier is None or not self.tier_prefetch or not self.queue
                or self.prefix_cache is None):
            return
        # peek the next ADMISSION candidate (priority/deadline order,
        # not the deque head) — the side-effect-free twin of _queue_pick
        head = min(self.queue, key=self._admission_key)
        hit = self.prefix_cache.lookup(head.prompt, record=False)
        pages = (hit.exact.pages if hit.exact is not None
                 else hit.full_pages)
        if not pages:
            return
        avoid = frozenset(int(p) for p in pages)
        for p in pages:
            if self.tier.is_resident(p):
                self.tier.touch(p)      # keep warm until admission pins
            else:
                try:
                    self._promote(p, avoid=avoid)
                except OutOfHotSlots:
                    break
                self.stats["tier_prefetch_pages"] += 1

    def _push_tables(self):
        """Mirror the host page tables into the device cache leaves (only
        when a mapping actually changed — steady-state decode steps that
        stay inside a page skip the upload entirely)."""
        if not self._tables_dirty:
            return
        upd = {}
        if self.alloc is not None:
            upd["page_table_g"] = self._put(self._table_np)
        if self.alloc_w is not None:
            upd["page_table_w"] = self._put(self._table_w_np)
        if upd:
            self.cache = dataclasses.replace(self.cache, **upd)
        self._tables_dirty = False

    def _alloc_g(self, logical: int) -> int:
        """One global-pool page, evicting prefix-cache LRU entries under
        pressure (their pages are the only reclaimable slack)."""
        while True:
            try:
                p = self.alloc.alloc_for_logical(logical)
                self.stats["pool_peak_pages"] = max(
                    self.stats["pool_peak_pages"], self.alloc.live_count)
                return p
            except OutOfPages:
                if self.prefix_cache is None or \
                        not self.prefix_cache.evict_lru():
                    raise RuntimeError(
                        "shared page pool exhausted despite admission "
                        "reservations — allocator accounting bug") from None

    def _ensure_page(self, i: int, lp: int):
        """Slot i is about to WRITE logical page lp: allocate it fresh if
        unmapped, COW it if currently shared (refcount > 1).  Tiered
        pools additionally pin the page hot — a fresh allocation binds a
        slot with no byte traffic (its contents are written before the
        length ever covers them), a COW round-trips the shared bytes
        through the host so the fresh binding may demote the old page
        itself when it was the last unpinned resident."""
        pages = self._slot_pages[i]
        if lp not in pages:
            p = self._alloc_g(lp)
            pages[lp] = p
            if self.tier is not None:
                self._table_np[i, lp] = self._bind_slot(p)
                self.tier.pin(p)
            else:
                self._table_np[i, lp] = p
            self._tables_dirty = True
            self._resv[i] -= 1
            self._outstanding -= 1
            return
        if lp in self._slot_shared[i]:
            old = pages[lp]
            fresh = self.alloc.cow(old)
            if fresh != old:
                self._count_compile("cow")
                if self.tier is not None:
                    # `old` is pinned (this slot maps it): snapshot its
                    # bytes, drop this slot's pin, then bind+stage the
                    # fresh copy — in that order, so the bind may pick
                    # `old` as its own demotion victim without losing
                    # the copy source
                    src = self.tier.slot_of(old)
                    self._store[fresh] = self._read_hot(src)
                    self.tier.unpin(old)
                    self._promote(fresh)
                    self.tier.pin(fresh)
                    self._table_np[i, lp] = self.tier.slot_of(fresh)
                else:
                    self.cache = self._cow_jit(
                        self.cache, *self._put((np.int32(old),
                                                np.int32(fresh))))
                    self._table_np[i, lp] = fresh
                pages[lp] = fresh
                self._tables_dirty = True
                self.stats["cow_copies"] += 1
                self._resv[i] -= 1
                self._outstanding -= 1
            self._slot_shared[i].discard(lp)
            self.stats["pool_peak_pages"] = max(
                self.stats["pool_peak_pages"], self.alloc.live_count)

    def _free_slot_pages(self, i: int):
        if not self.shared:
            return
        if self.alloc is not None and self._slot_pages[i]:
            if self.tier is not None:
                # unpin before the refcount drop: pages the prefix cache
                # still references stay resident (LRU demotion candidates),
                # dead pages release their slot via the allocator hook
                for p in self._slot_pages[i].values():
                    self.tier.unpin(p)
            self.alloc.free(list(self._slot_pages[i].values()))
        if self.alloc_w is not None and self._slot_ring[i]:
            self.alloc_w.free(self._slot_ring[i])
        if self.tier is not None:
            self._hot_out -= int(self._hot_resv[i])
            self._hot_resv[i] = 0
        self._slot_pages[i] = {}
        self._slot_shared[i] = set()
        self._slot_ring[i] = []
        self._outstanding -= int(self._resv[i])
        self._resv[i] = 0

    def _pages_needed(self, req: Request) -> int:
        total = min(self._prefix + len(req.prompt) + req.max_new,
                    self.max_context)
        return -(-total // self.engine.eng.page_tokens)

    def _map_cached_pages(self, i: int, pages) -> int:
        """Map cached pages read-only into slot i's logical pages 0..len:
        one allocator reference each, marked shared (COW before write).

        Tiered pools pin each page hot first: a page the prefetcher (or
        recency) kept resident is a TIER HIT; a page demoted to the
        capacity store demand-faults — promoted on the spot and counted
        as a stall token, the observable cost of the DRAM-free story."""
        req = self.slots[i]
        for j, p in enumerate(pages):
            self.alloc.share([p])
            self._slot_pages[i][j] = p
            self._slot_shared[i].add(j)
            if self.tier is not None:
                if self.tier.is_resident(p):
                    self.stats["tier_hit_pages"] += 1
                    req.tier_hits += 1
                else:
                    self._promote(p)
                    self.stats["tier_miss_pages"] += 1
                    self.stats["tier_stall_tokens"] += 1
                    req.tier_stalls += 1
                self.tier.pin(p)
                self._table_np[i, j] = self.tier.slot_of(p)
            else:
                self._table_np[i, j] = p
        return len(pages)

    def _register_prefix(self, i: int, ps: _PrefillState,
                         logits: np.ndarray):
        """Publish a freshly prefilled prompt's pages into the prefix
        cache.  Full pages are always safe to share (the slot never
        rewrites them).  The trailing PARTIAL page becomes shared too —
        making this slot's own first decode append copy-on-write it — but
        only when the pool has a free page of slack to fund that copy
        (the reservation grows by one to keep admission accounting
        exact)."""
        T = self.engine.eng.page_tokens
        n_pages = -(-ps.n // T)
        pages = [self._slot_pages[i][j] for j in range(n_pages)]
        partial = ps.n % T != 0
        slack = self.alloc.free_count - self._outstanding
        if self.tier is not None:
            # hot-tier slack, not whole-pool slack: the repeat that hits
            # this exact entry must re-pin every page hot AND fund the
            # partial page's COW with a hot slot — against a cold
            # capacity tier the flash pool can have plenty of free pages
            # while the hot tier has none to give, which would publish
            # an unservable hit
            slack = min(slack, self.tier.free_slot_count
                        + self.tier.demotable_count)
        include_exact = (not partial) or slack >= 1
        added = self.prefix_cache.register(
            ps.req.prompt, pages, logits, include_exact=include_exact)
        if added and partial and include_exact:
            self._resv[i] += 1
            self._outstanding += 1
        for j, p in enumerate(pages):
            if self.alloc.refcount[p] > 1:
                self._slot_shared[i].add(j)

    # -- host-side slot management ------------------------------------
    def _count_compile(self, name, *key):
        """Host-side compile census: one per distinct jit signature."""
        k = (name,) + key
        if k not in self._compile_keys:
            self._compile_keys.add(k)
            self.stats["compiles"] += 1

    # -- per-request sampling / lifecycle ------------------------------
    def _seed_of(self, req: Request) -> np.uint32:
        """The request's PRNG-stream seed: its explicit `params.seed`, or
        a (batcher seed, uid) hash — in both cases independent of batch
        composition and admission order, so a request's stream never
        consumes from (or perturbs) any other request's."""
        if req.params is not None and req.params.seed is not None:
            return np.uint32(req.params.seed & 0xFFFFFFFF)
        return np.uint32((self.seed * 0x9E3779B1 + req.uid * 0x85EBCA77
                          + 0x165667B1) & 0xFFFFFFFF)

    def _set_slot_params(self, i: int, req: Request):
        p = req.params
        self._temps[i] = p.temperature
        self._topk[i] = p.top_k
        self._topp[i] = p.top_p
        self._seeds[i] = self._seed_of(req)

    def _sample_row(self, logits, req: Request):
        """Sample ONE request's next token (prefill handoff / exact-hit
        paths) through the same per-request stream the batched decode
        uses: key = fold(seed, tokens emitted so far)."""
        p = req.params
        self._count_compile("sample_row")
        toks, lps = _sample_one(
            self._put(logits), *self._put((
                np.asarray([self._seed_of(req)], np.uint32),
                np.asarray([len(req.output)], np.int32),
                np.float32(p.temperature), np.int32(p.top_k),
                np.float32(p.top_p))), true_vocab=self.cfg.vocab_size)
        return int(toks[0]), float(lps[0])

    def _finish(self, i: int, reason: str):
        """Retire slot i's request: record the finish reason/timestamp and
        recycle the slot (shared pool: refcounts returned, reservations
        released)."""
        req = self.slots[i]
        req.done = True
        req.finish_reason = reason
        req.finish_ts = time.monotonic()
        self.completed[req.uid] = req
        self.slots[i] = None              # slot pages recycled in place
        self._lengths[i] = 0
        self._free_slot_pages(i)          # shared pool: refcount--

    def _emit_token(self, i: int, req: Request, tok: int, lp: float):
        """Append one sampled token and apply the finish rules (stop
        token beats length; capacity is checked by the decode sweep)."""
        req.output.append(tok)
        if req.params.logprobs:
            req.logprobs.append(lp)
        if req.first_ts is None:
            req.first_ts = time.monotonic()
        if tok in req.params.stop_token_ids:
            self._finish(i, "stop")
        elif len(req.output) >= req.max_new:
            self._finish(i, "length")

    def abort(self, uid: int) -> bool:
        """Cancel a request wherever it is: still queued, mid-chunked-
        prefill, or decoding.  Running requests release their shared-pool
        pages (refcounts intact — prefix-cache references survive) and
        free the slot immediately.  Returns False for unknown/finished
        uids."""
        for r in self.queue:
            if r.uid == uid:
                self.queue.remove(r)
                r.done = True
                r.finish_reason = "aborted"
                r.finish_ts = time.monotonic()
                self.completed[uid] = r
                return True
        for i, r in enumerate(self.slots):
            if r is not None and r.uid == uid:
                self._prefill_live.pop(i, None)
                self._finish(i, "aborted")
                return True
        return False

    def submit(self, req: Request):
        if req.params is None:
            # legacy surface: batcher-global temperature, greedy filters
            req.params = SamplingParams(temperature=self.temperature,
                                        max_new_tokens=req.max_new)
        else:
            req.max_new = req.params.max_new_tokens
        if req.submit_ts is None:
            req.submit_ts = time.monotonic()
        req.order = self._submit_seq
        self._submit_seq += 1
        n = len(req.prompt)
        cap = self.max_context - 1 - self._prefix
        if n == 0:
            raise ValueError(f"request {req.uid}: empty prompt")
        if n > cap:
            raise ValueError(
                f"request {req.uid}: prompt of {n} tokens exceeds the slot "
                f"capacity of {cap} (max_context={self.max_context} minus "
                f"1 decode token minus {self._prefix} prefix tokens); "
                "truncate the prompt or enlarge max_context")
        if self.shared and self.alloc is not None:
            need = self._pages_needed(req)
            if need > self.alloc.total:
                raise ValueError(
                    f"request {req.uid}: worst-case footprint of {need} "
                    f"pages exceeds the shared pool of "
                    f"{self.alloc.total} pages; shrink the prompt/max_new "
                    "or grow EngineConfig.total_pages")
            if self.tier is not None and need > self.tier.hot_slots:
                raise ValueError(
                    f"request {req.uid}: worst-case footprint of {need} "
                    f"pages exceeds the hot tier of "
                    f"{self.tier.hot_slots} pages (mapped pages stay "
                    "pinned hot); shrink the prompt/max_new or grow "
                    "EngineConfig.hot_pages")
        self.queue.append(req)

    @staticmethod
    def _admission_key(r: Request):
        """Admission order: lowest priority class first, then nearest
        deadline, then submit order — all defaults degrade to FIFO."""
        return (r.priority,
                r.deadline_ts if r.deadline_ts is not None else float("inf"),
                r.order)

    def _queue_pick(self) -> Optional[Request]:
        """Sweep queued requests whose deadline already passed (they
        finish as ``"deadline"`` without costing pages or steps), then
        return — without removing — the best admission candidate."""
        now = time.monotonic()
        for r in [r for r in self.queue
                  if r.deadline_ts is not None and now >= r.deadline_ts]:
            self.queue.remove(r)
            r.done = True
            r.finish_reason = "deadline"
            r.finish_ts = now
            self.completed[r.uid] = r
            self.stats["deadline_drops"] += 1
        if not self.queue:
            return None
        return min(self.queue, key=self._admission_key)

    def _admit(self):
        for i in range(self.B):
            if self.slots[i] is None and self.queue:
                req = self._queue_pick()
                if req is None:
                    break
                if self.shared:
                    if not self._admit_shared(i, req):
                        break          # best candidate waits for pages
                    continue
                self.queue.remove(req)
                self.slots[i] = req
                self._set_slot_params(i, req)
                self._start_prefill(i, req)
                self.stats["admits"] += 1

    def _start_prefill(self, i: int, req: Request, pos: int = 0):
        n = len(req.prompt)
        if self._whole_prompt:
            toks = np.asarray(req.prompt, np.int32)
        else:
            C = self.chunk_tokens
            toks = np.zeros(-(-n // C) * C, np.int32)
            toks[:n] = req.prompt
        self._prefill_live[i] = _PrefillState(
            req, toks, n, pos=pos, order=self._admit_seq)
        self._admit_seq += 1

    def _admit_shared(self, i: int, req: Request) -> bool:
        """Admission by KV footprint: reserve the request's worst-case
        pages against the pool; map any cached prefix read-only; admit
        only if the remainder fits free + evictable pages."""
        n = len(req.prompt)
        T = self.engine.eng.page_tokens
        need_g = self._pages_needed(req) if self.alloc is not None else 0
        need_w = 0
        if self.alloc_w is not None:
            total = min(self._prefix + n + req.max_new, self.max_context)
            need_w = min(-(-total // T), self._NPw)
        hit = CacheHit()
        if self.prefix_cache is not None:
            hit = self.prefix_cache.lookup(req.prompt)
        if self.alloc is not None:
            hit_pages = (hit.exact.pages if hit.exact is not None
                         else hit.full_pages)
            evictable = (self.prefix_cache.evictable_pages()
                         if self.prefix_cache is not None else 0)
            # mapping the hit PINS its pages: whatever part of `evictable`
            # they are stops being reclaimable the moment this request is
            # admitted, so discount them all (conservative — some may
            # already be pinned by another slot)
            avail = (self.alloc.free_count
                     + max(0, evictable - len(hit_pages))
                     - self._outstanding)
            # fresh pages this slot may still allocate: decode growth,
            # plus the COW of an exact hit's shared partial page
            resv_needed = need_g - (n // T if hit.exact is not None
                                    else len(hit.full_pages))
            if resv_needed > avail:
                return False
            # tiered pool: the request's worst-case footprint must ALSO
            # fit the hot tier net of every live slot's reservation —
            # mapped pages stay pinned for the slot's lifetime, so this
            # bound guarantees allocations/promotions always find a free
            # or demotable slot (never OutOfHotSlots mid-flight)
            if self.tier is not None \
                    and self._hot_out + need_g > self.tier.hot_slots:
                return False
        if self.alloc_w is not None and need_w > self.alloc_w.free_count:
            return False

        self.queue.remove(req)
        self.slots[i] = req
        self._set_slot_params(i, req)
        self.stats["admits"] += 1
        self.stats["prompt_pages"] += -(-n // T)
        if self.tier is not None:
            self._hot_resv[i] = need_g
            self._hot_out += need_g
        # eager window-ring allocation (bounded, recycled in place)
        if self.alloc_w is not None:
            for j in range(need_w):
                p = self.alloc_w.alloc_for_logical(j)
                self._slot_ring[i].append(p)
                self._table_w_np[i, j] = p
            self._tables_dirty = self._tables_dirty or need_w > 0
        if hit.exact is not None:
            # whole-prompt repeat: map EVERY page (incl. the trailing
            # partial one) read-only and skip prefill; the first decode
            # append into the partial page copy-on-writes it
            mapped = self._map_cached_pages(i, hit.exact.pages)
            self._resv[i] = need_g - (n // T)   # partial page may COW
            self._lengths[i] = n
            self.cache = dataclasses.replace(
                self.cache,
                lengths=self.cache.lengths.at[i].set(n))
        else:
            mapped = self._map_cached_pages(i, hit.full_pages)
            self._resv[i] = need_g - mapped     # full pages never rewritten
            self._start_prefill(i, req, pos=mapped * T)
        self._outstanding += int(self._resv[i])
        self.stats["prefix_hit_pages"] += mapped
        self._tables_dirty = self._tables_dirty or mapped > 0
        self._push_tables()
        if hit.exact is not None:
            # first token from the cached last-token logits, through the
            # request's OWN params and PRNG stream (accounting above is
            # final first: a stop/length finish frees the slot cleanly)
            tok, lp = self._sample_row(
                np.asarray(hit.exact.logits)[None], req)
            self._emit_token(i, req, tok, lp)
        return True

    def _prefill_tick(self, i: int, ps: _PrefillState):
        """Process ONE chunk of slot i's prompt into the shared cache."""
        if self._whole_prompt:
            chunk, c0, cl = ps.tokens, 0, ps.n
        else:
            c0 = ps.pos
            chunk, cl = ps.tokens[c0:c0 + self.chunk_tokens], \
                min(self.chunk_tokens, ps.n - c0)
        if self.shared:
            # lazy page allocation: back every page this chunk will write
            T = self.engine.eng.page_tokens
            span = c0 + cl + (self._prefix if c0 == 0 else 0)
            if self.alloc is not None:
                for lp in range(c0 // T, -(-span // T)):
                    self._ensure_page(i, lp)
            self._push_tables()
        fn = self._chunk_first if c0 == 0 else self._chunk_cont
        self._count_compile("chunk", c0 == 0, len(chunk))
        logits, self.cache = fn(
            self.params, self.cache, *self._put((
                np.asarray(chunk)[None], np.int32(i), np.int32(c0),
                np.int32(cl))))
        ps.pos = c0 + len(chunk)
        self.stats["prefill_chunks"] += 1
        if ps.pos >= ps.n:                         # prompt fully prefilled
            del self._prefill_live[i]
            self._lengths[i] = self._prefix + ps.n
            if self.prefix_cache is not None:
                self._register_prefix(i, ps, np.asarray(logits[0]))
            tok, lp = self._sample_row(logits, ps.req)
            self._emit_token(i, ps.req, tok, lp)

    def step(self) -> int:
        """One interleaved step — `dispatch()` then `collect()` back to
        back, the synchronous schedule (bit-identical to the pre-split
        loop).  An overlapped driver instead primes one dispatch and
        then runs dispatch(N+1); collect(N) so host post-processing of
        step N overlaps device compute of step N+1 (DESIGN.md §14).
        Returns the number of slots that advanced."""
        chunks = self.dispatch()
        return chunks + self.collect()

    def _mark_device_busy(self):
        """Close the host-observed device-idle window at the first
        device enqueue after a pipeline-empty collect."""
        if self._idle_since is not None:
            self.stats["device_idle_s"] += time.monotonic() - self._idle_since
            self._idle_since = None

    def _will_finish(self, i: int, pend: int) -> bool:
        """True when slot i's request is already CERTAIN to finish once
        the pipeline drains — `pend` uncollected tokens ahead of it hit
        its max_new budget, or an in-flight step predicted its capacity
        finish.  Such slots are excluded from the next dispatch instead
        of becoming guaranteed phantoms.  (Stop-token finishes are not
        host-predictable; those rows dispatch and may be discarded.)"""
        req = self.slots[i]
        if len(req.output) + pend >= req.max_new:
            return True
        return any(i in inf.cap_finish and inf.reqs.get(i) is req
                   for inf in self._inflight)

    def dispatch(self) -> int:
        """Host half of one scheduler step: admissions, prefill chunks
        (budgeted — decode batch funded first), page ensures and table
        pushes, then the jitted decode/verify ENQUEUE.  The step's
        token/logprob outputs stay un-materialized device futures in
        `self._inflight` until `collect()`.  Returns the number of
        prefill chunks processed."""
        if self._inflight and (self.spec_k > 0 or len(self._inflight) >= 2):
            # verify steps draft from host-visible history, and the
            # pipeline is one step deep — drain before dispatching again
            self.collect()
        self._admit()
        n_decoding = sum(1 for i, r in enumerate(self.slots)
                         if r is not None and i not in self._prefill_live
                         and not r.hold)
        # a verify step processes spec_k+1 query tokens per decoding
        # slot — charge the budget what the step actually computes, so
        # prefill-chunk packing doesn't overshoot under speculation
        per_slot = self.spec_k + 1 if self.spec_k > 0 else 1
        budget = self.step_token_budget - n_decoding * per_slot
        chunks_done = 0
        for i, ps in sorted(self._prefill_live.items(),
                            key=lambda kv: kv[1].order):
            cost = ps.n if self._whole_prompt else self.chunk_tokens
            # always fund at least one chunk (prefill must progress even
            # under a tiny budget); extra chunks only within budget
            if chunks_done and budget < cost:
                break
            self._prefill_tick(i, ps)
            budget -= cost
            chunks_done += 1
        pending = {i for inf in self._inflight for i in inf.active
                   if self.slots[i] is inf.reqs[i]}
        active = [i for i, r in enumerate(self.slots)
                  if r is not None and i not in self._prefill_live
                  and not r.hold
                  and not self._will_finish(i, int(i in pending))]
        if active:
            if self.spec_k > 0:
                self._dispatch_verify(active)
            else:
                self._dispatch_sequential(active)
        self.stats["steps"] += 1
        return chunks_done

    def collect(self) -> int:
        """Host half of step N's completion: materialize the OLDEST
        in-flight step (ONE `jax.device_get` round-trip for all of its
        arrays), emit its tokens through the finish rules — TTFT/TPOT
        timestamps are stamped here, when tokens are host-visible — then
        run the queue-ahead tier prefetch.  Returns slots advanced; a
        no-op (apart from the prefetch tick) when nothing is in flight."""
        emitted = 0
        if self._inflight:
            inf = self._inflight.popleft()
            if inf.kind == "verify":
                emitted = self._collect_verify(inf)
            else:
                emitted = self._collect_decode(inf)
        self._tier_prefetch_tick()
        if not self._inflight:
            self._idle_since = time.monotonic()
        return emitted

    @property
    def pending_steps(self) -> int:
        """Dispatched-but-uncollected steps (0 outside overlap mode)."""
        return len(self._inflight)

    def _decode_batch(self, active: List[int]) -> int:
        """One SYNCHRONOUS decode step over `active` slots (shared by
        both schedulers — the parity pair must never diverge on this
        body): dispatch immediately followed by its collect.  With
        ``speculation_k > 0`` the step runs draft-and-verify — same
        streams, same emitted tokens, up to k+1 of them per slot;
        otherwise (or when no row may accept) the sequential step."""
        if not active:
            return 0
        if self.spec_k > 0:
            self._dispatch_verify(active)
        else:
            self._dispatch_sequential(active)
        inf = self._inflight.popleft()
        return (self._collect_verify(inf) if inf.kind == "verify"
                else self._collect_decode(inf))

    def _dispatch_sequential(self, active: List[int]):
        """Enqueue one masked decode over `active` slots, sampling each
        row through its OWN params/PRNG stream inside the jitted step.
        Double-buffered token staging: a row whose previous token is
        still on device (the overlapped schedule dispatches step N+1
        before collecting step N) takes its input from the in-flight
        step's `toks` future via an on-device merge, so the host never
        syncs to build the feed; every other row is staged host-side
        from `output[-1]` exactly as before."""
        prev = self._inflight[-1] if self._inflight else None
        tokens = np.zeros((self.B, 1), np.int32)
        mask = np.zeros(self.B, bool)
        positions = np.zeros(self.B, np.int32)
        chain = np.zeros(self.B, bool)
        for i in active:
            req = self.slots[i]
            mask[i] = True
            if prev is not None and prev.reqs.get(i) is req:
                # feed comes from the uncollected step's device token;
                # the PRNG position accounts for that pending emission
                chain[i] = True
                positions[i] = len(req.output) + 1
            else:
                tokens[i, 0] = req.output[-1]
                positions[i] = len(req.output)
        if self.shared and self.alloc is not None:
            # every active slot appends at its current position: make that
            # page exclusively writable (lazy alloc, or COW off a shared
            # prefix/partial page) before the jitted step runs
            T = self.engine.eng.page_tokens
            for i in active:
                self._ensure_page(i, int(self._lengths[i]) // T)
            self._push_tables()
        ch, prev_t = ((chain, prev.toks) if chain.any()
                      else self._no_chain)
        self._mark_device_busy()
        self._count_compile("decode", self.B)
        # sampling params ride as traced per-slot arrays: any mix of
        # per-request combinations hits this one compiled signature
        toks, lps, self.cache = self._decode(
            self.params, self.cache, self._put(tokens), *self._put(
                (ch, prev_t, mask, self._temps, self._topk, self._topp,
                 self._seeds, positions)))
        self._lengths[active] += 1
        cap = {i for i in active
               if self._lengths[i] + 1 >= self.max_context}
        self._inflight.append(_Inflight(
            "decode", list(active),
            {i: self.slots[i] for i in active}, toks, lps,
            cap_finish=cap))

    def _collect_decode(self, inf: _Inflight) -> int:
        """Emit one collected sequential step: a single host transfer
        fetches tokens and logprobs together, then each surviving row
        advances through the finish rules."""
        toks, lps = jax.device_get((inf.toks, inf.lps))
        emitted = 0
        for i in inf.active:
            req = inf.reqs[i]
            if self.slots[i] is not req:
                # PHANTOM row (§14): the occupant stop-finished or
                # aborted after this step dispatched — its appended
                # token sits in pages `_finish` already recycled and is
                # rewritten by the next occupant before becoming valid
                self.stats["phantom_tokens"] += 1
                continue
            self._emit_token(i, req, int(toks[i]), float(lps[i]))
            self.stats["decode_tokens"] += 1
            emitted += 1
            if self.slots[i] is req and i in inf.cap_finish:
                self._finish(i, "capacity")
        return emitted

    def _rollback_pages(self, i: int):
        """Host half of the speculative rollback: logical pages allocated
        for the span but never reached by an accepted token go back to
        the allocator, and the slot's worst-case reservation is restored
        — refcounts and `_outstanding` exactly as if the pages had never
        been handed out.  (The device half is the write gate: rejected
        positions were dropped, so the freed pages hold no live data;
        the stale table entries they leave sit past `lengths` and stay
        data-invalid until `_ensure_page` remaps them.)"""
        if not self.shared or self.alloc is None:
            return
        last = (int(self._lengths[i]) - 1) // self.engine.eng.page_tokens
        for lp in [p for p in self._slot_pages[i] if p > last]:
            p = self._slot_pages[i].pop(lp)
            if self.tier is not None:
                self.tier.unpin(p)      # release hook retires the slot
            self.alloc.free([p])
            self._slot_shared[i].discard(lp)
            self._resv[i] += 1
            self._outstanding += 1

    def _dispatch_verify(self, active: List[int]):
        """Enqueue one draft-and-verify step over `active` slots: each
        drafts up to `spec_k` tokens by prompt lookup over its own
        history and the engine scores the whole span in ONE jitted pass.
        Drafts, positions, and the span's page ensures all consume the
        requests' host-visible emitted history, which is why `dispatch`
        drains the pipeline before building a verify step — speculation
        runs unoverlapped but token-identical (DESIGN.md §14)."""
        assert not self._inflight, "verify dispatch needs a drained pipeline"
        S = self.spec_k + 1
        T = self.engine.eng.page_tokens
        tokens = np.zeros((self.B, S), np.int32)
        mask = np.zeros(self.B, bool)
        allowed = np.zeros(self.B, np.int32)
        positions = np.zeros(self.B, np.int32)
        reqs: Dict[int, Request] = {}
        for i in active:
            req = self.slots[i]
            reqs[i] = req
            cap = req.params.speculation
            k_eff = self.spec_k if cap is None else min(cap, self.spec_k)
            # a slot may accept only as many drafts as its remaining
            # max_new budget (minus the guaranteed correction token) and
            # its slot capacity allow — so the span can never write past
            # the reservation sequential decode would have used
            allowed[i] = max(0, min(
                k_eff, req.max_new - len(req.output) - 1,
                self.max_context - 2 - int(self._lengths[i])))
            draft = (propose_draft(req.prompt + req.output, self.spec_k)
                     if allowed[i] > 0 else [0] * self.spec_k)
            tokens[i, 0] = req.output[-1]
            tokens[i, 1:] = draft
            mask[i] = True
            positions[i] = len(req.output)
        if not allowed.any():
            # no row may accept anything (per-request opt-outs, or every
            # slot at its max_new/capacity edge): the span forward would
            # be a k+1×-wide way to emit one token per slot — take the
            # sequential step instead
            return self._dispatch_sequential(active)
        if self.shared and self.alloc is not None:
            # back every page the span MAY write (positions up to
            # lengths + allowed): lazy alloc or COW, exactly like the
            # sequential path — just up to ceil(S/T)+1 pages at once
            for i in active:
                lo = int(self._lengths[i]) // T
                hi = (int(self._lengths[i]) + int(allowed[i])) // T
                for lp in range(lo, hi + 1):
                    self._ensure_page(i, lp)
            self._push_tables()
        self._mark_device_busy()
        self._count_compile("verify", self.B, S)
        (toks, lps, acc), self.cache = self._verify(
            self.params, self.cache, *self._put(
                (tokens, mask, allowed, self._temps, self._topk,
                 self._topp, self._seeds, positions)))
        self._inflight.append(_Inflight(
            "verify", list(active), reqs, toks, lps, acc=acc,
            allowed=allowed))

    def _collect_verify(self, inf: _Inflight) -> int:
        """Emit one collected verify step: every slot emits its accepted
        prefix plus the correction/bonus token through the same
        `_emit_token` finish rules and per-request PRNG streams as the
        sequential path — outputs identical token for token, only the
        tokens-per-step changes.  Length advance and span rollback are
        acceptance-dependent, so they live here on the collect side."""
        toks, lps, acc = jax.device_get((inf.toks, inf.lps, inf.acc))
        allowed = inf.allowed
        emitted = 0
        for i in inf.active:
            req = inf.reqs[i]
            if self.slots[i] is not req:
                self.stats["phantom_tokens"] += 1
                continue
            n = int(acc[i]) + 1           # tokens the device appended
            # spec accounting counts ROW-steps that actually offered a
            # draft (matching the per-request counter): the fleet-level
            # accepted_tokens_per_step is then the weighted mean of the
            # per-request values, undiluted by opt-out rows and not
            # inflated by the slot count
            if int(allowed[i]) > 0:
                req.spec_steps += 1
                req.spec_drafted += int(allowed[i])
                self.stats["spec_steps"] += 1
                self.stats["spec_drafted"] += int(allowed[i])
            emitted_i = 0
            for j in range(n):
                if self.slots[i] is not req:
                    break                 # stop token finished mid-span
                self._emit_token(i, req, int(toks[i, j]), float(lps[i, j]))
                emitted_i += 1
            emitted += emitted_i
            # count only EMITTED accepted drafts (a stop-token finish
            # truncates the span): every counted verify step thus
            # contributes exactly spec_accepted + 1 tokens
            if int(allowed[i]) > 0:
                req.spec_accepted += emitted_i - 1
                self.stats["spec_accepted"] += emitted_i - 1
            if self.slots[i] is req:
                self._lengths[i] += n
                self._rollback_pages(i)
                if self._lengths[i] + 1 >= self.max_context:
                    self._finish(i, "capacity")
        self.stats["decode_tokens"] += emitted
        return emitted

    def run_to_completion(self, max_steps: int = 10_000):
        steps = 0
        while self.queue or any(r is not None for r in self.slots):
            if steps >= max_steps:
                stuck = sorted(
                    [r.uid for r in self.queue]
                    + [r.uid for r in self.slots if r is not None])
                raise RuntimeError(
                    f"run_to_completion: max_steps={max_steps} exhausted "
                    f"with requests still pending (uids {stuck}); raise "
                    "max_steps or check for a wedged slot")
            self.step()
            steps += 1
        return self.completed


class SpliceBatcher(ContinuousBatcher):
    """Admit-time full prefill + jit'd slot splice — the pre-interleave
    baseline.  Kept as the measured reference for `serving_bench` and the
    parity tests; every admit stalls the whole decode batch for the full
    prompt and double-writes its KV pages (one-sequence cache → splice).
    """

    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        if self.shared:
            raise ValueError(
                "SpliceBatcher is the stripe-layout baseline: a shared "
                "pool has no per-slot stripe to splice into (a B=1 "
                "prefill cache owns a different pool entirely); use "
                "ContinuousBatcher with shared_pool=True, or the stripe "
                "layout for splice-baseline measurements")
        max_context = self.max_context
        self._prefill1 = jax.jit(
            lambda p, b: self.engine.prefill(p, b, max_context))
        self._prefill1_bucketed = jax.jit(
            lambda p, b, n: self.engine.prefill(p, b, max_context,
                                                prompt_len=n))
        self._splice = jax.jit(_splice_slot, donate_argnums=(0,))

    def _admit(self):
        for i in range(self.B):
            if self.slots[i] is None and self.queue:
                req = self.queue.popleft()
                self.slots[i] = req
                self._set_slot_params(i, req)
                # decoders idle for the whole admit: in chunk units, the
                # interleaved scheduler would have run this many decode
                # steps over the currently active slots
                n_dec = sum(1 for j, r in enumerate(self.slots)
                            if r is not None and j != i)
                span = len(self._padded(req))
                self.stats["decode_stall_tokens"] += n_dec * (
                    -(-span // self.chunk_tokens))
                self.stats["admits"] += 1
                self._prefill_slot(i, req)

    def _padded(self, req: Request) -> List[int]:
        n = len(req.prompt)
        if not self.bucket_prompts:
            return req.prompt
        Sb = bucket_length(n, hi=self.max_context - 1)
        return req.prompt + [0] * (Sb - n)

    def _prefill_slot(self, i: int, req: Request):
        """Prefill one sequence and splice its pools/length into slot i."""
        n = len(req.prompt)
        toks = self._put(np.asarray(self._padded(req), np.int32)[None])
        self._count_compile("prefill", toks.shape[1])
        if self.bucket_prompts:
            logits, c1 = self._prefill1_bucketed(
                self.params, {"tokens": toks}, self._put(np.int32(n)))
        else:
            logits, c1 = self._prefill1(self.params, {"tokens": toks})
        self._count_compile("splice")
        self.cache = self._splice(self.cache, c1, self._put(np.int32(i)))
        self._lengths[i] = self._prefix + n
        tok, lp = self._sample_row(logits, req)
        self._emit_token(i, req, tok, lp)

    def step(self) -> int:
        """One decode step over all active slots (admits prefill eagerly
        inside `_admit`, stalling the batch)."""
        self._admit()
        active = [i for i, r in enumerate(self.slots) if r is not None]
        decoded = self._decode_batch(active)
        self.stats["steps"] += 1
        return decoded


# module-level aliases so tests can monkeypatch `sched._splice_slot`
# (the writers themselves live with the pool-leaf writer family in
# core/paged_kv.py — KV004 discipline, DESIGN.md §15)
_splice_slot = paged_kv.splice_slot
_splice_slot_ref = paged_kv.splice_slot_ref
