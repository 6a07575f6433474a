"""Serving-scheduler benchmark: interleaved chunked prefill vs the splice
baseline, plus the shared-pool allocator (FTL-mapped paged KV, §IV-D),
all driven through the request-centric `KVNANDServer` facade.

Runs the same request trace through three schedulers on the reduced
config and emits, per scheduler:

  serving/<mode>/wall                 end-to-end µs (derived: tok/s)
  serving/<mode>/steps_to_drain       scheduler steps to drain the trace
  serving/<mode>/compiles             distinct jit signatures compiled
  serving/<mode>/decode_stall_per_admit
        decode tokens NOT generated while an admit monopolized the engine
        (0 by construction for the interleaved schedulers).
  serving/<mode>/ttft_p50, ttft_p95   time to first token (µs), from
  serving/<mode>/tpot_p50, tpot_p95   RequestOutput timing; TPOT = mean
        per-token time after the first.  p95 TTFT lands on the requests
        that pay the jit compiles (fresh server per drain).

Shared-pool trajectory metrics (the allocator's capacity win):

  serving/shared/pool_util            peak live pages / pool pages
  serving/shared_prefix/prefix_hit_rate
        prompt pages served from the radix prefix cache on a
        shared-system-prompt trace (> 0 == prefix sharing works)
  serving/shared_capacity/stripe_overcommit
        summed per-slot stripe pages of the admitted mix / pool pages —
        > 1 means the mix could NOT have been admitted under the old
        per-slot stripe layout, yet the pooled allocator drains it.

Speculative decoding (DESIGN.md §11) gets its own trace: a repetitive
prompt set where prompt-lookup drafts actually hit, drained through the
verify path and cross-checked token-identical against sequential decode:

  serving/spec/accepted_per_step      tokens emitted per verify step
        (accepted drafts + the correction token); > 1.0 == speculation
        genuinely amortizes weight loads on this trace
  serving/spec/wall                   end-to-end µs for the spec drain
  serving/spec/seq_wall               the same trace decoded sequentially

Tiered flash KV hierarchy (DESIGN.md §13) gets a two-wave trace whose
working set exceeds the hot tier (wave 2 re-admits wave 1's prompts
after their cache pages were demoted to the capacity store), drained
with prefetch on and off and cross-checked token-identical against the
single-tier pool:

  serving/tiered/wall                 end-to-end µs (prefetch on)
  serving/tiered/hit_rate             cached map-ins served hot (< 100%
        by construction — the first re-admission wave demand-faults)
  serving/tiered/stall_tokens         demand promotions with prefetch ON
        (must beat stall_tokens_noprefetch; derived column carries the
        flashsim-modeled stall seconds)
  serving/tiered/stall_tokens_noprefetch   the ablation
  serving/tiered/pool_util_hot        peak hot-resident / hot slots
  serving/tiered/pool_util_capacity   peak live flash pages / flash pool

Overlapped host/device pipeline (DESIGN.md §14) gets a Poisson-arrival
open-loop trace — requests arrive on their own clock, not when a slot
frees — drained twice through the SAME load generator, overlap on and
off, hard-failing on token divergence OR on the overlapped drain
losing to the synchronous one.  The drain is a modeled-device replay:
the real scheduler decodes real tokens on CPU-XLA, and every dispatched
decode step additionally occupies a MODELED kvnand-d device window
(flashsim.serving_step_time with host_s=0 — the flash-read latency a
CPU cannot emulate; the XLA compute rides inside it).  `collect()`
blocks until the oldest step's modeled completion, steps serialize on
the modeled device, and the two disciplines differ only in WHEN the
host half runs: the synchronous loop pays window + host per step, the
pipelined loop does step N+1's host half inside step N's window —
dev + host vs max(dev, host), the exact comparison the flashsim model
makes, here executed by the real scheduler under real load.  (This
container is single-core: without the modeled window, JAX's own async
dispatch plus CPU timesharing make the two disciplines statistically
indistinguishable — there is no second core for "overlap" to use.)

  serving/async/wall                  end-to-end µs, overlap ON (the
        derived column carries the overlap-off wall and the speedup)
  serving/async/wall_overlap_off      the synchronous ablation
  serving/async/device_idle_frac      % of the overlapped drain's wall
        with NO step in flight (sync fraction in derived — the host
        time the pipeline hides; feeds flashsim.overlap_speedup)
Multi-replica router + disaggregated prefill/decode (DESIGN.md §16):
the routed trace drains over 1 and 2 replicas — every replica's decode
steps occupy its OWN modeled kvnand-d device window, so aggregate
throughput scales with fleet slot capacity while staying token-identical
to the single-server drain; the disaggregated drain measures what a
migration actually ships over the wire:

  serving/replicas/tok_s_1, tok_s_2   aggregate modeled tok/s at 1 and
        2 replicas (hard-fails unless 2 replicas drain in fewer router
        steps than 1 — the scaling the fleet exists for)
  serving/replicas/ttft_p95_1, ttft_p95_2   modeled p95 time to first
        token at each replica count
  serving/replicas/migration_bytes_per_req  KVEnvelope wire bytes per
        migrated request (prefill replica -> decode replica, all
        requests migrating)

  serving/async/goodput_under_sla     req/s finishing within the SLA
        (TTFT + max_new x TPOT budget) under overlap

`wall`, `steps_to_drain`, and the ttft/tpot p50 rows are gated by
check_regression.py (p95 rows are informational — compile-dominated;
the serving/spec/*, serving/tiered/* and serving/async/* rows ride the
ungated-prefix mechanism while those features land); counter rows
carry the count in `us_per_call` (the harness's one numeric column)
with the unit spelled out in `derived`.
"""
import time
from collections import deque

import jax
import numpy as np

from benchmarks.common import emit

ARCH = "qwen1.5-0.5b"
SLOTS = 3
MAX_CONTEXT = 128
CHUNK = 32
MAX_NEW = 8
N_REQUESTS = 8
PAGE_TOKENS = 16


def _trace(vocab):
    rng = np.random.default_rng(7)
    return [rng.integers(1, vocab, int(n)).tolist()
            for n in rng.integers(5, 45, N_REQUESTS)]


def _prefix_trace(vocab):
    """Shared 32-token system prompt + unique tails, incl. one repeat."""
    rng = np.random.default_rng(11)
    sysp = rng.integers(1, vocab, 32).tolist()
    tails = [rng.integers(1, vocab, 9).tolist() for _ in range(5)]
    return [sysp + t for t in tails] + [sysp + tails[0]]


def _spec_trace(vocab):
    """Repetitive prompts (a 6-token motif repeated) so prompt-lookup
    drafting has something to hit."""
    rng = np.random.default_rng(17)
    return [(rng.integers(1, vocab, 6).tolist() * 5) for _ in range(4)]


N_TIER_UNIQ = 10
TIER_TOTAL_PAGES = 96
TIER_HOT_PAGES = 12


def _tier_trace(vocab):
    """Shared 32-token system prompt + 10 unique 9-token tails.  Ten
    41-token prompts page out to far more flash pages than the 12-slot
    hot tier holds, so draining them twice forces wave 1's prefix-cache
    pages through demotion and back."""
    rng = np.random.default_rng(23)
    sysp = rng.integers(1, vocab, 32).tolist()
    return [sysp + rng.integers(1, vocab, 9).tolist()
            for _ in range(N_TIER_UNIQ)]


def _drain_tiered(cfg, params, eng, uniq, *, prefetch=True):
    """Two-wave drain on ONE server: wave 1 admits the uniques, wave 2
    re-submits the same prompts after their pages were demoted.  The
    first re-admissions demand-fault in both modes (no queue to peek
    before they map), the staggered rest give prefetch its window."""
    from repro.serving.api import (KVNANDServer, SamplingParams,
                                   ServerConfig)

    server = KVNANDServer(
        ServerConfig(scheduler="interleaved", engine=eng,
                     batch_slots=SLOTS, max_context=64,
                     prefill_chunk_tokens=PAGE_TOKENS,
                     tier_prefetch=prefetch),
        cfg=cfg, params=params)
    sp = SamplingParams(max_new_tokens=MAX_NEW)
    outs = {}
    t0 = time.perf_counter()
    for wave in range(2):
        uids = [server.submit(p, sp) for p in uniq]
        server.run()
        for u in uids:
            outs[(wave, u)] = server.output(u).token_ids
            server.release(u)
    dt = time.perf_counter() - t0
    return dt, outs, server.stats


N_ASYNC = 12
ASYNC_RATE_HZ = 120.0           # open-loop arrivals fast enough to keep
                                # a backlog — overlap has work to hide
ASYNC_SLA_S = 2.0               # e2e budget per request (reduced model)


def _poisson_arrivals(n, rate_hz):
    rng = np.random.default_rng(29)
    return np.cumsum(rng.exponential(1.0 / rate_hz, n)).tolist()


def _drain_poisson(cfg, params, eng, prompts, arrivals, warmup, *,
                   overlap, device_s):
    """Open-loop drain: submit each prompt at its arrival offset while
    stepping the scheduler — the serving shape the overlapped pipeline
    exists for.  Both modes run the SAME generator; only the stepping
    discipline (dispatch N+1 before collect N vs dispatch; collect)
    differs.

    Every dispatched decode step occupies the modeled kvnand-d device
    for `device_s` (the flash-read window CPU-XLA cannot emulate; the
    real XLA compute of the step rides inside it).  Modeled steps
    serialize — step N+1's window opens when step N's closes — and
    `collect` blocks until the oldest step's modeled completion.  The
    synchronous discipline therefore pays window + host per step; the
    pipelined one runs the next step's host half inside the current
    window.  Prefill chunks execute host-side inside `dispatch` in both
    disciplines and are deliberately NOT charged a window (symmetric,
    so the A/B isolates the decode pipeline).

    `warmup` prompts have the SAME lengths as `prompts` but different
    content: chunk jit signatures key on (first-chunk, length) only, so
    the warmup drain compiles every signature the timed window will hit
    WITHOUT seeding the prefix cache with the timed prompts — cache
    hits would both skew the measurement and re-prefill evicted entries
    from mid-page offsets, compiling fresh chunk lengths mid-window."""
    from repro.serving.api import (KVNANDServer, SamplingParams,
                                   ServerConfig)

    server = KVNANDServer(
        ServerConfig(scheduler="interleaved", engine=eng,
                     batch_slots=SLOTS, max_context=MAX_CONTEXT,
                     prefill_chunk_tokens=CHUNK, overlap=overlap),
        cfg=cfg, params=params)
    sp = SamplingParams(max_new_tokens=MAX_NEW)
    server.generate(warmup, sp)             # warmup: pay ALL the compiles
    uids = {}
    nxt = 0
    deadlines = deque()                     # modeled completion, oldest 1st
    last_dl = 0.0

    def _dispatch():
        nonlocal last_dl
        before = server.pending_steps()
        server.dispatch()
        if server.pending_steps() > before:
            last_dl = max(time.perf_counter(), last_dl) + device_s
            deadlines.append(last_dl)

    def _collect():
        if deadlines:
            time.sleep(max(0.0, deadlines[0] - time.perf_counter()))
        server.collect()
        while len(deadlines) > server.pending_steps():
            deadlines.popleft()

    t0 = time.perf_counter()
    # device-idle accounting starts at t0, not at the warmup's end
    server._batcher._idle_since = time.monotonic()
    idle0 = server.stats["device_idle_s"]
    steps0 = server.stats["steps"]
    while nxt < len(prompts) or server._busy() or server.pending_steps():
        now = time.perf_counter() - t0
        while nxt < len(prompts) and arrivals[nxt] <= now:
            uids[nxt] = server.submit(prompts[nxt], sp)
            nxt += 1
        if not server._busy() and not server.pending_steps():
            if nxt < len(prompts):          # idle until the next arrival
                time.sleep(max(0.0, arrivals[nxt]
                               - (time.perf_counter() - t0)))
            continue
        if overlap:
            if server.pending_steps() == 0 and server._busy():
                _dispatch()                 # prime the pipeline
            if server._busy():
                _dispatch()                 # step N+1 onto the device
            _collect()                      # step N's tokens
        else:
            _dispatch()
            _collect()
    wall = time.perf_counter() - t0
    outs = {i: server.output(u) for i, u in uids.items()}
    st = dict(server.stats)
    st["idle_s"] = st["device_idle_s"] - idle0
    st["steps"] = st["steps"] - steps0
    return wall, outs, st


def _drain(scheduler, cfg, params, eng, prompts, *, slots=SLOTS,
           max_context=MAX_CONTEXT, spec_k=0, max_new=MAX_NEW):
    from repro.serving.api import (KVNANDServer, SamplingParams,
                                   ServerConfig)

    server = KVNANDServer(
        ServerConfig(scheduler=scheduler, engine=eng, batch_slots=slots,
                     max_context=max_context,
                     prefill_chunk_tokens=CHUNK, speculation_k=spec_k),
        cfg=cfg, params=params)
    sp = SamplingParams(max_new_tokens=max_new)
    t0 = time.perf_counter()
    outs = server.generate(prompts, sp)
    dt = time.perf_counter() - t0
    total = sum(len(o.token_ids) for o in outs)
    return dt, total, server.stats, {o.uid: o.token_ids for o in outs}, \
        outs


def _drain_router(cfg, params, eng, prompts, n, *, disaggregate=False):
    """Drain `prompts` through a ReplicaRouter over `n` serving replicas
    (+ a dedicated prefill replica when disaggregated).  Returns router
    steps to drain (the fleet's modeled wall — replicas step their own
    modeled devices in parallel), the router step at which each uid's
    first token appeared, the per-uid token streams, and the router."""
    from repro.serving.api import (KVNANDServer, SamplingParams,
                                   ServerConfig)
    from repro.serving.router import ReplicaRouter

    servers = [
        KVNANDServer(
            ServerConfig(scheduler="interleaved", engine=eng,
                         batch_slots=SLOTS, max_context=MAX_CONTEXT,
                         prefill_chunk_tokens=CHUNK),
            cfg=cfg, params=params)
        for _ in range(n + (1 if disaggregate else 0))]
    router = ReplicaRouter(servers, disaggregate=disaggregate)
    sp = SamplingParams(max_new_tokens=MAX_NEW)
    uids = [router.submit(p, sp, uid=i) for i, p in enumerate(prompts)]
    first_step = {}
    steps = 0
    while router._busy():
        for e in router.step():
            if e.index == 0 and e.token is not None:
                first_step.setdefault(e.uid, steps + 1)
        steps += 1
        if steps >= 10_000:
            raise AssertionError("router drain did not converge")
    outs = {u: router.output(u).token_ids for u in uids}
    return steps, first_step, outs, router


def _emit_latency(mode, outs):
    from repro.serving.api import latency_percentile
    for name, sel in (("ttft", lambda o: o.ttft),
                      ("tpot", lambda o: o.tpot)):
        vals = [sel(o) for o in outs if sel(o) is not None]
        for q in (50, 95):
            emit(f"serving/{mode}/{name}_p{q}",
                 latency_percentile(vals, q) * 1e6,
                 f"us {name} p{q} over {len(vals)} requests")


def run():
    from repro.configs import EngineConfig, get_config
    from repro.models.registry import Model
    from repro.models.transformer import Runtime

    cfg = get_config(ARCH).reduced()
    params = Model(cfg, Runtime()).init(jax.random.PRNGKey(0))
    stripe = EngineConfig(page_tokens=PAGE_TOKENS)
    shared = EngineConfig(page_tokens=PAGE_TOKENS, shared_pool=True)
    prompts = _trace(cfg.vocab_size)

    outs = {}
    for mode, sched, eng in (("splice", "splice", stripe),
                             ("interleaved", "interleaved", stripe),
                             ("shared", "interleaved", shared)):
        dt, total, st, outs[mode], ro = _drain(sched, cfg, params, eng,
                                               prompts)
        stall = st["decode_stall_tokens"] / max(st["admits"], 1)
        emit(f"serving/{mode}/wall", dt * 1e6,
             f"{total / dt:.1f} tok/s cpu ({total} tokens)")
        emit(f"serving/{mode}/steps_to_drain", float(st["steps"]),
             f"steps; {st['prefill_chunks']} prefill chunks")
        emit(f"serving/{mode}/compiles", float(st["compiles"]),
             "distinct jit signatures")
        emit(f"serving/{mode}/decode_stall_per_admit", stall,
             f"decode tokens stalled per admit "
             f"({st['decode_stall_tokens']} over {st['admits']} admits)")
        _emit_latency(mode, ro)
        if mode == "shared":
            util = st["pool_peak_pages"] / max(st["pool_total_pages"], 1)
            emit("serving/shared/pool_util", util * 100.0,
                 f"% peak: {st['pool_peak_pages']} of "
                 f"{st['pool_total_pages']} pool pages live")
    for mode in ("interleaved", "shared"):
        if outs[mode] != outs["splice"]:
            raise AssertionError(
                f"{mode} scheduler diverged from the splice baseline")

    # prefix sharing: shared system prompt -> cached pages served
    pprompts = _prefix_trace(cfg.vocab_size)
    _, _, st_ref, o_ref, _ = _drain("interleaved", cfg, params, stripe,
                                    pprompts)
    dt, total, st, o_shared, _ = _drain("interleaved", cfg, params,
                                        shared, pprompts)
    if o_shared != o_ref:
        raise AssertionError("prefix-cache outputs diverged from stripe")
    hit_rate = st["prefix_hit_pages"] / max(st["prompt_pages"], 1)
    emit("serving/shared_prefix/prefix_hit_rate", hit_rate * 100.0,
         f"% of prompt pages served from cache "
         f"({st['prefix_hit_pages']}/{st['prompt_pages']}; "
         f"{st['cow_copies']} COW copies)")

    # capacity-proportional admission: 6 slots whose per-slot stripes
    # (6 × NPg pages) cannot fit the 16-page pool, yet the actual mix can
    cap_eng = EngineConfig(page_tokens=PAGE_TOKENS, shared_pool=True,
                           total_pages=16)
    rng = np.random.default_rng(13)
    cap_prompts = [rng.integers(1, cfg.vocab_size, 11).tolist()
                   for _ in range(6)]
    dt, total, st, o_cap, _ = _drain("interleaved", cfg, params, cap_eng,
                                     cap_prompts, slots=6)
    if len(o_cap) != len(cap_prompts):
        raise AssertionError("capacity mix did not drain")
    npg = -(-MAX_CONTEXT // PAGE_TOKENS)
    overcommit = 6 * npg / st["pool_total_pages"]
    emit("serving/shared_capacity/stripe_overcommit", overcommit,
         f"x: {6 * npg} stripe pages admitted through a "
         f"{st['pool_total_pages']}-page pool "
         f"(peak {st['pool_peak_pages']} live)")

    # speculative decoding: a repetitive trace where lookup drafts hit;
    # outputs must stay token-identical to sequential decode, and each
    # verify step must amortize > 1 token (the whole point)
    sprompts = _spec_trace(cfg.vocab_size)
    dt_seq, _, _, o_seq, _ = _drain("interleaved", cfg, params, shared,
                                    sprompts, max_new=16)
    emit("serving/spec/seq_wall", dt_seq * 1e6,
         "us: same trace decoded sequentially")
    dt, total, st, o_spec, _ = _drain("interleaved", cfg, params, shared,
                                      sprompts, spec_k=4, max_new=16)
    if o_spec != o_seq:
        raise AssertionError("speculative outputs diverged from "
                             "sequential decode")
    from repro.serving.api import accepted_tokens_per_step
    per_step = accepted_tokens_per_step(st["spec_accepted"],
                                        st["spec_steps"]) or 0.0
    if per_step <= 1.0:
        raise AssertionError(
            f"speculation never accepted a draft on the repetitive "
            f"trace (accepted {st['spec_accepted']} over "
            f"{st['spec_steps']} verify row-steps)")
    emit("serving/spec/accepted_per_step", per_step,
         f"tokens per request-verify-step ({st['spec_accepted']} "
         f"drafts accepted of {st['spec_drafted']} over "
         f"{st['spec_steps']} row-steps)")
    emit("serving/spec/wall", dt * 1e6,
         f"{total / dt:.1f} tok/s cpu ({total} tokens, spec_k=4)")

    # tiered flash KV hierarchy (DESIGN.md §13): two-wave trace whose
    # working set (96 flash pages) exceeds the 12-slot hot tier; outputs
    # must stay token-identical to the single-tier pool, the hot tier
    # must actually miss (< 100% hit rate), and prefetch must absorb
    # demand faults relative to the ablation
    tier_uniq = _tier_trace(cfg.vocab_size)
    flat_eng = EngineConfig(page_tokens=PAGE_TOKENS,
                            shared_pool=True,
                            total_pages=TIER_TOTAL_PAGES)
    tier_eng = EngineConfig(page_tokens=PAGE_TOKENS,
                            shared_pool=True,
                            total_pages=TIER_TOTAL_PAGES,
                            hot_pages=TIER_HOT_PAGES)
    _, o_flat, _ = _drain_tiered(cfg, params, flat_eng, tier_uniq)
    dt_off, o_off, st_off = _drain_tiered(cfg, params, tier_eng,
                                          tier_uniq, prefetch=False)
    dt_on, o_on, st_on = _drain_tiered(cfg, params, tier_eng, tier_uniq)
    for name, o in (("prefetch-on", o_on), ("prefetch-off", o_off)):
        if o != o_flat:
            raise AssertionError(
                f"tiered {name} outputs diverged from the single-tier "
                "pool")
    touched = st_on["tier_hit_pages"] + st_on["tier_miss_pages"]
    tier_hr = st_on["tier_hit_pages"] / max(touched, 1)
    if tier_hr >= 1.0:
        raise AssertionError(
            "tiered trace never missed the hot tier — working set does "
            "not exceed it")
    if st_on["tier_stall_tokens"] >= st_off["tier_stall_tokens"]:
        raise AssertionError(
            f"prefetch did not reduce demand faults "
            f"({st_on['tier_stall_tokens']} on vs "
            f"{st_off['tier_stall_tokens']} off)")
    from repro.core import flashsim as fs
    sysm = fs.kvnand_d(8, 8, 4, 16, kv_bits=8)
    stall_s = fs.tier_stall_time(sysm, get_config(ARCH),
                                 st_on["tier_stall_tokens"],
                                 PAGE_TOKENS)
    emit("serving/tiered/wall", dt_on * 1e6,
         f"us two-wave drain, prefetch on ({dt_off * 1e6:.0f} off)")
    emit("serving/tiered/hit_rate", tier_hr * 100.0,
         f"% cached map-ins hot ({st_on['tier_hit_pages']}/{touched}; "
         f"{st_on['tier_prefetch_pages']} prefetched)")
    emit("serving/tiered/stall_tokens",
         float(st_on["tier_stall_tokens"]),
         f"demand promotions, prefetch on; modeled stall "
         f"{stall_s * 1e6:.0f} us on kvnand-d")
    emit("serving/tiered/stall_tokens_noprefetch",
         float(st_off["tier_stall_tokens"]),
         f"demand promotions with prefetch disabled "
         f"({st_off['tier_demotes']} demotes)")
    emit("serving/tiered/pool_util_hot",
         st_on["tier_peak_hot"] / st_on["tier_hot_slots"] * 100.0,
         f"% peak: {st_on['tier_peak_hot']} of "
         f"{st_on['tier_hot_slots']} hot slots resident")
    emit("serving/tiered/pool_util_capacity",
         st_on["pool_peak_pages"] / st_on["pool_total_pages"] * 100.0,
         f"% peak: {st_on['pool_peak_pages']} of "
         f"{st_on['pool_total_pages']} flash pages live")

    # overlapped host/device pipeline (DESIGN.md §14): the SAME
    # Poisson-arrival trace through both stepping disciplines over the
    # modeled kvnand-d decode window; tokens must match exactly and the
    # pipelined drain must win wall-clock (best of 2 per mode — arrival
    # sleeps and modeled windows are identical, so the min isolates the
    # stepping discipline from runner noise)
    dev_s = fs.serving_step_time(sysm, get_config(ARCH), MAX_CONTEXT,
                                 0.0, overlap=False)
    rng = np.random.default_rng(31)
    alens = rng.integers(5, 45, N_ASYNC)
    aprompts = [rng.integers(1, cfg.vocab_size, int(n)).tolist()
                for n in alens]
    wrng = np.random.default_rng(37)        # same lengths, fresh content
    awarmup = [wrng.integers(1, cfg.vocab_size, int(n)).tolist()
               for n in alens]
    arrivals = _poisson_arrivals(N_ASYNC, ASYNC_RATE_HZ)
    runs = {}
    for overlap in (False, True):
        runs[overlap] = min(
            (_drain_poisson(cfg, params, shared, aprompts, arrivals,
                            awarmup, overlap=overlap, device_s=dev_s)
             for _ in range(2)),
            key=lambda r: r[0])
    (wall_off, ao_off, ast_off) = runs[False]
    (wall_on, ao_on, ast_on) = runs[True]
    for i in ao_on:
        if ao_on[i].token_ids != ao_off[i].token_ids:
            raise AssertionError(
                f"overlapped pipeline diverged from the synchronous "
                f"schedule on request {i}")
    if wall_on >= wall_off:
        raise AssertionError(
            f"overlapped drain did not beat the synchronous one "
            f"({wall_on * 1e3:.1f} ms on vs {wall_off * 1e3:.1f} ms off)")
    idle_on = ast_on["idle_s"] / wall_on
    idle_off = ast_off["idle_s"] / wall_off
    # the host time the sync loop serializes, per step: what the DSE's
    # overlap recommendation consumes (flashsim.overlap_speedup)
    host_s = ast_off["idle_s"] / max(ast_off["steps"], 1)
    from repro.core import dse
    rec = dse.recommend_overlap(sysm, get_config(ARCH), MAX_CONTEXT,
                                host_s)
    emit("serving/async/wall", wall_on * 1e6,
         f"us Poisson drain, overlap on; {wall_off * 1e6:.0f} us off "
         f"(speedup {wall_off / wall_on:.2f}x, {N_ASYNC} requests at "
         f"{ASYNC_RATE_HZ:.0f}/s, modeled kvnand-d decode window "
         f"{dev_s * 1e6:.0f} us/step)")
    emit("serving/async/wall_overlap_off", wall_off * 1e6,
         "us: the synchronous-stepping ablation, same trace and "
         "modeled device windows")
    emit("serving/async/device_idle_frac", idle_on * 100.0,
         f"% of wall with no step in flight (sync {idle_off * 100.0:.1f}%"
         f"; host {host_s * 1e6:.0f} us/step, dse.recommend_overlap="
         f"{rec} on kvnand-d)")
    met = sum(1 for o in ao_on.values()
              if o.finish_time - o.submit_time <= ASYNC_SLA_S)
    emit("serving/async/goodput_under_sla", met / wall_on,
         f"req/s within the {ASYNC_SLA_S:.1f}s SLA "
         f"({met}/{len(ao_on)} requests met it)")

    # multi-replica router + disaggregated prefill/decode (DESIGN.md
    # §16): same trace, 1 vs 2 replicas; each replica's decode steps
    # occupy its own modeled kvnand-d window, so router steps-to-drain
    # is the fleet's modeled wall.  Token streams must match the
    # single-server drain exactly at every replica count AND through
    # the disaggregated prefill->migrate->decode path.
    from repro.serving.api import latency_percentile
    rep = {}
    for n in (1, 2):
        rep[n] = _drain_router(cfg, params, shared, prompts, n)
    steps_1, _, _, _ = rep[1]
    steps_2, _, _, _ = rep[2]
    if steps_2 >= steps_1:
        raise AssertionError(
            f"2 replicas did not drain in fewer router steps than 1 "
            f"({steps_2} vs {steps_1})")
    total = sum(len(t) for t in outs["shared"].values())
    for n in (1, 2):
        steps_n, first, router_outs, _ = rep[n]
        if router_outs != outs["shared"]:
            raise AssertionError(
                f"router drain at {n} replicas diverged from the "
                "single-server baseline")
        wall_n = steps_n * dev_s
        emit(f"serving/replicas/tok_s_{n}", total / wall_n,
             f"modeled aggregate tok/s, {n} replica(s) x {SLOTS} slots "
             f"({steps_n} router steps x {dev_s * 1e6:.0f} us window)")
        ttft = [s * dev_s * 1e6 for s in first.values()]
        emit(f"serving/replicas/ttft_p95_{n}",
             latency_percentile(ttft, 95),
             f"us modeled p95 TTFT over {len(ttft)} requests")
    _, _, dis_outs, dis_router = _drain_router(cfg, params, shared,
                                               prompts, 1,
                                               disaggregate=True)
    if dis_outs != outs["shared"]:
        raise AssertionError(
            "disaggregated prefill/decode diverged from the "
            "single-server baseline")
    n_mig = dis_router.stats["migrations"]
    if n_mig != len(prompts):
        raise AssertionError(
            f"only {n_mig} of {len(prompts)} requests migrated")
    emit("serving/replicas/migration_bytes_per_req",
         dis_router.stats["migration_bytes"] / n_mig,
         f"KVEnvelope wire bytes per migrated request "
         f"({n_mig} migrations, retries "
         f"{dis_router.stats['migration_retries']})")


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
