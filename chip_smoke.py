#!/usr/bin/env python3
"""Chip smoke test: serve qwen1.5-0.5b at full width on one TPU.

Drives the serving path a user calls — `KVNANDServer` ->
`ContinuousBatcher` -> `KVNANDEngine` -> paged KV pool -> Pallas decode
kernel — at the published qwen1.5-0.5b widths (24 layers, d_model 1024,
16 MHA heads, d_head 64, vocab 151,936), with random weights from
`--seed`, and checks what comes out:

  (a) device: a TPU, or exit non-zero with no result line;
  (b) the default `ServerConfig` path (striped pool, bf16 KV,
      attn_impl "auto"): 8 greedy requests, prompt lengths 64-1024 drawn
      from --seed, 32 new tokens each, batch_slots 8, max_context 4096
      (256 pages per slot, so the split-page partitioned grid runs);
  (c) the same prompts through a shared pool with kv8 pages;
  (d) one request on a float32 pool: each served token's logprob against
      a float32 teacher-forced reference (`Model.forward`,
      attn_impl="ref", highest matmul precision);
  (e) the compiled decode steps of (b) and (c) contain the Pallas kernel
      (`tpu_custom_call`).

`--replicas 4` runs only the four-chip phase and its comparison: four
`build_replica` replicas, one per device, behind a disaggregated
`ReplicaRouter`, against one `KVNANDServer` on device 0 — tokens and
logprobs bit-identical, every replica's arrays on its own device, one
compiled decode signature per replica.

Compile seconds and decode ms/step are printed as "smoke, not a
benchmark".  The last line of stdout is one JSON object,
{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}; any
failed phase exits 1 without it.

  python chip_smoke.py [--seed 0] [--replicas 4]
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback
from importlib import metadata

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import EngineConfig, get_config  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.registry import Model  # noqa: E402
from repro.models.transformer import Runtime  # noqa: E402
from repro.serving.api import (KVNANDServer, SamplingParams,  # noqa: E402
                               ServerConfig)
from repro.serving.replica import build_replica  # noqa: E402
from repro.serving.router import ReplicaRouter  # noqa: E402

ARCH = "qwen1.5-0.5b"
N_REQUESTS, MIN_PROMPT, MAX_PROMPT, NEW_TOKENS = 8, 64, 1024, 32
SLOTS, MAX_CONTEXT = 8, 4096
REF_LEN = MAX_PROMPT + NEW_TOKENS       # one compiled reference length

# Logprob tolerances, in nats, against the float32 reference run at the
# highest matmul precision.  The served path runs the TPU's default
# precision: every f32 matmul rounds its operands to bf16 (relative
# 2^-9).  With fan-in scaled random weights the logits have unit spread,
# and rounding noise of ~0.3% per sublayer, summed over 48 sublayers,
# moves them by ~0.01-0.02 nats.  TOL_F32 leaves ~5-10x headroom on that
# for the float32 pool; TOL_POOL adds bf16 (2^-9) or kv8 (1/127 of a
# page's absmax) rounding of every cached K/V.  A decode kernel reading a
# wrong page, mask or scale moves attention outputs by their own O(1)
# scale, far beyond either bound.
TOL_F32 = 0.1
TOL_POOL = 0.3

_COMPILE_S = [0.0]


def _on_duration(event, secs, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE_S[0] += secs


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok, msg) -> None:
    """A check that also holds under `python -O` (which drops asserts);
    `msg` may be a callable, for messages only valid on failure."""
    if not ok:
        raise RuntimeError(msg() if callable(msg) else msg)


def check_device(need: int):
    """(a): a TPU with at least `need` devices, or exit non-zero."""
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found {d.platform} "
                 f"({d.device_kind}) — no result")
    if len(devs) < need:
        sys.exit(f"chip_smoke: needs {need} TPU devices, found "
                 f"{len(devs)} — no result")
    log(f"[a] device: {d.platform} {d.device_kind} x{len(devs)}; "
        f"jax {jax.__version__}, jaxlib {metadata.version('jaxlib')}, "
        f"libtpu {metadata.version('libtpu')}")
    return devs


def make_prompts(seed: int, vocab: int):
    rng = np.random.default_rng(seed)
    lens = rng.integers(MIN_PROMPT, MAX_PROMPT + 1, N_REQUESTS)
    return [rng.integers(0, vocab, int(n)).tolist() for n in lens]


def server_config(engine: EngineConfig | None, seed: int,
                  slots: int = SLOTS) -> ServerConfig:
    return ServerConfig(arch=ARCH, engine=engine, batch_slots=slots,
                        max_context=MAX_CONTEXT, seed=seed)


def drive(server: KVNANDServer, prompts, label: str):
    """Serve `prompts` greedily step by step; returns the outputs and
    prints compile seconds and decode ms per step (steps that ran no
    prefill chunk and compiled nothing)."""
    sp = SamplingParams(max_new_tokens=NEW_TOKENS, logprobs=True)
    c0, t0 = _COMPILE_S[0], time.perf_counter()
    uids = [server.submit(p, sp) for p in prompts]
    done, decode_ms = 0, []
    while done < len(uids):
        st = server.stats
        before = (st["compiles"], st["prefill_chunks"])
        t = time.perf_counter()
        done += sum(e.finish_reason is not None for e in server.step())
        if (st["compiles"], st["prefill_chunks"]) == before:
            decode_ms.append((time.perf_counter() - t) * 1e3)
    outs = [server.output(u) for u in uids]
    med = float(np.median(decode_ms)) if decode_ms else float("nan")
    log(f"[{label}] smoke, not a benchmark: {time.perf_counter() - t0:.1f}"
        f" s wall, {_COMPILE_S[0] - c0:.1f} s XLA compile, decode "
        f"{med:.2f} ms/step (median of {len(decode_ms)} decode-only "
        f"steps, batch {server.config.batch_slots})")
    return outs


def check_served(outs, label: str):
    for o in outs:
        require(o.finish_reason == "length"
                and len(o.token_ids) == NEW_TOKENS,
                f"{label}: request {o.uid} finished {o.finish_reason} "
                f"after {len(o.token_ids)} tokens")
        require(np.all(np.isfinite(o.logprobs)), f"{label}: non-finite")


def make_reference(cfg, params):
    """Teacher-forced float32 reference: per position, the logprob of the
    next token, the argmax and the top-2 margin (padded to REF_LEN —
    causal, so padding never reaches an earlier position)."""
    model = Model(cfg, Runtime(attn_impl="ref"))

    @jax.jit
    def score(params, tokens):
        logits, _ = model.forward(params, {"tokens": tokens})
        lg = logits[0, :-1, :cfg.vocab_size].astype(jnp.float32)
        lp = jax.nn.log_softmax(lg, axis=-1)
        nxt = jnp.take_along_axis(lp, tokens[0, 1:, None], axis=-1)[:, 0]
        top, idx = jax.lax.top_k(lg, 2)
        return nxt, idx[:, 0], top[:, 0] - top[:, 1]

    def run(prompt, out_tokens):
        seq = list(prompt) + list(out_tokens)
        toks = np.zeros((1, REF_LEN), np.int32)
        toks[0, :len(seq)] = seq
        with jax.default_matmul_precision("highest"):
            nxt, amax, margin = jax.device_get(score(params, toks))
        # output token j is predicted at position len(prompt) - 1 + j
        sl = slice(len(prompt) - 1, len(prompt) - 1 + len(out_tokens))
        return nxt[sl], amax[sl], margin[sl]

    return run


def check_against_reference(ref, prompts, outs, tol: float, label: str):
    worst, checked = 0.0, 0
    for p, o in zip(prompts, outs):
        lp, amax, margin = ref(p, o.token_ids)
        err = np.abs(np.asarray(o.logprobs) - lp)
        worst = max(worst, float(err.max()))
        require(err.max() <= tol,
                f"{label}: request {o.uid} logprob off by {err.max():.4f} "
                f"nats at token {int(err.argmax())} (tolerance {tol})")
        clear = margin > tol
        checked += int(clear.sum())
        bad = np.nonzero(clear & (np.asarray(o.token_ids) != amax))[0]
        require(not len(bad),
                lambda: f"{label}: request {o.uid} token {int(bad[0])} is "
                f"not the reference argmax though its top-2 margin "
                f"{float(margin[bad[0]]):.3f} exceeds {tol}")
    log(f"[{label}] logprobs vs float32 reference: max |diff| {worst:.5f} "
        f"nats (tolerance {tol}); {checked} tokens with top-2 margin > "
        f"{tol} all equal the reference argmax")


def decode_hlo(server: KVNANDServer) -> str:
    """Compiled HLO of the server's jitted decode step, at the shapes it
    serves with."""
    b = server._batcher
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,  # noqa: E731
                                         sharding=a.sharding)
    vec = lambda dt: jax.ShapeDtypeStruct((b.B,), dt)  # noqa: E731
    args = (jax.tree.map(sds, b.params), jax.tree.map(sds, b.cache),
            jax.ShapeDtypeStruct((b.B, 1), jnp.int32), vec(jnp.bool_),
            vec(jnp.int32), vec(jnp.bool_), vec(jnp.float32),
            vec(jnp.int32), vec(jnp.float32), vec(jnp.uint32),
            vec(jnp.int32))
    return b._decode.lower(*args).compile().as_text()


def one_chip(cfg, params, prompts, seed):
    """Phases (b)-(e), one server (and one KV pool) alive at a time."""
    ref = make_reference(cfg, params)
    results = {}

    def phase(name, fn):
        try:
            fn()
            results[name] = True
            log(f"[{name}] PASS")
        except Exception as e:  # noqa: BLE001 — report every phase
            results[name] = False
            traceback.print_exc()
            log(f"[{name}] FAIL: {type(e).__name__}: {e}")
        gc.collect()

    def serve(label, engine, prompts, tol, slots=SLOTS):
        server = KVNANDServer(server_config(engine, seed, slots), cfg=cfg,
                              params=params)
        outs = drive(server, prompts, label)
        check_served(outs, label)
        check_against_reference(ref, prompts, outs, tol, label)
        return server

    def kernel(server, label):
        """(e): the Pallas kernel is in the compiled decode step."""
        n = decode_hlo(server).count("tpu_custom_call")
        require(n > 0, f"{label}: no tpu_custom_call in its decode step")
        log(f"[e:{label}] {n} tpu_custom_call op(s) in the compiled "
            "decode step")

    served = {}
    phase("b", lambda: served.update(b=serve("b", None, prompts, TOL_POOL)))
    phase("e:b", lambda: kernel(served.pop("b"), "b"))
    kv8 = EngineConfig(page_tokens=16, uniform_lengths=False,
                       shared_pool=True, kv_quant="kv8")
    phase("c", lambda: served.update(c=serve("c", kv8, prompts, TOL_POOL)))
    phase("e:c", lambda: kernel(served.pop("c"), "c"))
    f32 = EngineConfig(page_tokens=16, uniform_lengths=False,
                       kv_dtype="float32")
    phase("d", lambda: serve("d", f32, prompts[:1], TOL_F32, slots=1))
    return all(results.values())


def replicas(cfg, params, prompts, seed, n, devs):
    """Four one-chip replicas behind a disaggregated router vs one
    server on device 0 (which is freed before the fleet is built)."""
    eng = EngineConfig(page_tokens=16, uniform_lengths=False,
                       shared_pool=True)
    sc = server_config(eng, seed)
    solo = KVNANDServer(sc, cfg=cfg, params=params)
    want = drive(solo, prompts, "solo device 0")
    check_served(want, "solo")
    del solo
    gc.collect()

    servers = [build_replica(sc, cfg=cfg, params=params, device=devs[k])
               for k in range(n)]
    router = ReplicaRouter(servers, disaggregate=True)
    sp = SamplingParams(max_new_tokens=NEW_TOKENS, logprobs=True)
    c0, t0 = _COMPILE_S[0], time.perf_counter()
    for i, p in enumerate(prompts):
        router.submit(p, sp, uid=i)
    router.run()
    got = [router.output(i) for i in range(len(prompts))]
    log(f"[replicas] smoke, not a benchmark: {time.perf_counter() - t0:.1f}"
        f" s wall, {_COMPILE_S[0] - c0:.1f} s XLA compile, "
        f"{router.stats['migrations']} migrations, "
        f"{router.stats['migration_bytes']} migration bytes")
    check_served(got, "replicas")
    require(router.stats["migrations"] == len(prompts),
            f"migrations: {router.stats}")
    for w, g in zip(want, got):
        require(g.token_ids == w.token_ids,
                f"request {w.uid}: tokens differ from the single server")
        require(g.logprobs == w.logprobs,
                f"request {w.uid}: logprobs differ from the single server")
    log(f"[replicas] tokens and logprobs of {len(got)} requests "
        "bit-identical to one server on device 0")
    for k, s in enumerate(servers):
        b = s._batcher
        leaves = jax.tree.leaves((b.params, b.cache))
        placed = {frozenset(x.devices()) for x in leaves}
        require(placed == {frozenset({devs[k]})},
                f"replica {k}: arrays on {placed}, want {{{devs[k]}}}")
        sigs = b._decode._cache_size()
        # replica 0 prefills only: its held slots never decode
        require(sigs == 1 or (k == 0 and sigs == 0),
                f"replica {k}: _decode compiled {sigs} signatures")
        log(f"[replicas] replica {k}: {len(leaves)} params/cache arrays "
            f"all on {devs[k]}; _decode compiled {sigs} signature(s)")
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--replicas", type=int, default=0,
                    help="run only the N-replica router phase (N chips)")
    args = ap.parse_args(argv)
    devs = check_device(max(args.replicas, 1))
    log(f"compile cache: {enable_compile_cache()}")
    jax.monitoring.register_event_duration_secs_listener(_on_duration)

    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    # one compiled init: eager init dispatches (and compiles) op by op
    params = jax.jit(Model(cfg, Runtime()).init)(
        jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    log(f"params: {sum(x.size for x in jax.tree.leaves(params)):,} f32 "
        f"from seed {args.seed} in {time.perf_counter() - t0:.1f} s")
    prompts = make_prompts(args.seed, cfg.vocab_size)
    log(f"prompts: lengths {[len(p) for p in prompts]}")

    if args.replicas:
        ok = replicas(cfg, params, prompts, args.seed, args.replicas, devs)
        count = args.replicas
    else:
        ok = one_chip(cfg, params, prompts, args.seed)
        count = 1
    if not ok:
        log("chip_smoke: a phase failed — no result")
        return 1
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
