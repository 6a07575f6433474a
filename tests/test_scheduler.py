"""Continuous-batching scheduler: jitted slot splice (vs the old eager
full-pool copy), power-of-two prompt bucketing, end-to-end decode
equivalence across both repairs, and the admission hardening (capacity
rejection, bucket clamp, stuck-drain diagnostics)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import EngineConfig, get_config
from repro.models.registry import Model
from repro.models.transformer import Runtime
from repro.serving.scheduler import (ContinuousBatcher, Request,
                                     SpliceBatcher, bucket_length,
                                     _splice_slot, _splice_slot_ref)

ARCH = "qwen1.5-0.5b"


def _model(arch=ARCH):
    cfg = get_config(arch).reduced()
    rt = Runtime()
    m = Model(cfg, rt)
    return cfg, rt, m.init(jax.random.PRNGKey(0))


def test_bucket_length():
    assert bucket_length(1) == 16
    assert bucket_length(16) == 16
    assert bucket_length(17) == 32
    assert bucket_length(100) == 128
    # near-capacity prompts must not round past the slot stripe
    assert bucket_length(100, hi=120) == 120
    assert bucket_length(100, hi=128) == 128


def test_submit_rejects_oversized_and_empty_prompts():
    cfg, rt, params = _model()
    b = ContinuousBatcher(cfg, params, batch_slots=2, max_context=64)
    b.submit(Request(0, list(range(1, 64)), max_new=1))    # 63 == capacity
    with pytest.raises(ValueError, match="exceeds the slot capacity"):
        b.submit(Request(1, list(range(1, 65)), max_new=1))  # 64 > capacity
    with pytest.raises(ValueError, match="empty prompt"):
        b.submit(Request(2, [], max_new=1))


def test_run_to_completion_raises_on_exhausted_steps():
    cfg, rt, params = _model()
    b = ContinuousBatcher(cfg, params, batch_slots=1, max_context=64)
    b.submit(Request(7, [1, 2, 3], max_new=8))
    b.submit(Request(9, [4, 5], max_new=8))
    with pytest.raises(RuntimeError, match=r"uids \[7, 9\]"):
        b.run_to_completion(max_steps=1)


def test_jitted_splice_identical_to_eager():
    """The dynamic_update_slice splice produces a cache bit-identical to
    the old `.at[:, i].set` path, for every leaf and several slots."""
    cfg, rt, params = _model()
    b = ContinuousBatcher(cfg, params, batch_slots=3, max_context=64)
    eng = b.engine
    _, c1 = eng.prefill(params,
                        {"tokens": jnp.arange(1, 12)[None].astype(jnp.int32)},
                        64)
    for i in (0, 2):
        jitted = _splice_slot(eng.init_cache(3, 64), c1,
                              jnp.asarray(i, jnp.int32))
        eager = _splice_slot_ref(eng.init_cache(3, 64), c1, i)
        for f in dataclasses.fields(jitted):
            a, e = getattr(jitted, f.name), getattr(eager, f.name)
            if a is None:
                continue
            np.testing.assert_array_equal(np.asarray(a), np.asarray(e),
                                          err_msg=f.name)


def test_jitted_splice_is_single_dynamic_update_per_leaf():
    """Admit must not lower to a whole-pool gather/scatter: the jaxpr of
    the splice contains only dynamic_update_slice writes (no scatter)."""
    cfg, rt, params = _model()
    b = ContinuousBatcher(cfg, params, batch_slots=3, max_context=64)
    _, c1 = b.engine.prefill(
        params, {"tokens": jnp.arange(1, 12)[None].astype(jnp.int32)}, 64)
    jaxpr = jax.make_jaxpr(_splice_slot)(b.cache, c1,
                                         jnp.asarray(1, jnp.int32))
    prims = {eqn.primitive.name for eqn in jaxpr.jaxpr.eqns}
    assert "dynamic_update_slice" in prims
    assert "scatter" not in prims and "gather" not in prims


def _run(cfg, params, prompts, *, bucket, max_new=5, slots=2, ctx=96,
         eng=None, cls=SpliceBatcher):
    """Bucketing lives in the splice path (the interleaved scheduler uses
    the chunk grid instead), so the bucket-parity tests run SpliceBatcher."""
    b = cls(cfg, params, batch_slots=slots, max_context=ctx,
            temperature=0.0, bucket_prompts=bucket, eng=eng)
    for uid, p in enumerate(prompts):
        b.submit(Request(uid, list(p), max_new=max_new))
    done = b.run_to_completion()
    return {u: r.output for u, r in done.items()}


PROMPTS = [list(range(1, 8)), list(range(3, 24)), list(range(2, 13)),
           [5, 4, 3]]


def test_bucketed_prefill_matches_exact_dense():
    cfg, rt, params = _model()
    assert _run(cfg, params, PROMPTS, bucket=False) == \
        _run(cfg, params, PROMPTS, bucket=True)


def test_bucketed_prefill_matches_exact_window():
    """gemma3 reduced: the window-ring dyn fill must keep live pages even
    when the padded prompt spans more source pages than the ring holds."""
    cfg, rt, params = _model("gemma3-12b")
    assert _run(cfg, params, PROMPTS, bucket=False, max_new=4) == \
        _run(cfg, params, PROMPTS, bucket=True, max_new=4)


def test_recurrent_family_falls_back_to_exact():
    cfg, rt, params = _model("rwkv6-3b")
    b = ContinuousBatcher(cfg, params, batch_slots=2, max_context=64,
                          bucket_prompts=True)
    assert not b.bucket_prompts            # silently disabled, still runs
    b.submit(Request(0, [1, 2, 3, 4, 5], max_new=3))
    done = b.run_to_completion()
    assert len(done[0].output) == 3


def test_scheduler_with_quantized_kv():
    """Continuous batching over kv8 pools: ragged requantizing appends +
    jitted splice of the scale leaves."""
    cfg, rt, params = _model()
    eng = EngineConfig(page_tokens=16, kv_quant="kv8")
    outs = _run(cfg, params, PROMPTS[:2], bucket=True, max_new=4, eng=eng)
    assert sorted(outs) == [0, 1]
    assert all(len(v) == 4 for v in outs.values())
