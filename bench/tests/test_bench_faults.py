"""The harness end to end on the CPU at a tiny size, skipping only its
look for a chip: a sound run comes out correct, the fp8 control reads
above the limits, and a run with the timed path broken underneath comes
out not correct, once for each fault a serving cell can have."""
from __future__ import annotations

import json
import os
import sys
import time

import jax.numpy as jnp
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

TINY = {"name": "tiny", "hidden_size": 64, "intermediate_size": 128,
        "num_hidden_layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "vocab_size": 512,
        "tie_word_embeddings": False, "rope_theta": 10000.0,
        "rms_norm_eps": 1e-6,
        "precision": {"compute": "bfloat16", "weights_held": "float32",
                      "kv_cache": "bfloat16"},
        "program": {"arch": "qwen2.5-32b", "replace": {
            "d_model": 64, "n_heads": 4, "n_kv_heads": 2, "d_head": 16,
            "d_ff": 128, "vocab_size": 512, "n_layers": 2,
            "rope_theta": 10000.0, "norm_eps": 1e-6}},
        "reference": "bench/reference.py"}
MIX = {"loop": "closed", "clients": 4, "rounds": 3,
       "prompt_tokens": {"dist": "uniform", "min": 20, "max": 60},
       "output_tokens": {"dist": "uniform", "min": 12, "max": 24}}
# Readings of this tiny cell on the CPU (float32 matmuls, bfloat16 KV):
# sound runs gap 0 and logprob error ~0.01; the fp8 control ~0.4 / ~0.4.
# The check scores every finished request (the token target is above
# what the window serves), so a fault in any slot shows.
CELL = {"server": {"batch_slots": 4, "max_context": 128,
                   "prefill_chunk_tokens": 32, "max_queue": 16},
        "check": {"tokens": 10_000,
                  "limits": {"gap": 0.1, "logprob_err": 0.1}}}


def spec():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    return harness.Spec(
        name="tiny", chips=1, config=TINY, mix=MIX, cell=CELL,
        metrics=[m for m in bench["end_to_end"]
                 if harness.applies(m, "tiny")], per_layer=[],
        peaks=harness.load_json(ROOT, "bench", "peaks.json"))


def run(control=False, before_serve=None):
    res = harness.run_cell(spec(), 2**33 + 17, 4.0, False,
                           t_proc0=time.perf_counter(), require_tpu=False,
                           control=control, before_serve=before_serve)
    return res


@pytest.fixture(scope="module")
def sound():
    return run(control=True)


def test_sound_run_is_correct_and_control_is_not(sound):
    r = sound["readings"]
    assert sound["correct"], sound["check"]
    assert sound["failed"] == 0 and sound["attempted"] > 0
    assert r["tokens"] >= 60
    ctrl = harness.control_readings(r)
    assert set(ctrl) == {"tokens", "gap", "logprob_err"}
    assert not harness.is_correct(ctrl, CELL["check"]["limits"]), ctrl
    assert list(sound)[-1] == "check"
    assert set(sound["metrics"]) == {"output_tok_s", "itl_p50_ms",
                                     "itl_p95_ms", "setup_s"}


def _token_altered(monkeypatch):
    from repro.serving import scheduler
    orig = scheduler.sample_with_logprobs

    def altered(logits, *a, true_vocab, **k):
        toks, lps = orig(logits, *a, true_vocab=true_vocab, **k)
        return (toks + 1) % true_vocab, lps
    monkeypatch.setattr(scheduler, "sample_with_logprobs", altered)


def _state_unchanged(monkeypatch):
    from repro.core.engine import KVNANDEngine
    orig = KVNANDEngine.decode_step

    def frozen(self, params, cache, tokens, **k):
        logits, _ = orig(self, params, cache, tokens, **k)
        return logits, cache
    monkeypatch.setattr(KVNANDEngine, "decode_step", frozen)


def _half_batch_left_out(monkeypatch):
    from repro.core.engine import KVNANDEngine
    orig = KVNANDEngine.decode_step

    def half(self, params, cache, tokens, active=None, **k):
        B = tokens.shape[0]
        keep = jnp.arange(B) < B // 2
        active = keep if active is None else active & keep
        return orig(self, params, cache, tokens, active=active, **k)
    monkeypatch.setattr(KVNANDEngine, "decode_step", half)


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch_left_out])
def test_broken_timed_path_is_not_correct(fault, monkeypatch, sound):
    res = run(before_serve=lambda: fault(monkeypatch))
    assert not res["correct"], res["check"]
