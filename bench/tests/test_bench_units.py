"""CPU tests of the benchmark's own arithmetic: traffic, reductions,
configuration files, the reference and the front of `bench/run.py`."""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import client, loadgen, measure, reference, work  # noqa: E402
from bench import harness  # noqa: E402

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(ROOT, "bench",
                                                       "traffic")))


def mix(name):
    return harness.load_json(ROOT, "bench", "traffic", f"{name}.json")


# -- traffic ---------------------------------------------------------------

@pytest.mark.parametrize("name", MIXES)
def test_traffic_is_deterministic_per_seed(name):
    a = loadgen.make_requests(mix(name), 2**33 + 7, 45, 1000)
    b = loadgen.make_requests(mix(name), 2**33 + 7, 45, 1000)
    c = loadgen.make_requests(mix(name), 2**33 + 8, 45, 1000)
    assert [dataclasses.astuple(r) for r in a] == \
        [dataclasses.astuple(r) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in c]


@pytest.mark.parametrize("name", MIXES)
def test_traffic_lengths_within_clips_and_same_work_per_seed(name):
    m = mix(name)
    runs = [loadgen.make_requests(m, s, 45, 1000) for s in (3, 2**40 + 1)]
    for reqs in runs:
        for r in reqs:
            p, o = m["prompt_tokens"], m["output_tokens"]
            assert p["min"] <= len(r.prompt) <= p["max"]
            assert o["min"] <= r.max_tokens <= o["max"]
            assert all(0 <= t < 1000 for t in r.prompt)
    for key in (lambda r: len(r.prompt), lambda r: r.max_tokens):
        assert sorted(map(key, runs[0])) == sorted(map(key, runs[1]))
    if m["loop"] == "closed":            # the same pairs in each round
        pairs = [sorted((r.round, len(r.prompt), r.max_tokens)
                        for r in reqs) for reqs in runs]
        assert pairs[0] == pairs[1]
    if m["loop"] == "open":
        due = [[r.due for r in reqs] for reqs in runs]
        # a mix with a schedule of its own gives every seed that schedule
        assert (due[0] == due[1]) == ("schedule_seed" in m)
        # every block of arrivals offers the same rate
        gaps = np.diff(due[0])
        b = loadgen.BLOCK
        blocks = [gaps[i:i + b].sum() for i in range(0, len(gaps) - b, b)]
        assert np.std(blocks) < 0.6 * np.mean(blocks)


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_schedule_seed_fixes_the_order_and_the_seed_the_tokens(loop):
    m = dict(mix("chat" if loop == "open" else "longctx-4k"),
             schedule_seed=5)
    a, b = (loadgen.make_requests(m, s, 45, 1000) for s in (3, 2**40 + 1))
    shape = lambda r: (len(r.prompt), r.max_tokens, r.due, r.client)  # noqa
    assert [shape(r) for r in a] == [shape(r) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]
    c = loadgen.make_requests(dict(m, schedule_seed=6), 3, 45, 1000)
    assert [shape(r) for r in c] != [shape(r) for r in a]


def test_closed_loop_rounds_deal_fixed_pairs():
    m = mix("longctx-4k")
    reqs = loadgen.make_requests(m, 11, 45, 1000)
    nc = m["clients"]
    for r0 in range(m["rounds"]):
        rnd = [r for r in reqs if r.round == r0]
        assert sorted(r.client for r in rnd) == list(range(nc))


# -- reductions ------------------------------------------------------------

def rec(times, n_prompt=10, due=0.0):
    r = client.Record(rid=0, n_prompt=n_prompt, max_tokens=len(times),
                      due=due)
    r.times = list(times)
    return r


def test_percentiles_pool_all_samples_not_medians_of_parts():
    a = rec([0.0, 1.0, 2.0, 3.0])            # gaps 1, 1, 1
    b = rec([0.0, 10.0])                     # gap 10
    gaps = measure.inter_token_gaps([a, b], 0.0, 100.0)
    assert sorted(gaps) == [1.0, 1.0, 1.0, 10.0]
    assert measure.percentile(gaps, 50) == 1.0
    assert measure.percentile(gaps, 95) == pytest.approx(8.65)
    assert measure.percentile([], 50) is None


def test_rates_count_the_whole_window():
    a = rec([0.5, 1.5, 2.5, 9.5])
    b = rec([3.0, 12.0])
    assert measure.rate([a, b], 0.0, 10.0) == pytest.approx(0.5)
    # a gap counts where its later token lands in the window
    assert measure.inter_token_gaps([a], 2.0, 10.0) == [1.0, 7.0]


def test_ttft_counts_requests_due_in_the_window_from_their_due_time():
    early = rec([1.0], due=0.5)
    due_in = rec([12.0], due=10.0)
    late = rec([30.0], due=21.0)
    assert measure.ttfts([early, due_in, late], 5.0, 20.0) == [2.0]


def test_work_counts_needed_decode_from_token_records():
    dm = reference.dims(harness.load_json(
        ROOT, "bench", "configs", "qwen1.5-0.5b.json"))
    r = rec([0.0, 1.0, 2.0], n_prompt=100)
    af, kb, mf, nd = work.window_work([r], dm, 2, 0.5, 10.0)
    assert nd == 2
    per_tok = 2 * 16 * 64 * 2                # K+V bf16 bytes, one layer
    assert per_tok == 4096 and per_tok * 24 == 98304
    assert kb == (101 + 102) * per_tok * 24
    assert af == 4 * 16 * 64 * (101 + 102) * 24
    af0, kb0, mf0, nd0 = work.window_work([r], dm, 2, 0.0, 0.5)
    assert (af0, kb0, nd0) == (0, 0, 0)
    assert mf0 == work.prefill_flops(dm, 100)


# -- configurations ---------------------------------------------------------

@pytest.mark.parametrize("name,want", [("qwen1.5-0.5b", 464.1e6),
                                       ("qwen2.5-32b-l2", 2.53e9)])
def test_config_files_hold_published_widths(name, want):
    conf = harness.load_json(ROOT, "bench", "configs", f"{name}.json")
    assert conf["name"] == name
    entry = {c["name"]: c for c in BENCH["configs"]}.get(name)
    if entry is not None:
        assert conf["source"] == entry["source"]
        assert conf["reduced"] == entry["reduced"]
    dm = reference.dims(conf)
    published = {"qwen1.5-0.5b": (1024, 16, 16, 64, 2816, 151936, True),
                 "qwen2.5-32b-l2": (5120, 40, 8, 128, 27648, 152064,
                                    False)}[name]
    assert (dm.d, dm.H, dm.K, dm.dh, dm.ff, dm.V, dm.tied) == published
    n = reference.n_params(dm)
    assert n == pytest.approx(want, rel=0.0005 if want < 1e9 else 0.002)
    mc = harness.model_config(conf, dm)        # the program agrees
    assert mc.n_layers == dm.L


def test_harness_takes_the_reference_the_configuration_names(tmp_path):
    conf = harness.load_json(ROOT, "bench", "configs", "qwen1.5-0.5b.json")
    assert harness.reference_of(conf).dims(conf) == reference.dims(conf)
    other = tmp_path / "other_reference.py"
    other.write_text("from bench.reference import *  # noqa\n"
                     "def program_fields(dm):\n"
                     "    return {'n_layers': dm.L + 1}\n")
    conf = dict(conf, reference=str(other))
    with pytest.raises(SystemExit, match="n_layers"):
        harness.model_config(conf, reference.dims(conf))


@pytest.mark.parametrize("name", ["qwen1.5-0.5b", "qwen2.5-32b-l2"])
def test_weights_match_the_program_parameter_tree(name):
    from repro.models.registry import Model
    conf = harness.load_json(ROOT, "bench", "configs", f"{name}.json")
    dm = reference.dims(conf)
    mine = jax.eval_shape(lambda: reference.make_weights(dm, 5))
    theirs = Model(harness.model_config(conf, dm)).abstract_params()[0]
    shape = lambda t: jax.tree.map(lambda a: (a.shape, a.dtype), t)  # noqa
    assert shape(mine) == shape(theirs)


# -- reference --------------------------------------------------------------

TINY = {"hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "vocab_size": 300, "tie_word_embeddings": False,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-6}


@pytest.mark.parametrize("tied", [False, True])
def test_blockwise_reference_equals_one_shot(tied):
    dm = reference.dims(dict(TINY, tie_word_embeddings=tied))
    params = reference.make_weights(dm, 2**35 + 3)
    toks = np.random.default_rng(0).integers(0, dm.V, 48)
    buf = np.zeros(64, np.int32)
    buf[:48] = toks
    x = reference.hidden(params, dm, buf, block=16)
    idx = np.arange(48)
    mx, am, at, lse = reference.head_stats(params, dm, x, idx,
                                           toks.astype(np.int32), rows=32)
    full = reference.oneshot_logits(params, dm, toks)
    np.testing.assert_allclose(mx, full.max(-1), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(am, full.argmax(-1))
    np.testing.assert_allclose(at, full[idx, toks], rtol=1e-5, atol=1e-5)
    lse_full = np.log(np.exp(full - full.max(-1, keepdims=True)).sum(-1)) \
        + full.max(-1)
    np.testing.assert_allclose(lse, lse_full, rtol=1e-5, atol=1e-5)


def test_weights_are_the_same_for_the_same_seed():
    dm = reference.dims(TINY)
    a = jax.tree.leaves(reference.make_weights(dm, 2**33 + 1))
    b = jax.tree.leaves(reference.make_weights(dm, 2**33 + 1))
    c = jax.tree.leaves(reference.make_weights(dm, 1))
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])


# -- the command ------------------------------------------------------------

def test_run_refuses_a_machine_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        BENCH["workloads"][0]["name"], "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_benchmark_names_a_file_for_every_piece():
    for c in BENCH["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in BENCH["workloads"]:
        assert os.path.exists(os.path.join(
            ROOT, "bench", "traffic", f"{w['traffic']}.json"))
        assert os.path.exists(os.path.join(
            ROOT, "bench", "cells", f"{w['name']}.json"))
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert callable(harness.reader(m["name"]))
