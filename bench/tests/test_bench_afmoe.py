"""The AfMoE reference (`bench/reference_afmoe.py`) and the readers of the
MoE and window-ring counters: its sizes against hand counts, its
weights against the program's parameter tree, its blockwise forward
against its one-piece forward, its routing tie margins by hand, the
check leaving out only near-tie tokens and still reading planted faults,
and `moe_tokens_per_expert` /
`window_live_page_share` on hand-made runs, and None where the counters
are absent (a program without them, or the Qwen cells)."""
from __future__ import annotations

import importlib.util
import os
import sys
from types import SimpleNamespace

import jax
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from bench import harness, reference, reference_afmoe as ra  # noqa: E402

CONF = harness.load_json(ROOT, "bench", "configs", "trinity-mini-l8.json")


def metric(name):
    spec = importlib.util.spec_from_file_location(
        f"m_{name}", os.path.join(ROOT, "bench", "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_dims_match_hand_counts():
    dm = ra.dims(CONF)
    # q, k, v, o, gate: 2048*4096 + 2*2048*512 + 4096*2048 + 2048*4096
    attn = 8_388_608 * 3 + 2_097_152
    assert dm.attn_params == attn == 27_262_976
    expert = 3 * 2048 * 1024                                # 6,291,456
    dense = attn + 3 * 2048 * 6144                          # 65.0M
    moe = attn + 2048 * 128 + expert + 8 * 16 * expert // 128   # 40.1M
    assert (dense, moe) == (65_011_712, 40_108_032)
    assert dm.moe_layer_matmul_params == moe
    assert dm.layer_matmul_params == (2 * dense + 6 * moe) // 8 \
        == 46_333_952
    # the published 32-layer model with all 128 experts: ~26.1B
    norms = 4 * 2048 + 2 * 128
    pub = (2 * (dense + norms)
           + 30 * (attn + 2048 * 128 + 128 + 129 * expert + norms)
           + 2 * 200_192 * 2048 + 2048)
    assert ra.n_params(dm) == pub
    assert pub == pytest.approx(26.1e9, rel=0.002)
    assert (dm.L, dm.L_pub, dm.E, dm.E_held, dm.n_dense, dm.top_k) == \
        (8, 32, 128, 16, 2, 8)
    assert [li for li in range(dm.L) if dm.is_global(li)] == [3, 7]


def test_config_file_names_source_cut_and_deployment():
    entry = {c["name"]: c for c in harness.load_json(
        ROOT, "BENCHMARK.json")["configs"]}["trinity-mini-l8"]
    assert CONF["source"] == entry["source"]
    assert CONF["reduced"] == entry["reduced"] == ["num_hidden_layers",
                                                   "num_experts"]
    assert CONF["published"] == {"num_hidden_layers": 32,
                                 "num_experts": 128}
    assert "32 chips" in CONF["deployment"]
    for key in ("embedding", "norms", "attention", "attention_gate", "moe",
                "expert_bias"):
        assert CONF["assumed"][key]
    mc = harness.model_config(CONF, ra.dims(CONF))        # the program
    assert (mc.n_layers, mc.experts_held, mc.n_experts) == (8, 16, 128)


def test_weights_match_the_program_parameter_tree():
    from repro.models.registry import Model
    dm = ra.dims(CONF)
    mine = jax.eval_shape(lambda: ra.make_weights(dm, 5))
    theirs = Model(harness.model_config(CONF, dm)).abstract_params()[0]
    shape = lambda t: jax.tree.map(lambda a: (a.shape, a.dtype), t)  # noqa
    assert shape(mine) == shape(theirs)


TINY = ra.Dims(d=64, H=4, K=2, dh=16, ff=32, ff_dense=96, V=300, Vp=512,
               L=6, L_pub=6, n_dense=2, E=8, E_held=4, off=4, top_k=2,
               n_shared=1, route_scale=2.826, window=24, global_every=4,
               theta=1e4, eps=1e-5)


def test_blockwise_reference_equals_one_shot():
    params = ra.make_weights(TINY, 2**35 + 3)
    toks = np.random.default_rng(0).integers(0, TINY.V, 56)
    buf = np.zeros(64, np.int32)
    buf[:56] = toks
    x = ra.hidden(params, TINY, buf, block=16)
    idx = np.arange(56)
    mx, am, at, lse = reference.head_stats(params, TINY, x, idx,
                                           toks.astype(np.int32), rows=32)
    full = ra.oneshot_logits(params, TINY, toks)
    np.testing.assert_allclose(mx, full.max(-1), rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(am, full.argmax(-1))
    np.testing.assert_allclose(at, full[idx, toks], rtol=1e-5, atol=1e-5)


def test_fp8_control_reads_differently():
    params = ra.make_weights(TINY, 7)
    toks = np.random.default_rng(1).integers(0, TINY.V, 40)
    buf = np.zeros(48, np.int32)
    buf[:40] = toks
    got = ra.score(params, TINY, toks[:20].tolist(), toks[20:].tolist(),
                   np.zeros(20), pad_to=48, block=16, control=True)
    assert np.max(got["ctrl_logprob_err"]) > 1e-3


def test_tie_margin_by_hand():
    """Scores all 0.5 (a zero router), so selection follows the bias:
    0.8, 0.7, 0.6, 0.5.  The top 2 are experts 0 and 1; the margin is
    expert 1's selection score less expert 2's, where 1 or 2 is held."""
    dm = TINY._replace(d=4, E=4, top_k=2)
    h = np.ones((3, 4), np.float32)
    m = {"router_w": np.zeros((4, 4), np.float32),
         "expert_bias": np.array([0.3, 0.2, 0.1, 0.0], np.float32)}
    for off, held, want in [(2, 2, 0.1), (1, 1, 0.1), (3, 1, np.inf),
                            (0, 1, np.inf)]:
        got = ra.tie_margin(h, m, dm._replace(off=off, E_held=held))
        np.testing.assert_allclose(np.asarray(got), [want] * 3, rtol=1e-6)


SCORE_DM = TINY._replace(E=32, E_held=8, off=8, top_k=4)


@pytest.fixture(scope="module")
def scored():
    """A 120-token sequence at SCORE_DM: weights, tokens, the positions'
    tie margins, and the reference's own logprobs of the last 60."""
    params = ra.make_weights(SCORE_DM, 11)
    toks = np.random.default_rng(1).integers(0, SCORE_DM.V, 120)
    buf = np.zeros(128, np.int32)
    buf[:120] = toks
    x, mg = ra.hidden(params, SCORE_DM, buf, 16, margins=True)
    idx = np.arange(59, 119)
    _, _, at, lse = reference.head_stats(params, SCORE_DM, x, idx,
                                         toks[60:].astype(np.int32), rows=64)
    return params, toks, np.asarray(mg)[idx], at - lse


def score_of(scored, logprobs, tokens=None, tie=None, monkeypatch=None):
    params, toks, _, _ = scored
    if tie is not None:
        monkeypatch.setattr(ra, "ROUTE_TIE", tie)
    served = toks[60:] if tokens is None else tokens
    return ra.score(params, SCORE_DM, toks[:60].tolist(), list(served),
                    logprobs, pad_to=128, block=16, control=True)


def test_score_leaves_out_only_routing_near_ties(scored, monkeypatch):
    _, _, mg, lp = scored
    tied = mg < ra.ROUTE_TIE
    assert 0 < tied.sum() < len(tied) // 2
    got = score_of(scored, lp)
    raw = score_of(scored, lp, tie=-np.inf, monkeypatch=monkeypatch)
    assert raw["tied_share"][0] == 0
    np.testing.assert_allclose(got["tied_share"], [tied.mean()])
    for k in ("gap", "logprob_err", "ctrl_gap", "ctrl_logprob_err"):
        np.testing.assert_array_equal(got[k], np.where(tied, 0.0, raw[k]))
    assert np.max(got["logprob_err"]) < 1e-4       # its own logprobs


@pytest.mark.parametrize("fault", ["logprob", "token"])
def test_faults_show_past_the_near_tie_mask(scored, fault):
    """Served logprobs off by 0.5, or a served token swapped for the one
    the reference ranks last: the untied tokens still read the fault."""
    params, toks, mg, lp = scored
    if fault == "logprob":
        got = score_of(scored, lp + 0.5)
        assert np.max(got["logprob_err"]) > 0.49
        return
    lg = np.asarray(ra.oneshot_logits(params, SCORE_DM, toks))[59:119]
    bad = toks[60:].copy()
    j = int(np.argmax(mg))                           # an untied position
    bad[j] = int(np.argmin(lg[j]))
    got = score_of(scored, lp, tokens=bad)
    assert got["gap"][j] > 1.0


def run_of(stats0, stats1, dm=None):
    return SimpleNamespace(stats0=stats0, stats1=stats1,
                           dm=dm or ra.dims(CONF))


def test_moe_tokens_per_expert_on_hand_made_runs():
    # 100 decode steps of 32 rows, 6 MoE layers, even routing: each of
    # the 16 held experts serves 32 * 8 / 128 = 2 pairs a step per layer
    s0 = {"moe_pairs_held": 500, "decode_steps": 7}
    s1 = {"moe_pairs_held": 500 + 100 * 32 * 6, "decode_steps": 107}
    assert metric("moe_tokens_per_expert").read(run_of(s0, s1)) == 2.0


def test_window_live_page_share_on_hand_made_runs():
    s0 = {"decode_pages_walked_w": 4352, "decode_pages_live_w": 4000}
    s1 = {"decode_pages_walked_w": 4352 * 11,
          "decode_pages_live_w": 4000 + 4352 * 10 // 2}
    assert metric("window_live_page_share").read(run_of(s0, s1)) == 50.0


QWEN = reference.dims(harness.load_json(ROOT, "bench", "configs",
                                        "qwen1.5-0.5b.json"))


@pytest.mark.parametrize("name,s0,s1,dm", [
    ("moe_tokens_per_expert", {}, {}, None),           # program without
    ("moe_tokens_per_expert", {"moe_pairs_held": 0, "decode_steps": 5},
     {"moe_pairs_held": 0, "decode_steps": 9}, QWEN),   # a dense model
    ("moe_tokens_per_expert", {"moe_pairs_held": 3, "decode_steps": 5},
     {"moe_pairs_held": 3, "decode_steps": 5}, None),   # no decode step
    ("window_live_page_share", {}, {}, None),
    ("window_live_page_share", {"decode_pages_walked_w": 0,
                                "decode_pages_live_w": 0},
     {"decode_pages_walked_w": 0, "decode_pages_live_w": 0}, QWEN),
])
def test_readers_are_silent_without_their_counters(name, s0, s1, dm):
    assert metric(name).read(run_of(s0, s1, dm)) is None
