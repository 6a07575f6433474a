"""The reduction from a profiler trace to per-layer numbers, on a small
recorded trace (`trace_longctx_cut.json`: 60 ms of the qwen05b-longctx
decode loop on a TPU v5 lite) and on hand-made events."""
from __future__ import annotations

import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from bench import trace_reduce as tr  # noqa: E402

DEV = "/device:TPU:0"
PALLAS = 'custom-call(), custom_call_target="tpu_custom_call"'


def metric(name):
    spec = importlib.util.spec_from_file_location(
        f"m_{name}", os.path.join(ROOT, "bench", "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "trace_longctx_cut.json")) as f:
        return tr.Trace.from_json(f.read())


def test_recorded_window_busy_and_gaps_add_up(recorded):
    assert recorded.window_s == pytest.approx(0.06)
    assert recorded.chips() == [DEV]
    busy = tr.busy_s(recorded)
    gaps = tr.idle_gaps(recorded, DEV)
    assert busy == pytest.approx(0.059996459, abs=1e-9)
    assert sum(b - a for a, b in gaps) / 1e9 == pytest.approx(
        recorded.window_s - busy, abs=1e-9)
    assert all(a < b for a, b in gaps)


def test_recorded_kernel_matcher_finds_the_paged_decode_kernel(recorded):
    k = metric("paged_attn_roofline")
    assert k.kernel_s(recorded) == pytest.approx(0.010915683, abs=1e-9)
    # the decode launch that starts in the stretch, whole
    assert tr.module_time(recorded, r"^jit__decode_fn\b") == (
        pytest.approx(0.126574636, abs=1e-9), 1)
    assert tr.module_time(recorded, r"^jit__lambda") is None


def test_recorded_breakdown_names_ops_and_labels_gaps(recorded):
    b = tr.breakdown(recorded, top=3)
    assert b["device_ops"][0][0].startswith("%closed_call.14")
    assert b["device_ops"][0][1] == pytest.approx(0.010915683, abs=1e-9)
    assert all(lbl.startswith("python3: kvnand.")
               for lbl, _ in b["idle_gaps"])


def events(*evs):
    return tr.Trace([*evs, ("/host:CPU", "main", tr.WINDOW, 0, 1000)])


def test_union_merges_overlaps_and_clips_to_the_window():
    t = events((DEV, "XLA Ops", "a", -50, 100), (DEV, "XLA Ops", "b", 20, 30),
               (DEV, "XLA Ops", "c", 40, 20), (DEV, "XLA Ops", "d", 900, 500))
    assert tr.busy_ns(t, DEV) == 60 + 100
    assert tr.idle_gaps(t, DEV) == [(60, 900)]
    assert tr.union([(5, 7), (1, 3), (3, 4)]) == [(1, 4), (5, 7)]


def test_self_time_leaves_out_nested_ops():
    t = events((DEV, "XLA Ops", "while", 0, 100),
               (DEV, "XLA Ops", "k1", 10, 30), (DEV, "XLA Ops", "k2", 50, 40),
               (DEV, "XLA Ops", "after", 200, 10))
    st = tr.self_times(t.device("XLA Ops"))
    assert st == {"while": 30, "k1": 30, "k2": 40, "after": 10}


def test_kernel_outside_the_decode_step_is_not_counted():
    t = events((DEV, "XLA Modules", "jit__decode_fn(1)", 0, 100),
               (DEV, "XLA Modules", "jit__lambda(2)", 200, 100),
               (DEV, "XLA Ops", "%k = " + PALLAS, 10, 50),
               (DEV, "XLA Ops", "%j = " + PALLAS, 210, 50),
               (DEV, "XLA Ops", "%f = fusion()", 60, 30))
    assert metric("paged_attn_roofline").kernel_s(t) == 50e-9


def test_gap_label_prefers_the_benchmark_spans():
    host = [("/host:CPU", "eng", "kvnand.collect", 0, 40),
            ("/host:CPU", "pjrt", "TransferFromDevice", 0, 100)]
    assert tr.label(host, 10, 90) == "eng: kvnand.collect"
    assert tr.label(host[1:], 10, 90) == "pjrt: TransferFromDevice"
    assert tr.label([], 10, 90) == "no host event"


def test_a_trace_needs_exactly_one_window_span():
    with pytest.raises(ValueError):
        tr.Trace([(DEV, "XLA Ops", "a", 0, 1)])
