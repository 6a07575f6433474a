"""The readers of the program's own spans and counters: `host_step_ms`
(host-work spans per decode enqueue) and `decode_live_page_share` (live
over walked decode pages), on hand-made events, on a run shaped like one
of a program without them (None, never 0), and on two recorded cuts of
traced `qwen05b-chat` runs: `trace_chat_spans_cut.json` (every program
span) and `trace_chat_gap_cut.json` (an idle gap after a first-token
wait)."""
from __future__ import annotations

import importlib.util
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from bench import trace_reduce as tr  # noqa: E402

ENG = ("/host:CPU", "kvnand-engine")
PROGRAM = ("kvnand.admit", "kvnand.prefill_enqueue",
           "kvnand.first_token_wait", "kvnand.decode_enqueue",
           "kvnand.fetch", "kvnand.emit", "kvnand.route", "kvnand.commands")


def metric(name):
    spec = importlib.util.spec_from_file_location(
        f"m_{name}", os.path.join(ROOT, "bench", "metrics", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def recorded(name):
    with open(os.path.join(HERE, name)) as f:
        return tr.Trace.from_json(f.read())


@pytest.fixture(scope="module")
def chat_cut():
    return recorded("trace_chat_spans_cut.json")


@pytest.fixture(scope="module")
def gap_cut():
    return recorded("trace_chat_gap_cut.json")


def run_of(*host, stats0=None, stats1=None):
    """A Run as a reader sees it: a 1000 ns traced stretch at [0, 1000)
    holding `host` events (name, start, duration) on the engine thread."""
    trace = tr.Trace([*(ENG + e for e in host),
                      ("/host:CPU", "main", tr.WINDOW, 0, 1000)])
    return SimpleNamespace(trace=trace, stats0=stats0 or {},
                           stats1=stats1 or {})


def test_host_step_ms_sums_host_work_per_decode_enqueue():
    run = run_of(("kvnand.dispatch", 0, 300),        # the harness's own
                 ("kvnand.admit", 0, 10),
                 ("kvnand.prefill_enqueue", 10, 40),
                 ("kvnand.first_token_wait", 50, 200),   # a device wait
                 ("kvnand.decode_enqueue", 250, 50),
                 ("kvnand.fetch", 300, 400),             # a device wait
                 ("kvnand.emit", 700, 20),
                 ("kvnand.route", 720, 5),
                 ("kvnand.commands", 725, 5),
                 ("kvnand.decode_enqueue", 800, 30),
                 ("kvnand.emit", 990, 100),      # starts in, ends after
                 ("kvnand.decode_enqueue", -40, 30),     # before: out
                 ("kvnand.admit", 1000, 10))             # after: out
    got = metric("host_step_ms").read(run)
    assert got == pytest.approx((10 + 40 + 50 + 20 + 5 + 5 + 30 + 100)
                                / 2 / 1e6)


@pytest.mark.parametrize("host", [
    (),                                               # nothing at all
    (("kvnand.dispatch", 0, 300), ("kvnand.collect", 300, 600)),
    (("kvnand.admit", 0, 10), ("kvnand.emit", 10, 10)),  # no enqueue
])
def test_host_step_ms_is_none_without_decode_enqueues(host):
    assert metric("host_step_ms").read(run_of(*host)) is None


def test_host_step_ms_is_none_untraced():
    assert metric("host_step_ms").read(
        SimpleNamespace(trace=None)) is None


def test_live_page_share_differences_the_counters_over_the_window():
    run = run_of(stats0={"decode_pages_walked": 2048,
                         "decode_pages_live": 1000},
                 stats1={"decode_pages_walked": 2048 * 11,
                         "decode_pages_live": 1000 + 14336})
    assert metric("decode_live_page_share").read(run) == \
        pytest.approx(100.0 * 14336 / 20480)


@pytest.mark.parametrize("stats0,stats1", [
    ({"steps": 1}, {"steps": 9}),                 # a program without them
    ({"decode_pages_walked": 64, "decode_pages_live": 9},
     {"decode_pages_walked": 64, "decode_pages_live": 9}),   # no decode
    ({}, {"decode_pages_walked": 64, "decode_pages_live": 9}),
])
def test_live_page_share_is_none_without_counted_walks(stats0, stats1):
    run = run_of(stats0=stats0, stats1=stats1)
    assert metric("decode_live_page_share").read(run) is None


def test_recorded_chat_cut_holds_the_program_spans_as_leaves(chat_cut):
    spans = sorted((e for e in chat_cut.host() if e[2] in PROGRAM),
                   key=lambda e: e[3])
    assert {e[2] for e in spans} == set(PROGRAM)
    assert len({e[1] for e in spans}) == 1        # all on the engine thread
    assert all(a[3] + a[4] <= b[3] for a, b in zip(spans, spans[1:]))


def test_recorded_host_step_ms(chat_cut):
    """One decode enqueue starts in the 50 ms cut; the host work that
    starts there: the enqueue, emit, route, commands, admit and a
    512-token chunk's enqueue."""
    run = SimpleNamespace(trace=chat_cut)
    assert metric("host_step_ms").read(run) == pytest.approx(7.70508,
                                                             abs=1e-9)


def test_recorded_gap_ends_in_the_decode_enqueue(chat_cut):
    """The cut's one long idle gap (a finished prompt's first token) ends
    when the decode step that the host enqueues after it starts."""
    gaps = [(a, b) for a, b in tr.idle_gaps(chat_cut, chat_cut.chips()[0])
            if b - a > 1_000_000]
    assert len(gaps) == 1
    (a, b), = gaps
    assert (b - a) / 1e6 == pytest.approx(5.351393, abs=1e-6)
    enq, = [e for e in chat_cut.host() if e[2] == "kvnand.decode_enqueue"]
    assert enq[3] < b < enq[3] + enq[4]
    assert tr.module_time(chat_cut, r"^jit__decode_fn\b") == (
        pytest.approx(0.12618336, abs=1e-9), 1)


def test_recorded_gap_spans_the_first_token_wait_and_the_enqueue(gap_cut):
    """On the host's clock the device idles from inside the first-token
    wait (the host still fetching the token and its logprob) to the
    launch of the decode step enqueued after it.  The device clock reads
    early: a decode launched onto an idle device starts, on it, before
    the host's launch."""
    (a, b), = [(a, b) for a, b in tr.idle_gaps(gap_cut, gap_cut.chips()[0])
               if b - a > 1_000_000]
    mod, = [e for e in gap_cut.launches("XLA Modules")
            if e[2].startswith("jit__decode_fn")]
    launch = min(e[3] for e in gap_cut.host()
                 if e[2] == "PjitFunction(_decode_fn)")
    assert 0 <= b - mod[3] < 10_000          # the decode's first op
    skew = mod[3] - launch
    assert -2_000_000 < skew < 0
    a, b = a - skew, b - skew
    wait, = [e for e in gap_cut.host() if e[2] == "kvnand.first_token_wait"]
    enq = min((e for e in gap_cut.host()
               if e[2] == "kvnand.decode_enqueue"), key=lambda e: e[3])
    assert wait[3] < a < wait[3] + wait[4] <= enq[3] < b < enq[3] + enq[4]
    assert metric("host_step_ms").read(SimpleNamespace(trace=gap_cut)) == \
        pytest.approx(5.0445545, abs=1e-9)
