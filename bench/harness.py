"""One run of one benchmark cell, driven by the files `BENCHMARK.json`
names.

A cell (`workloads` entry) names a configuration (`bench/configs/
<config>.json`: the model's published sizes and the program's config it
maps to) and a traffic mix (`bench/traffic/<mix>.json`); the cell's own
file (`bench/cells/<cell>.json`) holds the server it is offered to and
the limits of its correctness check.  The configuration file names its
plain reference module (`"reference"`, a path under the benchmark),
which supplies the sizes, the seeded weights, the program fields it
stands for and the scoring.  Metrics are readers in
`bench/metrics/<metric>.py`, each `read(run) -> float | None`.

The run: weights from the seed in one jitted call; the HTTP front door
(`BackgroundServer` -> `AsyncKVNANDServer`, overlapped engine loop) in
this process; the benchmark's asyncio client offers the mix; set-up
ends when every program the window runs has run once; the window lasts
`--seconds`; afterwards the server is shut down, the device's peak
memory read, the program's arrays freed, and a sample of the finished
requests is scored by the configuration's float32 reference.
"""
from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402

from bench import client, loadgen, measure, trace_reduce  # noqa: E402
from bench.work import DTYPE_BYTES  # noqa: E402

TRACE_SECONDS = 10.0        # longest traced stretch of a window
STALL_S = 30.0              # in flight this long with no token: failed
TAIL_TIMEOUT_S = 60.0       # wait past the window for due first tokens


class NoChip(SystemExit):
    """No accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Spec:
    """Everything one cell is, read from its files."""
    name: str
    chips: int
    config: Dict
    mix: Dict
    cell: Dict
    metrics: List[Dict]         # end-to-end ones (trace 0)
    per_layer: List[Dict]       # per-layer ones (trace 1)
    peaks: Dict

    @property
    def ref(self) -> ModuleType:
        return reference_of(self.config)


def applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_spec(cell: str) -> Spec:
    bench = load_json(ROOT, "BENCHMARK.json")
    wl = {w["name"]: w for w in bench["workloads"]}
    if cell not in wl:
        raise SystemExit(f"unknown workload {cell!r}; BENCHMARK.json has "
                         f"{sorted(wl)}")
    w = wl[cell]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    return Spec(
        name=cell, chips=w["chips"],
        config=load_json(ROOT, cfg["file"]),
        mix=load_json(HERE, "traffic", f"{w['traffic']}.json"),
        cell=load_json(HERE, "cells", f"{cell}.json"),
        metrics=[m for m in bench["end_to_end"] if applies(m, cell)],
        per_layer=[m for m in bench["per_layer"] if applies(m, cell)],
        peaks=load_json(HERE, "peaks.json"))


_MODULES: Dict[str, ModuleType] = {}


def load_module(path: str) -> ModuleType:
    """The Python file at `path` (under the checkout's root), loaded once
    by its path."""
    path = os.path.normpath(os.path.join(ROOT, path))
    if path not in _MODULES:
        rel = os.path.relpath(path, ROOT)[:-len(".py")]
        spec = importlib.util.spec_from_file_location(
            "bench_" + "".join(c if c.isalnum() else "_" for c in rel), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def reader(name: str) -> Callable:
    return load_module(os.path.join("bench", "metrics", f"{name}.py")).read


def reference_of(conf: Dict) -> ModuleType:
    """The plain reference module a configuration file names."""
    return load_module(conf["reference"])


def check_device(chips: int):
    """The chips the cell asks for, or NoChip (no result line)."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"bench: needs a TPU; JAX found {devs[0].platform} "
                     f"({devs[0].device_kind}) - no result")
    if len(devs) < chips:
        raise NoChip(f"bench: needs {chips} TPU chips, found {len(devs)} "
                     "- no result")
    return devs[:chips]


def model_config(conf: Dict, dm):
    """The program's ModelConfig for a configuration file, checked
    field by field against what its reference module says it must be."""
    from repro.configs import get_config
    prog = conf["program"]
    mc = dataclasses.replace(get_config(prog["arch"]),
                             **prog.get("replace", {}))
    want = reference_of(conf).program_fields(dm)
    bad = {k: (getattr(mc, k), v) for k, v in want.items()
           if getattr(mc, k) != v}
    if bad:
        raise SystemExit(f"program config {mc.name} differs from "
                         f"{conf['name']}: {bad}")
    return mc


class CompileCounter:
    """Backend compiles and their seconds, from jax.monitoring."""

    def __init__(self):
        self.n, self.s = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.s += secs


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""
    dm: Any                         # the reference module's sizes
    setup_s: float
    records: List[client.Record]
    w0: float                       # measured window, perf_counter s
    w1: float
    stats0: Dict
    stats1: Dict
    batch_slots: int
    kv_bytes: int
    peak: Dict                      # peaks.json row of this chip
    trace: Optional[trace_reduce.Trace] = None
    tw0: Optional[float] = None     # traced stretch, perf_counter s
    tw1: Optional[float] = None


def _wrap_spans(inner) -> None:
    """Host spans around the engine loop's calls into the server (only
    in a traced run): they label the device's idle gaps."""
    for name in ("dispatch", "collect", "submit", "output", "release"):
        fn = getattr(inner, name)

        def wrapped(*a, _fn=fn, _n=f"kvnand.{name}", **k):
            with jax.profiler.TraceAnnotation(_n):
                return _fn(*a, **k)
        setattr(inner, name, wrapped)


def _traced(trace_dir: str, seconds: float, marks: Dict) -> None:
    """Profile `seconds` of the window from a side thread, marking the
    stretch with the WINDOW span."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        marks["tw0"] = time.perf_counter()
        time.sleep(seconds)
        marks["tw1"] = time.perf_counter()
    jax.profiler.stop_trace()


async def _first_tokens(recs, timeout: float) -> None:
    t = time.perf_counter()
    while not all(r.times or r.ended for r in recs):
        if time.perf_counter() - t > timeout:
            raise RuntimeError("set-up requests got no first token in "
                               f"{timeout:.0f} s")
        await asyncio.sleep(0.005)


async def drive(addr, spec: Spec, reqs, seconds: float, t_proc0: float,
                inner, trace_dir: Optional[str], seed: int, vocab: int,
                comp: "CompileCounter") -> Dict:
    """Offer the mix, hold the window open for `seconds`, and return the
    records and the window's marks."""
    host, port = addr
    mix = spec.mix
    recs: List[client.Record] = []
    tasks: List[asyncio.Task] = []
    marks: Dict = {}
    stop = asyncio.Event()

    def send(r: loadgen.Req, due: float) -> asyncio.Task:
        rec = client.Record(rid=r.rid, n_prompt=len(r.prompt),
                            max_tokens=r.max_tokens, due=due)
        recs.append(rec)
        task = asyncio.ensure_future(
            client.complete(host, port, r.prompt, r.max_tokens, rec))
        tasks.append(task)
        return task

    if mix["loop"] == "closed":
        by_client: Dict[int, List[loadgen.Req]] = {}
        for r in reqs:
            by_client.setdefault(r.client, []).append(r)

        async def one_client(queue):
            for r in queue:
                if stop.is_set():
                    return
                await send(r, time.perf_counter())

        ctasks = [asyncio.ensure_future(one_client(q))
                  for q in by_client.values()]
        await asyncio.sleep(0)
        await _first_tokens(list(recs), 900.0)
    else:
        for w in loadgen.warmup_requests(mix, seed, vocab):
            rec = await send(w, time.perf_counter())
            if not rec.ok:
                raise RuntimeError(f"warm-up request failed: {rec}")
        recs.clear()
        tasks.clear()
        ta = time.perf_counter()

        async def arrivals():
            for r in reqs:
                delay = ta + r.due - time.perf_counter()
                if delay > 0:
                    await asyncio.sleep(delay)
                if stop.is_set():
                    return
                send(r, ta + r.due)

        ctasks = [asyncio.ensure_future(arrivals())]
        await asyncio.sleep(ta + mix["preroll_s"] - time.perf_counter())

    # -- the window ----------------------------------------------------
    w0 = time.perf_counter()
    c0 = (comp.n, comp.s)
    stats0 = dict(inner.stats)
    tthread = None
    if trace_dir is not None:
        tlen = min(TRACE_SECONDS, seconds / 2)
        lead = (seconds - tlen) / 2

        async def start_trace():
            await asyncio.sleep(lead)
            th = threading.Thread(target=_traced,
                                  args=(trace_dir, tlen, marks))
            th.start()
            return th
        tthread = asyncio.ensure_future(start_trace())
    await asyncio.sleep(w0 + seconds - time.perf_counter())
    w1 = time.perf_counter()
    stats1 = dict(inner.stats)
    c1 = (comp.n, comp.s)
    if mix["loop"] == "open":
        # every request due in the window gets its first token (or fails)
        due = [r for r in recs if w0 <= r.due < w1]
        t = time.perf_counter()
        while (not all(r.times or r.ended for r in due)
               and time.perf_counter() - t < TAIL_TIMEOUT_S):
            await asyncio.sleep(0.01)
    stop.set()
    if tthread is not None:
        th = await tthread
        await asyncio.get_running_loop().run_in_executor(None, th.join)
    for t in ctasks + tasks:
        t.cancel()
    await asyncio.gather(*ctasks, *tasks, return_exceptions=True)
    return dict(records=recs, w0=w0, w1=w1, stats0=stats0, stats1=stats1,
                compiles=(c1[0] - c0[0], c1[1] - c0[1]),
                setup_s=w0 - t_proc0, **marks)


def _late(recs) -> str:
    late = [r.sent - r.due for r in recs if r.sent is not None]
    if not late:
        return "no requests sent"
    q = np.percentile(late, [50, 99])
    return (f"{len(late)} sends, lateness p50 {q[0] * 1e3:.3f} ms, "
            f"p99 {q[1] * 1e3:.3f} ms, max {max(late) * 1e3:.3f} ms")


def outcome(recs, w0: float, w1: float):
    """(attempted, failed): requests in flight at some point of the
    window, and those of them that failed: an error answer, a lost
    stream, a finish other than `length`, or no token for STALL_S."""
    att = [r for r in recs if (r.sent or r.due) < w1
           and (r.ended is None or r.ended >= w0)]
    failed = 0
    for r in att:
        last = r.times[-1] if r.times else (r.sent or r.due)
        stalled = r.cancelled and not r.ok and w1 - last > STALL_S
        failed += int(r.failed or stalled)
    return len(att), failed


def pick_sample(recs, w0: float, target: int, seed: int):
    """Finished requests to score: the one with the most served tokens,
    then others in an order drawn from the seed, until `target` served
    tokens are in."""
    done = [r for r in recs if r.ok and r.ended is not None
            and r.ended >= w0]
    if not done:
        return []
    done.sort(key=lambda r: (-len(r.tokens), r.rid))
    rest = done[1:]
    loadgen.rng(seed, 3).shuffle(rest)
    out, n = [done[0]], len(done[0].tokens)
    for r in rest:
        if n >= target:
            break
        out.append(r)
        n += len(r.tokens)
    return out


def check(spec: Spec, dm, seed: int, sample, prompts: Dict[int, list],
          control: bool = False) -> Dict:
    """Score the sample against the float32 reference; the widest gap
    and logprob error over all its served tokens."""
    if not sample:
        return {"tokens": 0}
    block = 256
    pad_to = -(-spec.cell["server"]["max_context"] // block) * block
    params = spec.ref.make_weights(dm, seed)
    parts = [spec.ref.score(params, dm, prompts[r.rid], r.tokens,
                             r.logprobs, pad_to=pad_to, block=block,
                             control=control)
             for r in sample]
    del params
    out = {"tokens": int(sum(len(r.tokens) for r in sample))}
    for k in parts[0]:
        out[k] = float(max(np.max(p[k]) for p in parts))
    return out


def numbers(readings: Dict, limits: Dict) -> Dict:
    return {k: {"value": readings.get(k), "limit": v}
            for k, v in limits.items()}


def is_correct(readings: Dict, limits: Dict) -> bool:
    if not readings.get("tokens"):
        return False                    # nothing finished: nothing shown
    return all(readings.get(k) is not None and readings[k] <= v
               for k, v in limits.items())


def control_readings(readings: Dict) -> Dict:
    """The control's readings (`ctrl_<number>`) under the numbers' own
    names, so that `is_correct` judges them as it judges the program."""
    return {"tokens": readings.get("tokens", 0),
            **{k[len("ctrl_"):]: v for k, v in readings.items()
               if k.startswith("ctrl_")}}


@contextlib.contextmanager
def serving(spec: Spec, mc, params, seed: int):
    from repro.serving.api import ServerConfig
    from repro.serving.async_server import (AsyncServerConfig,
                                            BackgroundServer)
    sv = spec.cell["server"]
    sc = ServerConfig(arch=mc.name, batch_slots=sv["batch_slots"],
                      max_context=sv["max_context"],
                      prefill_chunk_tokens=sv["prefill_chunk_tokens"],
                      seed=int(seed) % 2**31)
    ac = AsyncServerConfig(max_queue=sv["max_queue"], overlap=True)
    bg = BackgroundServer(sc, ac, cfg=mc, params=params)
    with bg:
        yield bg
    bg.server = bg._params = None


def run_cell(spec: Spec, seed: int, seconds: float, trace: bool, *,
             t_proc0: float, require_tpu: bool = True, control: bool = False,
             before_serve: Optional[Callable] = None,
             score: bool = True) -> Dict:
    """One run; returns the result line's dict (without printing), plus
    `readings` (every number the check read) and `_run` (the Run)."""
    # libtpu logs under /tmp/tpu_logs unless told otherwise: keep them in
    # this run's own temporary directory (read when the backend starts)
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    devs = check_device(spec.chips) if require_tpu else jax.devices()
    peak = spec.peaks["devices"].get(devs[0].device_kind)
    if require_tpu:
        if peak is None:
            raise SystemExit(f"bench: {devs[0].device_kind!r} is not in "
                             "bench/peaks.json - no result")
        from repro.launch.compile_cache import enable_compile_cache
        log(f"compile cache: {enable_compile_cache()}")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    comp = CompileCounter()
    ref = spec.ref
    dm = ref.dims(spec.config)
    mc = model_config(spec.config, dm)
    t = time.perf_counter()
    params = ref.make_weights(dm, seed)
    jax.block_until_ready(params)
    log(f"weights: {ref.n_params(dm):,} parameters (published count)"
        f", f32 from seed {seed} in {time.perf_counter() - t:.2f} s")
    reqs = loadgen.make_requests(spec.mix, seed, seconds, dm.V)
    prompts = {r.rid: r.prompt for r in reqs}
    if before_serve is not None:
        before_serve()
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="trace-")
    with serving(spec, mc, params, seed) as bg:
        inner = bg.server._server
        if trace:
            _wrap_spans(inner)
        del params
        got = asyncio.run(drive(bg.address, spec, reqs, seconds, t_proc0,
                                inner, tdir, seed, dm.V, comp))
        del inner
    gc.collect()
    recs, w0, w1 = got["records"], got["w0"], got["w1"]
    dev = devs[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"set-up {got['setup_s']:.3f} s; window {w1 - w0:.3f} s; "
        f"compiles in window: {got['compiles'][0]} "
        f"({got['compiles'][1]:.3f} s)")
    log(f"load generator: {_late(recs)}")
    tt = measure.ttfts(recs, w0, w1)
    if tt:
        q = np.percentile(tt, [50, 75, 90, 95]) * 1e3
        log(f"ttft of {len(tt)} requests due in the window: p50 {q[0]:.1f} "
            f"p75 {q[1]:.1f} p90 {q[2]:.1f} p95 {q[3]:.1f} mean "
            f"{np.mean(tt) * 1e3:.1f} ms")
    attempted, failed = outcome(recs, w0, w1)
    log(f"requests: {len(recs)} sent, {attempted} attempted in the window"
        f", {failed} failed; live arrays after shutdown: "
        f"{sum(a.nbytes for a in jax.live_arrays()) / 1e9:.3f} GB")

    run = Run(dm=dm, setup_s=got["setup_s"],
              records=recs, w0=w0, w1=w1, stats0=got["stats0"],
              stats1=got["stats1"], batch_slots=spec.cell["server"]
              ["batch_slots"],
              kv_bytes=DTYPE_BYTES[spec.config["precision"]["kv_cache"]],
              peak=peak or {})
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": spec.chips, "memory_peak_bytes": mem}
    out: Dict = {}
    if trace:
        run.trace = trace_reduce.load(tdir)
        run.tw0, run.tw1 = got["tw0"], got["tw1"]
        shutil.rmtree(tdir, ignore_errors=True)
        device["busy_s"] = trace_reduce.busy_s(run.trace)
        device["window_s"] = run.trace.window_s
        out["breakdown"] = trace_reduce.breakdown(run.trace)
    wanted = spec.per_layer if trace else spec.metrics
    metrics = {}
    for m in wanted:
        v = reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    t = time.perf_counter()
    chk = spec.cell["check"]
    sample = pick_sample(recs, w0, chk["tokens"], seed) if score else []
    readings = check(spec, dm, seed, sample, prompts, control=control)
    log(f"check: {len(sample)} requests, {readings['tokens']} served "
        f"tokens scored in {time.perf_counter() - t:.1f} s")
    correct = is_correct(readings, chk["limits"])
    return {"correct": correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device, **out,
            "readings": readings, "_run": run,
            "check": numbers(readings, chk["limits"])}


def emit(result: Dict) -> None:
    """Print the compared numbers last on stderr and the result line
    last on stdout (without the extra readings)."""
    for k, v in result["check"].items():
        log(f"check {k} {v['value']} limit {v['limit']}")
    line = {k: v for k, v in result.items()
            if k not in ("readings", "_run")}
    print(json.dumps(line), flush=True)


def main(argv=None, t_proc0: Optional[float] = None) -> int:
    import argparse
    t_proc0 = time.perf_counter() if t_proc0 is None else t_proc0
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec = load_spec(args.workload)
    try:
        result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                          t_proc0=t_proc0)
    except NoChip as e:
        log(str(e))
        return 2
    emit(result)
    return 0
