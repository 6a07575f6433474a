"""From a JAX profiler trace to the numbers the per-layer metrics read.

A trace is reduced to a flat list of events, (plane, line, name, start
ns, duration ns), kept only for the device planes' "XLA Modules" and
"XLA Ops" lines and for the host threads.  The traced window is the
span of the host event `WINDOW` that the benchmark writes around it;
device events are clipped to it.

- busy: the union of the device's op intervals inside the window;
- idle gaps: the holes in that union, each labelled by the host event
  that overlaps it most (the benchmark's own `kvnand.*` spans first);
- self time of an op: its duration less that of the ops nested in it
  (an XLA `while` holds its body's ops on the same line).
"""
from __future__ import annotations

import glob
import json
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.trace_window"
DEVICE_LINES = ("XLA Modules", "XLA Ops")
_DEVICE = re.compile(r"^/device:(TPU|GPU):\d+$")


class Trace:
    """Events of one traced window: tuples (plane, line, name, t0, dur),
    times in ns on the trace's clock."""

    def __init__(self, events: List[Tuple[str, str, str, int, int]]):
        self.events = [tuple(e) for e in events]
        marks = [e for e in self.events if e[2] == WINDOW]
        if len(marks) != 1:
            raise ValueError(f"trace holds {len(marks)} {WINDOW!r} spans")
        self.t0, self.t1 = marks[0][3], marks[0][3] + marks[0][4]

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    def device(self, line: str) -> List[Tuple[str, str, str, int, int]]:
        """Device events of one line, clipped to the window."""
        out = []
        for e in self.events:
            if _DEVICE.match(e[0]) and e[1] == line:
                a, b = max(e[3], self.t0), min(e[3] + e[4], self.t1)
                if b > a:
                    out.append((e[0], e[1], e[2], a, b - a))
        return out

    def launches(self, line: str):
        """Device events of one line that start inside the window,
        whole (for per-launch times)."""
        return [e for e in self.events if _DEVICE.match(e[0])
                and e[1] == line and self.t0 <= e[3] < self.t1]

    def host(self):
        return [e for e in self.events
                if not _DEVICE.match(e[0]) and e[2] != WINDOW]

    def chips(self) -> List[str]:
        return sorted({e[0] for e in self.events if _DEVICE.match(e[0])})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        return cls(json.loads(text)["events"])


def load(trace_dir: str) -> Trace:
    """Read the newest `.xplane.pb` under `trace_dir`."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    events = []
    for plane in pd.planes:
        dev = bool(_DEVICE.match(plane.name))
        if not dev and plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if dev and line.name not in DEVICE_LINES:
                continue
            for ev in line.events:
                events.append((plane.name, line.name, ev.name,
                               int(ev.start_ns), int(ev.duration_ns)))
    return Trace(events)


def union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merged, sorted (start, end) intervals."""
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(trace: Trace, chip: str) -> int:
    """Nanoseconds of the window in which some op ran on `chip`."""
    iv = [(e[3], e[3] + e[4]) for e in trace.device("XLA Ops")
          if e[0] == chip]
    return sum(b - a for a, b in union(iv))


def busy_s(trace: Trace) -> float:
    """Busy seconds, averaged over the chips in the trace."""
    chips = trace.chips()
    if not chips:
        return 0.0
    return sum(busy_ns(trace, c) for c in chips) / len(chips) / 1e9


def idle_gaps(trace: Trace, chip: str) -> List[Tuple[int, int]]:
    """(start, end) of every stretch of the window with no op on chip."""
    iv = union([(e[3], e[3] + e[4]) for e in trace.device("XLA Ops")
                if e[0] == chip])
    gaps, t = [], trace.t0
    for a, b in iv:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if trace.t1 > t:
        gaps.append((t, trace.t1))
    return gaps


def label(host, a: int, b: int) -> str:
    """The host event overlapping [a, b) most, as 'thread: name'; the
    benchmark's own spans (`kvnand.*`) win over runtime events."""
    best: Dict[bool, Tuple[int, str]] = {}
    for plane, line, name, t0, dur in host:
        ov = min(b, t0 + dur) - max(a, t0)
        if ov <= 0:
            continue
        own = name.startswith("kvnand.")
        if ov > best.get(own, (0, ""))[0]:
            best[own] = (ov, f"{line}: {name}")
    for own in (True, False):
        if own in best:
            return best[own][1]
    return "no host event"


def self_times(events) -> Dict[str, int]:
    """Total self time (ns) by op name over events of one line."""
    out: Dict[str, int] = defaultdict(int)
    by_chip = defaultdict(list)
    for e in events:
        by_chip[(e[0], e[1])].append(e)
    for evs in by_chip.values():
        evs = sorted(evs, key=lambda e: (e[3], -e[4]))
        stack: List[List] = []          # [end, name, self]
        for _, _, name, t0, dur in evs:
            while stack and stack[-1][0] <= t0:
                _, n, s = stack.pop()
                out[n] += s
            if stack:
                stack[-1][2] -= min(dur, stack[-1][0] - t0)
            stack.append([t0 + dur, name, dur])
        for _, n, s in stack:
            out[n] += s
    return out


def short(name: str, n: int = 96) -> str:
    """An op's HLO text cut to its name, result type and opcode."""
    return re.sub(r"\s+", " ", name)[:n]


def breakdown(trace: Trace, top: int = 10) -> Dict[str, list]:
    """The device ops with the most self time and the longest idle gaps
    (first chip), in seconds."""
    st = self_times(trace.device("XLA Ops"))
    ops = sorted(st.items(), key=lambda kv: -kv[1])[:top]
    chips = trace.chips()
    gaps = idle_gaps(trace, chips[0]) if chips else []
    host = trace.host()
    longest = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    return {"device_ops": [[short(n), s / 1e9] for n, s in ops],
            "idle_gaps": [[label(host, a, b), (b - a) / 1e9]
                          for a, b in longest]}


def module_time(trace: Trace, pattern: str) -> Optional[Tuple[float, int]]:
    """(device seconds, launches) of the XLA modules whose name matches
    `pattern` (a regex searched in the module name); None if none ran."""
    rx = re.compile(pattern)
    ev = [e for e in trace.launches("XLA Modules") if rx.search(e[2])]
    if not ev:
        return None
    return sum(e[4] for e in ev) / 1e9, len(ev)
