"""From a profiler trace (``*.xplane.pb``) to the numbers the readers need.

The trace holds, per TPU, the device's operations (line "XLA Ops") and the
program executions they belong to (line "XLA Modules", named only by a
fingerprint); on the host, where the profiler recorded it, the spans the
benchmark wraps around its own calls (``bench.*``) and the launch of each
jitted program (``PjitFunction(<name>)``). All share one clock. A program
execution takes the name of the last launch before it starts. The traced
window is the ``bench.traced`` span.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import re
import statistics

_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Event:
    name: str                     # e.g. "fusion.12" or "jit_f(123)"
    start: int                    # ns
    end: int                      # ns
    device: int = -1
    text: str = ""                # the whole HLO instruction, where given

    def __post_init__(self):
        # An op may come named by its whole HLO text,
        # "%fusion.12 = f32[...] fusion(...)": keep the name, match the text.
        if not self.text:
            self.text = self.name
        if " = " in self.name:
            self.name = self.name.split(" = ", 1)[0].lstrip("%")


def union(intervals) -> list:
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


@dataclasses.dataclass
class Trace:
    ops: list                     # device operations
    modules: list                 # device program executions
    spans: list                   # host spans of the benchmark
    devices: list                 # device ids traced
    window: tuple                 # (start, end) ns

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_intervals(self, device: int) -> list:
        lo, hi = self.window
        return union(_clip([(e.start, e.end) for e in self.ops
                            if e.device == device], lo, hi))

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        tot = sum(sum(e - s for s, e in self.busy_intervals(d))
                  for d in self.devices)
        return tot / max(1, len(self.devices)) / 1e9

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def modules_named(self, pattern: str) -> list:
        """Program executions inside the window whose program's name
        matches."""
        rx = re.compile(pattern)
        lo, hi = self.window
        return [m for m in self.modules
                if rx.search(m.text) and m.start >= lo and m.end <= hi]

    def ops_matching(self, pattern: str) -> list:
        """Operations inside the window whose HLO text matches."""
        rx = re.compile(pattern)
        lo, hi = self.window
        return [e for e in self.ops if e.device in self.devices
                and e.start >= lo and e.end <= hi and rx.search(e.text)]

    def op_seconds(self, ops) -> float:
        return sum(e.end - e.start for e in ops) / 1e9

    def host_activity(self, t: int) -> str:
        """The innermost benchmark span open at ``t``."""
        best = None
        for s in self.spans:
            if s.start <= t <= s.end and s.name != "bench.traced":
                if best is None or s.end - s.start < best.end - best.start:
                    best = s
        return best.name if best else "host outside the benchmark's spans"

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time in the window, and its
        longest idle gaps on the first chip, named by what the host was
        doing in them."""
        lo, hi = self.window
        dur = collections.Counter()
        for e in self.ops:
            s, t = max(e.start, lo), min(e.end, hi)
            if t > s and e.device in self.devices:
                dur[e.name] += t - s
        busy = self.busy_intervals(self.devices[0])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        return {
            "device_ops": [[n, d / 1e9] for n, d in dur.most_common(top)],
            "idle_gaps": [[self.host_activity((s + e) // 2), (e - s) / 1e9]
                          for s, e in gaps[:top]],
        }


def _aligned(host_spans, modules) -> list:
    """Spans timed on the host's clock (seconds), placed on the trace's:
    each ``bench.step`` ends just after the program it waited for, so the
    median gap between step ends and the nearest program ends is the
    offset between the clocks."""
    steps = [t1 * 1e9 for name, _, t1 in host_spans if name == "bench.step"]
    ends = sorted(m.end for m in modules)
    if not steps or not ends:
        return []
    guess = steps[-1] - ends[-1]
    diffs = []
    for h in steps:
        i = bisect.bisect_left(ends, h - guess)
        near = min(ends[max(0, i - 1):i + 1], key=lambda e: abs(h - guess - e))
        diffs.append(h - near)
    off = statistics.median(diffs)
    return [Event(n, int(t0 * 1e9 - off), int(t1 * 1e9 - off))
            for n, t0, t1 in host_spans]


def load(path: str, devices=None, host_spans=()) -> Trace:
    """Read one ``.xplane.pb``; ``devices`` (ids) limits the chips kept.
    ``host_spans`` (name, start, end in seconds on the host's clock) stand
    in for the benchmark's spans where the profiler did not record the
    host."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, spans, launches, seen = [], [], [], [], set()
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if devices is not None and dev not in devices:
                continue
            seen.add(dev)
            for line in plane.lines:
                dest = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if dest is None:
                    continue
                for e in line.events:
                    s = int(e.start_ns)
                    dest.append(Event(e.name, s, s + int(e.duration_ns), dev))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                for e in line.events:
                    s = int(e.start_ns)
                    if e.name.startswith("bench."):
                        spans.append(Event(e.name, s, s + int(e.duration_ns)))
                    elif e.name.startswith("PjitFunction("):
                        launches.append((s, e.name[len("PjitFunction("):-1]))
    launches.sort()
    starts = [t for t, _ in launches]
    for m in modules:
        i = bisect.bisect_right(starts, m.start) - 1
        if i >= 0:
            m.text = launches[i][1]
    if not spans:
        spans = _aligned(host_spans, modules)
    win = [s for s in spans if s.name == "bench.traced"]
    if win:
        window = (win[0].start, win[0].end)
    else:
        evs = ops + spans
        window = ((min(e.start for e in evs), max(e.end for e in evs))
                  if evs else (0, 0))
    return Trace(ops=ops, modules=modules, spans=spans,
                 devices=sorted(seen), window=window)
