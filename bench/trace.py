"""Reduction of a ``jax.profiler`` trace to the intervals the metrics read.

A trace holds device planes (``/device:TPU:<n>``), whose ``XLA Ops`` line has
one event per operation that ran on the chip (a ``while`` op spans the ops of
its body), and the host plane
(``/host:CPU``), where the benchmark's own spans (``TraceAnnotation`` with a
name starting ``bench/``) sit on the same clock. Everything here works on
plain ``(name, start_s, end_s, device)`` tuples, so a recorded trace can check
it.

A cell on several chips keeps the planes of its first ``chips`` devices. Its
busy time and idle gaps are means over those devices (``Trace.busy``,
``idle_by_activity``), an op's time in ``top_ops`` is summed over them, and
``Trace.timeline`` reads one device. On one chip each reads what it read
before devices were counted.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict

import numpy as np

SPAN_PREFIX = "bench/"
DEVICE_PLANE = "/device:TPU:"
DEVICE_OPS_LINE = "XLA Ops"


class Timeline:
    """Disjoint sorted intervals with prefix sums: the covered seconds of any
    [a, b] in logarithmic time."""

    def __init__(self, intervals):
        merged = []
        for start, end in sorted(intervals):
            if merged and start <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], end)
            else:
                merged.append([start, end])
        self.starts = [m[0] for m in merged]
        self.ends = [m[1] for m in merged]
        self.cum = np.concatenate([[0.0], np.cumsum([e - s for s, e in merged])])

    def _covered_to(self, t: float) -> float:
        i = bisect.bisect_right(self.starts, t)  # intervals starting at or before t
        if i == 0:
            return 0.0
        return float(self.cum[i - 1]) + min(self.ends[i - 1], t) - self.starts[i - 1]

    def covered(self, a: float, b: float) -> float:
        return max(self._covered_to(b) - self._covered_to(a), 0.0)

    def gaps(self, a: float, b: float) -> list:
        """The uncovered (start, end) pieces of [a, b]."""
        out, cursor = [], a
        i = max(bisect.bisect_right(self.starts, a) - 1, 0)
        while i < len(self.starts) and self.starts[i] < b:
            if self.ends[i] > cursor:
                if self.starts[i] > cursor:
                    out.append((cursor, self.starts[i]))
                cursor = self.ends[i]
            i += 1
        if cursor < b:
            out.append((cursor, b))
        return out


class MeanTimeline:
    """Several devices' ``Timeline``s read as one: the mean covered seconds."""

    def __init__(self, devices: list):
        self.devices = devices

    def covered(self, a: float, b: float) -> float:
        return sum(t.covered(a, b) for t in self.devices) / len(self.devices)


@dataclasses.dataclass
class Trace:
    """Device operations of the cell's devices and benchmark spans, in seconds."""

    ops: list  # [(name, start_s, end_s, device)], sorted by start; device 0 where left out
    spans: list  # [(name, start_s, end_s)] with the prefix stripped, sorted by start
    devices: int = 1  # the cell's devices: ops carry a device in range(devices)

    def __post_init__(self):
        self.ops = [o if len(o) == 4 else (*o, 0) for o in self.ops]
        self.busy_per_device = [Timeline((o[1], o[2]) for o in self.ops if o[3] == d)
                                for d in range(self.devices)]
        self.busy = MeanTimeline(self.busy_per_device)

    def spans_named(self, name: str) -> list:
        return [s for s in self.spans if s[0] == name]

    def window(self) -> tuple:
        """(start, end) of the traced part of the window (span ``traced``)."""
        (w,) = self.spans_named("traced")
        return w[1], w[2]

    def timeline(self, match, device: int = 0) -> Timeline:
        """Device time of the operations whose name ``match`` accepts, on one
        of the cell's devices; a reader of a cell on several chips sums it over
        ``range(self.devices)``."""
        return Timeline((o[1], o[2]) for o in self.ops if o[3] == device and match(o[0]))


def op_name(event_name: str) -> str:
    """``%fusion.12 = s32[4097] fusion(...)`` -> ``fusion.12``: the trace names
    a TPU op by its whole HLO line, whose operands name other ops."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _ordinal(plane_name: str) -> int:
    """``/device:TPU:3`` -> 3; ``-1`` for a plane that is not one device's."""
    tail = plane_name[len(DEVICE_PLANE):]
    return int(tail) if plane_name.startswith(DEVICE_PLANE) and tail.isdigit() else -1


def load(path: str, chips: int = 1) -> Trace:
    """Read an ``.xplane.pb`` file, or the newest one under a trace directory,
    keeping the ops of the first ``chips`` device planes."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        files = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = files[-1]
    data = ProfileData.from_file(path)
    ops, spans = [], []
    kept = sorted((o for o in map(_ordinal, (p.name for p in data.planes)) if o >= 0))[:chips]
    device_of = {f"{DEVICE_PLANE}{o}": d for d, o in enumerate(kept)}
    for plane in data.planes:
        if plane.name in device_of:
            d = device_of[plane.name]
            for line in plane.lines:
                if line.name == DEVICE_OPS_LINE:
                    ops += [(op_name(e.name), e.start_ns * 1e-9, e.end_ns * 1e-9, d) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [
                    (e.name[len(SPAN_PREFIX):], e.start_ns * 1e-9, e.end_ns * 1e-9)
                    for e in line.events
                    if e.name.startswith(SPAN_PREFIX)
                ]
    return Trace(ops=sorted(ops, key=lambda o: o[1]), spans=sorted(spans, key=lambda s: s[1]),
                 devices=max(len(kept), 1))


def top_ops(trace: Trace, start: float, end: float, n: int = 10) -> list:
    """The ``n`` operations that took most device time in [start, end], by
    self time summed over the cell's devices: a ``while`` op spans the ops of
    its body, which count as theirs."""
    total = defaultdict(float)
    for device in range(trace.devices):
        stack = []  # open ops: [name, end, self seconds]
        for name, a, b, _ in sorted((o for o in trace.ops if o[3] == device), key=lambda o: (o[1], -o[2])):
            a, b = max(a, start), min(b, end)
            while stack and stack[-1][1] <= a:
                done = stack.pop()
                total[done[0]] += done[2]
            if b <= a:
                continue
            if stack:
                stack[-1][2] -= b - a
            stack.append([name, b, b - a])
        for name, _, self_s in stack:
            total[name] += self_s
    return sorted(([k, v] for k, v in total.items()), key=lambda kv: -kv[1])[:n]


def idle_by_activity(trace: Trace, start: float, end: float, n: int = 10) -> list:
    """Idle device time in [start, end], summed by the innermost benchmark
    span open at the middle of each gap (``host`` where none was open); the
    mean over the cell's devices."""
    spans = [s for s in trace.spans if s[0] not in ("window", "traced")]  # sorted by start
    total = defaultdict(float)
    for busy in trace.busy_per_device:
        active, nxt = [], 0
        for a, b in busy.gaps(start, end):  # sorted, so mids rise
            mid = 0.5 * (a + b)
            while nxt < len(spans) and spans[nxt][1] <= mid:
                active.append(spans[nxt])
                nxt += 1
            active = [s for s in active if s[2] >= mid]
            name = min(active, key=lambda s: s[2] - s[1])[0] if active else "host"
            total[name] += b - a
    devices = len(trace.busy_per_device)
    return sorted(([k, v / devices] for k, v in total.items()), key=lambda kv: -kv[1])[:n]
