"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

Device planes are those named ``/device:<KIND>:<i>`` (``/device:TPU:0``
on a v5e). On each, the ``XLA Modules`` line holds one event per
executed program: their union is when the device was busy. The ``XLA
Ops`` line holds the operations inside those programs, nested (a
``while`` spans the ops of its body). Host spans come from the
benchmark's own ``jax.profiler.TraceAnnotation`` names (``bench:*``) and
the Python tracer's frames on the host plane's ``python3`` line. Device
and host events share one clock.

The traced window is the host span named ``window_span``
(``bench:traced``), which the harness opens around the traced units.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
MODULES, OPS = "XLA Modules", "XLA Ops"

Interval = Tuple[int, int]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip_events(events, lo: int, hi: int):
    """Events cut to the window ``[lo, hi)``."""
    return [(max(a, lo), min(b, hi), n) for a, b, n in events
            if min(b, hi) > max(a, lo)]


def total(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def intersect(xs: List[Interval], ys: List[Interval]) -> List[Interval]:
    """Where two sorted lists of disjoint intervals overlap."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def op_name(full: str) -> str:
    """``%fusion.12 = s32[...] fusion(...)`` -> ``fusion.12``."""
    head = full.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def self_times(events: List[Tuple[int, int, str]]) -> Dict[str, int]:
    """Per-name time not covered by the events nested inside (one line,
    where a ``while`` spans the ops of its body)."""
    out: Dict[str, int] = {}
    stack: List[Tuple[int, str]] = []        # (end, name) of open events
    for start, end, name in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= start:
            stack.pop()
        out[name] = out.get(name, 0) + end - start
        if stack:
            parent_end, parent = stack[-1]
            out[parent] -= min(end, parent_end) - start
        stack.append((end, name))
    return out


class Reduction:
    """Per-device busy time, module and op times, host spans and idle
    gaps over the traced window."""

    def __init__(self, path: str, window_span: str = "bench:traced"):
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(path)
        self.devices: Dict[str, Dict[str, list]] = {}
        self.host: List[Tuple[int, int, str]] = []
        for plane in pd.planes:
            if DEVICE_PLANE.match(plane.name):
                lines = {}
                for line in plane.lines:
                    if line.name in (MODULES, OPS):
                        lines[line.name] = [
                            (int(e.start_ns), int(e.start_ns + e.duration_ns),
                             e.name) for e in line.events]
                self.devices[plane.name] = lines
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    if line.name.startswith("python"):
                        self.host += [(int(e.start_ns),
                                       int(e.start_ns + e.duration_ns),
                                       e.name) for e in line.events]
        spans = [(a, b) for a, b, n in self.host if n == window_span]
        if not spans:
            raise ValueError(f"trace holds no {window_span!r} span")
        self.lo = min(a for a, _ in spans)
        self.hi = max(b for _, b in spans)
        self.window_ns = self.hi - self.lo

    def _line(self, dev: str, line: str) -> list:
        return clip_events(self.devices[dev].get(line, []), self.lo, self.hi)

    def busy(self, dev: str) -> List[Interval]:
        return union((a, b) for a, b, _ in self._line(dev, MODULES))

    def busy_s(self) -> float:
        """Busy seconds averaged over the devices in the trace."""
        if not self.devices:
            return 0.0
        return sum(total(self.busy(d)) for d in self.devices) \
            / len(self.devices) / 1e9

    def spans(self, name: str) -> List[Interval]:
        """The union of the host spans called ``name``, cut to the window."""
        return union((a, b) for a, b, _ in clip_events(
            [e for e in self.host if e[2] == name], self.lo, self.hi))

    def idle_share(self, within: Optional[str] = None) -> Optional[float]:
        """1 less the devices' mean busy share of the traced window or,
        given ``within``, of the union of the host spans of that name."""
        if not self.devices:
            return None
        spans = [(self.lo, self.hi)] if within is None else self.spans(within)
        length = total(spans)
        if length <= 0:
            return None
        busy = sum(total(intersect(self.busy(d), spans))
                   for d in self.devices) / len(self.devices)
        return 1.0 - busy / length

    def module_ns(self, pattern: str) -> Tuple[int, int]:
        """(summed device ns over every device, events) of the modules
        whose name matches ``pattern``."""
        rx = re.compile(pattern)
        ns = n = 0
        for d in self.devices:
            for a, b, name in self._line(d, MODULES):
                if rx.search(name):
                    ns += b - a
                    n += 1
        return ns, n

    def top_ops(self, k: int = 10) -> List[List]:
        """Operations with the most device self time, in seconds summed
        over devices."""
        agg: Dict[str, int] = {}
        for d in self.devices:
            ev = [(a, b, op_name(n)) for a, b, n in self._line(d, OPS)]
            for name, ns in self_times(ev).items():
                agg[name] = agg.get(name, 0) + ns
        top = sorted(agg.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / 1e9] for name, ns in top]

    def host_at(self, t: int) -> str:
        """What the host was doing at ``t``: the innermost benchmark span
        and the innermost Python frame covering it."""
        covering = [(a, b, n) for a, b, n in self.host if a <= t < b]
        bench = [c for c in covering if c[2].startswith("bench:")]
        frames = [c for c in covering if not c[2].startswith("bench:")]
        inner = lambda cs: min(cs, key=lambda c: c[1] - c[0])[2]  # noqa
        label = inner(bench) if bench else "outside bench spans"
        if frames:
            label += " / " + inner(frames)
        return label

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The longest idle gaps on the first device, named by what the
        host was doing at their middle, in seconds."""
        if not self.devices:
            return []
        dev = sorted(self.devices)[0]
        gs = sorted(gaps(self.busy(dev), self.lo, self.hi),
                    key=lambda g: g[0] - g[1])[:k]
        return [[self.host_at((a + b) // 2), (b - a) / 1e9] for a, b in gs]

