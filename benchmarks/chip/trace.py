"""Reduction of a profiler trace (``.xplane.pb``) to what the per-layer
metrics read: device busy and idle time, device time by operation, the
executions of each compiled program, the host spans the benchmark opened,
and the longest idle gaps, each tagged by the host span open during it.

Busy time is the union of the intervals in which an operation ran on a
device (operations that overlap count once), averaged over the devices
that ran anything.  All times are seconds on the trace's own clock.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: line of a device plane that holds one event per operation executed
OPS_LINE = "XLA Ops"
#: line of a device plane that holds one event per program execution
MODULES_LINE = "XLA Modules"


@dataclass
class Op:
    start: float
    dur: float
    name: str
    text: str            # the event's name and every string stat, for matching


@dataclass
class Span:
    name: str
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class TraceSummary:
    window: Tuple[float, float]
    busy_s: float
    devices: int
    ops: List[Op] = field(default_factory=list)
    modules: List[Span] = field(default_factory=list)
    spans: List[Span] = field(default_factory=list)
    busy: List[Tuple[float, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def op_seconds(self, needles: Sequence[str]) -> float:
        """Device seconds of every operation whose name or stats contain
        any of ``needles``."""
        return sum(o.dur for o in self.ops if any(n in o.text for n in needles))

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` operations with the most device time, by HLO name (the
        instruction's text before `` = ``)."""
        tot: Dict[str, float] = {}
        for o in self.ops:
            key = o.name.split(" = ")[0]
            tot[key] = tot.get(key, 0.0) + o.dur
        return [[k, v] for k, v in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def gaps(self) -> List[Tuple[float, float]]:
        """Idle intervals of the window (complement of busy)."""
        out, t = [], self.window[0]
        for a, b in self.busy:
            if a > t:
                out.append((t, a))
            t = max(t, b)
        if self.window[1] > t:
            out.append((t, self.window[1]))
        return out

    def span_at(self, t: float) -> str:
        """Innermost host span open at ``t`` ("no_span" where none is)."""
        best = None
        for s in self.spans:
            if s.start <= t < s.end and (best is None or s.dur < best.dur):
                best = s
        return best.name if best else "no_span"

    def longest_gaps(self, n: int = 10) -> List[List]:
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:n]
        return [[self.span_at((a + b) / 2), b - a] for a, b in gaps]


def find_xplane(logdir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    return paths[-1] if paths else None


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _text(ev) -> str:
    parts = [ev.name]
    for k, v in ev.stats:
        if isinstance(v, str):
            parts.append(f"{k}={v}")
    return " ".join(parts)


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name \
        and "NONCORE" not in plane_name.upper()


def reduce(path: str, span_names: Sequence[str] = ()) -> TraceSummary:
    """Reduce one ``.xplane.pb``.  ``span_names`` are the host annotations
    (``jax.profiler.TraceAnnotation``) to keep for attribution; the window
    runs from the first to the last event of any plane."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    want = set(span_names)
    ops: List[Op] = []
    modules: List[Span] = []
    spans: List[Span] = []
    lo, hi = float("inf"), float("-inf")
    per_device: List[List[Tuple[float, float]]] = []
    for plane in pd.planes:
        device = _is_device(plane.name)
        intervals: List[Tuple[float, float]] = []
        for line in plane.lines:
            for ev in line.events:
                start, dur = ev.start_ns * 1e-9, ev.duration_ns * 1e-9
                if dur > 0 or device:
                    lo, hi = min(lo, start), max(hi, start + dur)
                if device and line.name == OPS_LINE:
                    ops.append(Op(start, dur, ev.name, _text(ev)))
                    intervals.append((start, start + dur))
                elif device and line.name == MODULES_LINE:
                    modules.append(Span(ev.name, start, dur))
                elif not device and ev.name in want:
                    spans.append(Span(ev.name, start, dur))
        if intervals:
            per_device.append(union(intervals))
    if lo == float("inf"):
        lo = hi = 0.0
    busy = [sum(b - a for a, b in u) for u in per_device]
    busy_s = sum(busy) / len(busy) if busy else 0.0
    merged = union(iv for u in per_device for iv in u)
    return TraceSummary(window=(lo, hi), busy_s=busy_s,
                        devices=len(per_device), ops=ops, modules=modules,
                        spans=spans, busy=merged)
