"""Reduce a profiler trace (``.xplane.pb``) to the device's busy time, each
program's device time, and the device's idle gaps by what the host was
doing.

The trace is read with ``jax.profiler.ProfileData``.  Device planes are
those named ``/device:<platform>:<n>``; on each, the ``XLA Ops`` line holds
one event per operation run and ``XLA Modules`` one per program run.  Host
spans are the benchmark's own ``TraceAnnotation`` events, whose names start
with ``bench.``; two of them, ``bench.window.open`` and
``bench.window.close``, mark the traced window on the same clock.
"""

from __future__ import annotations

import glob
import lzma
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[str, float, float]          # (name, start_ns, end_ns)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_OPEN = "bench.window.open"
WINDOW_CLOSE = "bench.window.close"
NO_SPAN = "no benchmark span"
CONTAINER = re.compile(r"%(while|conditional|call)[.\s=]")


@dataclass
class TraceEvents:
    ops: Dict[str, List[Interval]] = field(default_factory=dict)      # by device
    modules: Dict[str, List[Interval]] = field(default_factory=dict)  # by device
    spans: List[Interval] = field(default_factory=list)               # host


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and ":" in plane_name[8:]


def read_events(path: str) -> TraceEvents:
    """Events of an ``.xplane.pb`` file, or of one compressed with xz."""
    import jax
    if path.endswith(".xz"):
        with lzma.open(path) as f:
            pd = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        pd = jax.profiler.ProfileData.from_file(path)
    out = TraceEvents()
    for plane in pd.planes:
        if _is_device(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    out.ops[plane.name] = [(e.name, e.start_ns, e.end_ns)
                                           for e in line.events]
                elif line.name == MODULES_LINE:
                    out.modules[plane.name] = [(e.name, e.start_ns, e.end_ns)
                                               for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out.spans.extend((e.name, e.start_ns, e.end_ns)
                                 for e in line.events
                                 if e.name.startswith(SPAN_PREFIX))
    return out


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted, disjoint cover of the given [start, end) intervals."""
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def window_of(ev: TraceEvents) -> Optional[Tuple[float, float]]:
    """The traced window, from the two marker spans."""
    opens = [s for n, s, _ in ev.spans if n == WINDOW_OPEN]
    closes = [s for n, s, _ in ev.spans if n == WINDOW_CLOSE]
    if not opens or not closes:
        return None
    return min(opens), max(closes)


def busy_ns(ev: TraceEvents, lo: float, hi: float) -> Dict[str, float]:
    """Per device: time inside [lo, hi) in which some operation ran."""
    return {dev: sum(e - s for s, e in _clip(union((s, e) for _, s, e in ops),
                                              lo, hi))
            for dev, ops in ev.ops.items()}


def op_name(hlo: str) -> str:
    """An operation's HLO text without its layouts, cut to 160 letters."""
    return re.sub(r"\{[^{}]*\}", "", hlo)[:160]


def op_seconds(ev: TraceEvents, lo: float, hi: float,
               top: int = 10) -> List[List]:
    """The operations that took most device time in the window, summed by
    name over every device.  Loops and calls, whose time is that of the
    operations inside them, are left out."""
    tot: Dict[str, float] = defaultdict(float)
    for ops in ev.ops.values():
        for n, s, e in ops:
            s, e = max(s, lo), min(e, hi)
            if e > s and not CONTAINER.match(n):
                tot[op_name(n)] += e - s
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[n, t / 1e9] for n, t in ranked]


def module_ns(ev: TraceEvents, prefix: str, lo: float, hi: float) -> List[float]:
    """Device durations of the runs of programs whose name starts with
    ``prefix`` that lie wholly inside the window."""
    return [e - s for mods in ev.modules.values() for n, s, e in mods
            if n.startswith(prefix) and s >= lo and e <= hi]


def idle_by_span(ev: TraceEvents, lo: float, hi: float,
                 top: int = 10) -> List[List]:
    """Idle device time in the window, summed by the set of benchmark
    spans open on the host meanwhile (over devices, averaged)."""
    tot: Dict[str, float] = defaultdict(float)
    n_dev = max(1, len(ev.ops))
    spans = [(n, max(s, lo), min(e, hi)) for n, s, e in ev.spans
             if n not in (WINDOW_OPEN, WINDOW_CLOSE) and e > lo and s < hi]
    edges = sorted({lo, hi} | {s for _, s, _ in spans} | {e for _, _, e in spans})
    for ops in ev.ops.values():
        busy = union((s, e) for _, s, e in ops)
        gaps, t = [], lo
        for s, e in _clip(busy, lo, hi):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        for g0, g1 in gaps:
            cuts = [x for x in edges if g0 < x < g1]
            for a, b in zip([g0] + cuts, cuts + [g1]):
                mid = (a + b) / 2
                names = sorted({n for n, s, e in spans if s <= mid < e})
                tot["+".join(names) if names else NO_SPAN] += (b - a) / n_dev
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[n, t / 1e9] for n, t in ranked]


@dataclass
class Summary:
    window_s: float
    busy_s: float                    # averaged over devices
    device_ops: List[List]
    idle_gaps: List[List]
    events: TraceEvents
    lo: float
    hi: float

    def module_seconds(self, prefix: str) -> List[float]:
        return [d / 1e9 for d in module_ns(self.events, prefix, self.lo, self.hi)]


def summarize(ev: TraceEvents) -> Optional[Summary]:
    """None when the trace holds no window or no device operation."""
    win = window_of(ev)
    if win is None or not ev.ops:
        return None
    lo, hi = win
    busy = busy_ns(ev, lo, hi)
    return Summary(window_s=(hi - lo) / 1e9,
                   busy_s=sum(busy.values()) / len(busy) / 1e9,
                   device_ops=op_seconds(ev, lo, hi),
                   idle_gaps=idle_by_span(ev, lo, hi),
                   events=ev, lo=lo, hi=hi)
