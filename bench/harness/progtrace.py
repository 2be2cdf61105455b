"""Lay the workflow runtime's execution records on the device trace's clock,
and reduce them to the per-layer numbers that need the records.

Clock bridge.  The runner stamps its records with ``time.monotonic`` (ms);
the profiler's events come in ns from the start of its session.
``Driver`` starts every instance inside a ``bench.client.start`` span, and
the runner stamps the entry record's ``t_queued`` inside that span, so each
such span in the trace gives a pair of readings: its midpoint in trace ns
and that ``t_queued``.  The bridge is the least-squares line through the
pairs, an offset and a rate.  Which instances the traced spans started is
found from the gaps between them, which match the gaps between the entry
records' ``t_queued``; the time the tracer was due to start tells apart
bursts whose gaps repeat.

Program intervals: ``queued:<fn>`` from an attempt's ``t_queued`` to its
``t_start``, and ``<fn>:<phase>`` from each of its Trace phase marks to the
next mark or ``t_end``.  The device's idle time in the traced window is
summed by the set of intervals open meanwhile (``devtrace.idle_by_span``),
``nothing outstanding`` where none is.
"""

from __future__ import annotations

import math
import sys
import time
from dataclasses import dataclass
from typing import Any, Dict, Iterable, List, Optional, Tuple

from repro.core.subgraph import GC_FUNCTION

from harness import devtrace, records
from harness.cli import TRACE_S

NOTHING = "nothing outstanding"
CLIENT_START = "bench.client.start"     # harness.driver.Driver._start
GATE_MS = 2000.0    # how far the trace's start may lie from when it was due


@dataclass(frozen=True)
class Bridge:
    """trace ns = ``ns0`` + ``rate`` × runner ms."""

    ns0: float
    rate: float

    def ns(self, ms: float) -> float:
        return self.ns0 + self.rate * ms


def fit(pairs: List[Tuple[float, float]]) -> Optional[Bridge]:
    """Least-squares line through (runner ms, trace ns) pairs."""
    if len(pairs) < 2:
        return None
    mx = sum(x for x, _ in pairs) / len(pairs)
    my = sum(y for _, y in pairs) / len(pairs)
    var = sum((x - mx) ** 2 for x, _ in pairs)
    if var <= 0:
        return None
    rate = sum((x - mx) * (y - my) for x, y in pairs) / var
    return Bridge(my - rate * mx, rate)


def trace_due_ms(window: Any) -> float:
    """When ``cli.Tracer`` was due to start tracing, on the runner's clock:
    whole periods of the traffic fill the first half of the window."""
    return window.t0_ms + 1e3 * TRACE_S * int(window.seconds // (2 * TRACE_S))


def entry_queued_ms(window: Any, entry: str) -> List[float]:
    """When each instance the window drove had its entry attempt queued."""
    out = []
    for inst in window.instances:
        qs = [r.t_queued for r in inst.records if r.function == entry]
        if qs:
            out.append(min(qs))
    return sorted(out)


def bridge(summary: Any, window: Any, entry: str) -> Optional[Bridge]:
    """The clock bridge from the traced ``bench.client.start`` spans; None
    with fewer than two of them or no match near the trace's due start."""
    mids = sorted((s + e) / 2 for n, s, e in summary.events.spans
                  if n == CLIENT_START)
    if len(mids) < 2:
        return None
    queued = entry_queued_ms(window, entry)
    due = trace_due_ms(window)
    best: Optional[Tuple[float, int]] = None
    for k in range(len(queued) - len(mids) + 1):
        if abs(queued[k] - mids[0] / 1e6 - due) > GATE_MS:
            continue
        err = sum(((queued[k + i] - queued[k]) - (m - mids[0]) / 1e6) ** 2
                  for i, m in enumerate(mids))
        if best is None or err < best[0]:
            best = (err, k)
    if best is None:
        return None
    return fit([(queued[best[1] + i], m) for i, m in enumerate(mids)])


def window_records(window: Any) -> List[Any]:
    """The records of every instance the window drove, lead-in included,
    and the GC attempts queued since the first of them.  The keeper
    workflow and the warm-up instance are not among them."""
    recs = [r for inst in window.instances for r in inst.records]
    if not recs:
        return []
    first = min(r.t_queued for r in recs)
    return recs + [r for r in window.runner.executions_of(GC_FUNCTION)
                   if r.t_queued >= first]


def intervals(recs: Iterable[Any]) -> List[Tuple[str, float, float]]:
    """``queued:<fn>`` and ``<fn>:<phase>`` intervals, in runner ms."""
    out = []
    for r in recs:
        begun = r.t_end if math.isnan(r.t_start) else r.t_start
        if not math.isnan(begun):
            out.append((f"queued:{r.function}", r.t_queued, begun))
        if math.isnan(r.t_end):
            continue
        marks = list(r.phases) + [(r.t_end, None)]
        for (t0, name), (t1, _) in zip(marks, marks[1:]):
            out.append((f"{r.function}:{name}", t0, t1))
    return [iv for iv in out if iv[2] > iv[1]]


def idle_by_program(run: Any) -> Optional[Dict[str, float]]:
    """Idle device seconds in the traced window by the program intervals
    open meanwhile; None without a trace or a clock bridge."""
    if run.trace is None:
        return None
    br = bridge(run.trace, run.window, run.entry)
    if br is None:
        return None
    spans = [(n, br.ns(s), br.ns(e))
             for n, s, e in intervals(window_records(run.window))]
    ev = devtrace.TraceEvents(ops=run.trace.events.ops, spans=spans)
    # n intervals cut the window into at most 2n + 1 sets: keep every one
    idle = devtrace.idle_by_span(ev, run.trace.lo, run.trace.hi,
                                 top=2 * len(spans) + 1)
    return {NOTHING if n == devtrace.NO_SPAN else n: t for n, t in idle}


def in_flight_share(run: Any, idle: Optional[Dict[str, float]]) -> Optional[float]:
    """Share of the traced window, in %, in which the device was idle while
    some program interval was open."""
    if idle is None or run.trace.window_s <= 0:
        return None
    busy_idle = sum(t for n, t in idle.items() if n != NOTHING)
    return 100.0 * busy_idle / run.trace.window_s


def device_idle_in_flight(run: Any) -> Optional[float]:
    """The ``device_idle_in_flight`` metric.  Writes to standard error, as
    ``bench:`` lines, the idle time by program intervals (top 10), the
    critical paths of the window's p50 and slowest instance, and the host
    seconds this reduction took."""
    t = time.perf_counter()
    idle = idle_by_program(run)
    share = in_flight_share(run, idle)
    paths = critical_paths(run.window, run.entry)
    took = time.perf_counter() - t
    if idle is not None:
        top = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
        _say("idle by program " + "; ".join(f"{n} {s}" for n, s in top))
    for line in paths:
        _say(line)
    _say(f"program trace reduction {took} s")
    return share


def _say(line: str) -> None:
    print(f"bench: {line}", file=sys.stderr, flush=True)


# ---- datastore work per instance -------------------------------------------


def with_gc(window: Any) -> Dict[str, List[Any]]:
    """Each instance's records (by ``wfid``) and the GC attempts its
    attempts invoked, found by their ``parent`` link: GC records carry no
    workflow id.  Records without the link add no GC attempt."""
    gc_by_parent: Dict[Any, List[Any]] = {}
    for r in window.runner.executions_of(GC_FUNCTION):
        gc_by_parent.setdefault(getattr(r, "parent", None), []).append(r)
    out = {}
    for inst in window.instances:
        recs = list(inst.records)
        for r in inst.records:
            recs.extend(gc_by_parent.get(r.exec_id, ()))
        out[inst.wfid] = recs
    return out


def ds_per_wf(window: Any, count) -> Optional[float]:
    """Mean over the completed instances due in the window of ``count(r)``
    summed over all their attempts, GC included; None when the records
    carry no datastore counters."""
    attempts = with_gc(window)
    xs = []
    for inst in window.due_in_window():
        if records.completed_ms(inst.records, window.terminal) is None:
            continue
        recs = attempts[inst.wfid]
        if any(getattr(r, "ds_ms", None) is None for r in recs):
            return None
        xs.append(sum(count(r) for r in recs))
    return sum(xs) / len(xs) if xs else None


# ---- the critical path of one instance -------------------------------------


def _chain(inst: Any, terminal: str) -> List[Any]:
    """The instance's terminal ``done`` record and its ancestors by
    ``parent``, entry first (records without the link: the terminal's
    alone)."""
    done = [r for r in inst.records if r.function == terminal and r.status == "done"]
    if not done:
        return []
    by_id = {r.exec_id: r for r in inst.records}
    chain = [max(done, key=lambda r: r.t_end)]
    while getattr(chain[-1], "parent", None) in by_id:
        chain.append(by_id[chain[-1].parent])
    return chain[::-1]


def _hop_ms(parent: Any, child: Any) -> Optional[float]:
    """From the parent's last ``invoke`` mark before the child was queued
    to the child's ``t_queued``."""
    marks = [t for t, n in parent.phases if n == "invoke" and t <= child.t_queued]
    return child.t_queued - marks[-1] if marks else None


def _ms(x: Optional[float]) -> str:
    return "n/a" if x is None else f"{x:.3f}"


def critical_path(inst: Any, terminal: str, entry: str) -> str:
    """One line: the instance's lateness, then along the ``parent`` links
    from its entry to its terminal attempt each attempt's queue wait and
    phase times and each hop between them, in ms."""
    entries = [r.t_queued for r in inst.records if r.function == entry]
    late = min(entries) - inst.due_ms if entries else None
    parts = [f"late {_ms(late)}"]
    chain = _chain(inst, terminal)
    for prev, r in zip([None] + chain, chain):
        if prev is not None:
            parts.append(f"hop {_ms(_hop_ms(prev, r))}")
        phases = " ".join(f"{n} {t:.3f}" for n, t in r.phase_breakdown().items())
        parts.append(f"{r.function}#{r.exec_id} attempt {r.attempt} queue "
                     f"{r.t_start - r.t_queued:.3f} [{phases}]")
    return "; ".join(parts)


def critical_paths(window: Any, entry: str) -> List[str]:
    """Critical-path lines of the window's p50 instance and its slowest,
    among the completed instances due in it."""
    done = [(m, i) for i in window.due_in_window()
            if (m := records.makespan_from_due_ms(i, window.terminal)) is not None]
    if not done:
        return []
    done.sort(key=lambda x: x[0])
    out = []
    for label, (m, inst) in (("p50", done[len(done) // 2]), ("slowest", done[-1])):
        out.append(f"critical path, {label} instance {inst.index} (makespan "
                   f"{m:.3f} ms): {critical_path(inst, window.terminal, entry)}")
    return out
