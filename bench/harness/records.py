"""Reductions from a window's execution records to numbers.

Every time is on the runner's clock (``time.monotonic`` in ms).  The
percentile and the prices are copies kept here, so that no change to the
program can move the yardstick: ``percentile`` is
``repro.core.traffic.percentile`` and the arithmetic of ``usd`` is that of
``repro.backends.billing.Bill`` with the prices of ``bench/prices.json``.
"""

from __future__ import annotations

import json
import math
from typing import Any, Dict, Iterable, List, Optional, Sequence

from repro.core.subgraph import GC_FUNCTION

from harness.spec import BENCH, load_json


def percentile(sorted_xs: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile of an ascending sequence; None when empty."""
    k = len(sorted_xs)
    if not k:
        return None
    if q == 0.5:
        return sorted_xs[k // 2]
    return sorted_xs[min(k - 1, int(round(q * (k - 1))))]


def _done(records: Iterable[Any]) -> List[Any]:
    return [r for r in records
            if r.status == "done" and r.function != GC_FUNCTION]


def completed_ms(records: Sequence[Any], terminal: str) -> Optional[float]:
    """When the instance's terminal output was committed (None if never)."""
    ends = [r.t_end for r in _done(records) if r.function == terminal]
    return max(ends) if ends else None


def makespan_from_due_ms(inst: Any, terminal: str) -> Optional[float]:
    """Due time to the ``t_end`` of the instance's last ``done`` record, GC
    excluded; None while its terminal output is not committed."""
    if completed_ms(inst.records, terminal) is None:
        return None
    return max(r.t_end for r in _done(inst.records)) - inst.due_ms


def makespans_ms(window: Any) -> List[float]:
    out = [makespan_from_due_ms(i, window.terminal)
           for i in window.due_in_window()]
    return sorted(m for m in out if m is not None)


def lateness_ms(window: Any, entry: str) -> List[float]:
    """How late each entry invocation was queued after it was due."""
    out = []
    for inst in window.due_in_window():
        first = [r for r in inst.records if r.function == entry]
        if first:
            out.append(min(r.t_queued for r in first) - inst.due_ms)
    return sorted(out)


def queue_wait_ms(window: Any, function: str) -> List[float]:
    return sorted(r.t_start - r.t_queued
                  for inst in window.due_in_window() for r in inst.records
                  if r.function == function and not math.isnan(r.t_start))


def user_exec_ms(rec: Any) -> float:
    """The attempt's time inside the user function, from its Trace marks."""
    marks = list(rec.phases) + [(rec.t_end, "_end")]
    return sum(t1 - t0 for (t0, name), (t1, _) in zip(marks, marks[1:])
               if name == "user_exec")


def orchestration_ms(inst: Any) -> float:
    """Σ over the instance's done attempts of attempt time outside the user
    function: unwrap, checkpoints, invocations, coordination, GC marks."""
    return sum((r.t_end - r.t_start) - user_exec_ms(r)
               for r in _done(inst.records))


def payload_bytes(value: Any) -> int:
    """Wire size of a payload as JSON."""
    return len(json.dumps(value, separators=(",", ":")))


def prices() -> Dict[str, Any]:
    return load_json(BENCH / "prices.json")


def usd(inst: Any, memory_gb: Dict[str, float], egress_bytes: int,
        table: Dict[str, Any]) -> float:
    """What one instance is billed: GB·s of every attempt at its flavor's
    price, one invocation per attempt, and the cross-cloud egress of its
    payload between stages."""
    cost = 0.0
    for r in inst.records:
        if math.isnan(r.t_start) or math.isnan(r.t_end):
            continue
        flavor = table["flavors"][r.faas]
        mem = memory_gb.get(r.function) or flavor["memory_gb"]
        cost += mem * (r.t_end - r.t_start) / 1e3 * flavor["price_per_gb_s"]
        cost += table["invoke_price"]
    return cost + egress_bytes / 1e9 * table["egress_price_per_gb"]
