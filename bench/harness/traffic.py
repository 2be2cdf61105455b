"""The one traffic generator: a mix's data file in, an arrival schedule out.

``arrivals.process`` is one of

* ``poisson`` — open loop at ``rate_wf_s`` workflows a second;
* ``onoff``   — open loop in bursts: every ``period_s`` seconds, ``on_s``
  seconds at ``on_rate_wf_s``, then silence;
* ``closed``  — ``in_flight`` instances kept in flight: a new one is
  started when one completes.

Every schedule is the same set of inter-arrival gaps in some order, so no
seed changes how much work a window holds.  The gaps are the midpoint
quantiles of the exponential distribution, so within a window (or a burst)
the arrivals look Poisson, and a burst spans the same time and holds the
same arrivals under every seed.  Their order is drawn from
``arrivals.seed`` when the mix fixes one, so that every run replays one
schedule and the run's seed draws only the weights and prompts; otherwise
from the run's seed.  (Within a burst the order alone moves the makespan's
median by several per cent.)  A seed is any whole number;
``random.Random`` takes it whole.
"""

from __future__ import annotations

import math
import random
from typing import List, Optional


def _gaps(rate: float, n: int, rng: random.Random) -> List[float]:
    """``n`` exponential inter-arrival gaps (seconds) at ``rate``, the same
    set for every seed, shuffled by ``rng``."""
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    rng.shuffle(gaps)
    return gaps


def _cumulative(start: float, gaps: List[float]) -> List[float]:
    out, t = [], start
    for g in gaps:
        t += g
        out.append(t)
    return out


def schedule(arrivals: dict, seed: int, seconds: float) -> Optional[List[float]]:
    """Due times (seconds after the window opens) of every arrival in a
    window of ``seconds``, ascending; ``None`` for a closed loop."""
    process = arrivals["process"]
    rng = random.Random(arrivals.get("seed", seed))
    if process == "closed":
        return None
    if process == "poisson":
        rate = float(arrivals["rate_wf_s"])
        n = max(1, int(round(rate * seconds)))
        # the gaps sum to just under n / rate: every arrival is due inside
        return [t for t in _cumulative(0.0, _gaps(rate, n, rng)) if t < seconds]
    if process == "onoff":
        period, on = float(arrivals["period_s"]), float(arrivals["on_s"])
        rate = float(arrivals["on_rate_wf_s"])
        n = max(1, int(round(rate * on)))
        out: List[float] = []
        start = 0.0
        while start < seconds:
            out.extend(t for t in _cumulative(start, _gaps(rate, n, rng))
                       if t < seconds)
            start += period
        return out
    raise ValueError(f"unknown arrival process {process!r}")
