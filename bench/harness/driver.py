"""Drive one cell's window through the system under test.

The workflow is deployed with ``repro.core.workflow.deploy`` onto an
in-process ``LocalRunner``.  The runner's ``run`` loop works on a thread of
its own while this module is the client: it starts instances through
``DeployedWorkflow.start`` when they are due (open loop) or when one
completes (closed loop), then lets the runner drain.

A closed loop does not start all its instances at once: they would share
the chip alike and complete in waves of ``in_flight``, and a count of
completions in the window would move in steps of a whole wave.  Its
instances start one solo instance time apart (the warm-up instance's
makespan), and the window opens one loaded instance time after the last of
them, so that completions come spread out.

A one-function keeper workflow, due when the window closes, keeps the
runner from going quiescent (and ``run`` from returning) between arrivals.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax

from repro.backends.localjax import LocalRunner
from repro.backends.shim import Workload
from repro.core import workflow as wf
from repro.core.subgraph import WorkflowSpec

from harness import traffic
from harness.stage import Stage

DRAIN_TIMEOUT_S = 120.0
POLL_S = 0.002


def now_ms() -> float:
    """The runner's clock (``LocalRunner`` stamps ``time.monotonic``)."""
    return time.monotonic() * 1e3


def ramp(in_flight: int, solo_s: float) -> Tuple[List[float], float]:
    """A closed loop's lead-in: when each of its first ``in_flight``
    instances starts, and when the window opens (seconds from the first
    start).  Loaded, an instance takes about ``in_flight`` solo times."""
    starts = [k * solo_s for k in range(in_flight)]
    return starts, starts[-1] + in_flight * solo_s


def build_spec(workflow: Dict[str, Any], stage: Stage) -> WorkflowSpec:
    """The configuration's workflow shape, its user functions bound to the
    stage (a function's ``fn`` names a method of ``Stage``)."""
    spec = WorkflowSpec(workflow["name"], gc=workflow.get("gc", True))
    for f in workflow["functions"]:
        spec.function(f["name"], f["faas"], memory_gb=f.get("memory_gb"),
                      workload=Workload(compute_ms=f.get("compute_ms", 0.0),
                                        out_bytes=f.get("out_bytes"),
                                        accel=f.get("accel", True),
                                        fn=getattr(stage, f["fn"])))
    for src, dst in workflow["edges"]:
        spec.sequence(src, dst)
    return spec


@dataclass
class Instance:
    index: int
    due_ms: float
    wfid: str
    records: List[Any] = field(default_factory=list)


@dataclass
class Window:
    """What one measured window left behind, for the readers."""

    t0_ms: float
    t1_ms: float
    instances: List[Instance]
    stage: Stage
    runner: LocalRunner
    terminal: str
    drained_ms: float = 0.0

    @property
    def seconds(self) -> float:
        return (self.t1_ms - self.t0_ms) / 1e3

    def due_in_window(self) -> List[Instance]:
        """The instances the metrics read: those due while the window was
        open (a closed loop's lead-in is left out; the check reads all)."""
        return [i for i in self.instances if self.t0_ms <= i.due_ms < self.t1_ms]


class _RunnerThread(threading.Thread):
    def __init__(self, runner: LocalRunner, timeout_s: float):
        super().__init__(name="bench-runner", daemon=True)
        self.runner, self.timeout_s = runner, timeout_s
        self.error: Optional[BaseException] = None

    def run(self) -> None:
        try:
            self.runner.run(timeout_s=self.timeout_s)
        except BaseException as e:              # re-raised by the client
            self.error = e


class Driver:
    """One deployment of a cell's workflow, warmed, ready for windows."""

    def __init__(self, workflow: Dict[str, Any], stage: Stage,
                 concurrency: int = 8):
        self.stage = stage
        self.solo_s = 0.0                 # the warm-up instance's makespan
        self.runner = LocalRunner(concurrency=concurrency)
        self.terminal = workflow["terminal"]
        self.dep = wf.deploy(self.runner, build_spec(workflow, stage))
        keeper = WorkflowSpec("bench-keeper", gc=False)
        keeper.function("keep", workflow["functions"][0]["faas"],
                        workload=Workload(fn=lambda _: None))
        self.keeper = wf.deploy(self.runner, keeper)
        self._next = 0

    def _start(self, seed: int) -> Instance:
        i = self._next
        self._next += 1
        with jax.profiler.TraceAnnotation("bench.client.start"):
            due = now_ms()
            wfid = self.dep.start({"seed": seed, "instance": i})
        return Instance(i, due, wfid)

    def warm_up(self, seed: int) -> None:
        """One instance end to end, outside any window, timed alone."""
        t = now_ms()
        self._start(seed)
        self.runner.run(timeout_s=DRAIN_TIMEOUT_S)
        self.solo_s = (now_ms() - t) / 1e3

    def window(self, arrivals: dict, seed: int, seconds: float,
               on_open=None) -> Window:
        """Drive one window of ``seconds`` and drain.  ``on_open(t0_ms)`` is
        called as the window opens (the tracer hooks in there); a closed
        loop's lead-in comes before it."""
        due = traffic.schedule(arrivals, seed, seconds)
        if due is None:
            starts, lead = ramp(int(arrivals["in_flight"]), self.solo_s)
        else:
            starts, lead = [], 0.0
        self.keeper.start(None, t=(lead + seconds) * 1e3)
        th = _RunnerThread(self.runner, lead + seconds + DRAIN_TIMEOUT_S)
        th.start()
        t0 = now_ms() + lead * 1e3
        t1 = t0 + seconds * 1e3
        if due is None:
            insts = self._closed(starts, seed, t0, t1, th, on_open)
        else:
            if on_open is not None:
                on_open(t0)
            insts = self._open(due, seed, t0, th)
        th.join(lead + seconds + DRAIN_TIMEOUT_S + 10.0)
        if th.is_alive():
            raise RuntimeError("the runner did not drain")
        if th.error is not None:
            raise th.error
        for inst in insts:
            inst.records = self.dep.executions(inst.wfid)
        return Window(t0, t1, insts, self.stage, self.runner, self.terminal,
                      drained_ms=now_ms())

    def _open(self, due: List[float], seed: int, t0: float,
              th: _RunnerThread) -> List[Instance]:
        insts = []
        for offset in due:
            wait = (t0 + offset * 1e3 - now_ms()) / 1e3
            if wait > 0:
                time.sleep(wait)
            if th.error is not None:
                break
            inst = self._start(seed)
            inst.due_ms = t0 + offset * 1e3
            insts.append(inst)
        return insts

    def _closed(self, starts: List[float], seed: int, t0: float, t1: float,
                th: _RunnerThread, on_open) -> List[Instance]:
        """Start an instance at each of ``starts`` (seconds from now) and
        another whenever one completes, until the window closes."""
        seen = {r.exec_id for r in self.runner.executions_of(self.terminal)
                if r.status == "done"}
        first = now_ms()
        pending = [first + s * 1e3 for s in starts]
        insts: List[Instance] = []
        opened = on_open is None
        while now_ms() < t1 and th.error is None:
            if not opened and now_ms() >= t0:
                on_open(t0)
                opened = True
            while pending and now_ms() >= pending[0]:
                pending.pop(0)
                insts.append(self._start(seed))
            done = [r for r in self.runner.executions_of(self.terminal)
                    if r.status == "done" and r.exec_id not in seen]
            for r in done:
                seen.add(r.exec_id)
                if now_ms() < t1:
                    insts.append(self._start(seed))
            time.sleep(POLL_S)
        return insts
