"""``bench/run.py``: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (weights from the seed, every program of the cell compiled or
loaded from the persistent cache and run once, one workflow instance end to
end) is timed as ``setup_s``.  Then the window: the cell's traffic for
``--seconds``, drained.  With ``--trace 1`` a profiler trace of a few
seconds in the middle of the window feeds the per-layer metrics; the
end-to-end ones are taken with the profiler off.  After the window the peak
device memory is read and the output is checked against the plain
reference.  The last line of standard output is the result as JSON.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Dict, List, Optional

import jax
from jax import monitoring

from repro.compile_cache import use_compile_cache

from harness import check, devtrace
from harness.driver import Driver, Window, now_ms
from harness.spec import BENCH, ROOT, Cell, load_cell, load_json
from harness.stage import Stage, make_weights, model_config

TRACE_S = 4.0            # length of the traced part of a --trace 1 window
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


@dataclass
class Run:
    """What a metric's reader gets."""

    cell: Cell
    window: Window
    setup_s: float
    device_kind: str
    trace: Optional[devtrace.Summary] = None

    @property
    def model(self) -> Dict[str, Any]:
        return self.cell.config["model"]

    @property
    def request(self) -> Dict[str, int]:
        return self.cell.traffic["request"]

    @property
    def entry(self) -> str:
        return self.cell.config["workflow"]["functions"][0]["name"]

    def peaks(self) -> Dict[str, Any]:
        table = load_json(BENCH / "peaks.json")["devices"]
        if self.device_kind not in table:
            raise KeyError(f"no peaks for device kind {self.device_kind!r} "
                           f"in bench/peaks.json")
        return table[self.device_kind]


class Tracer:
    """Traces ``TRACE_S`` seconds of the window, starting where whole
    periods of the traffic before it fill the first half."""

    def __init__(self, seconds: float):
        self.length = min(TRACE_S, seconds)
        self.offset = TRACE_S * int(seconds // (2 * TRACE_S))
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.error: Optional[BaseException] = None
        self.thread: Optional[threading.Thread] = None

    def open(self, t0_ms: float) -> None:
        self.thread = threading.Thread(target=self._trace, args=(t0_ms,),
                                       name="bench-tracer", daemon=True)
        self.thread.start()

    def _trace(self, t0_ms: float) -> None:
        try:
            time.sleep(max(0.0, (t0_ms + self.offset * 1e3 - now_ms()) / 1e3))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(devtrace.WINDOW_OPEN):
                end = now_ms() + self.length * 1e3
            time.sleep(max(0.0, (end - now_ms()) / 1e3))
            with jax.profiler.TraceAnnotation(devtrace.WINDOW_CLOSE):
                pass
            jax.profiler.stop_trace()
        except BaseException as e:
            self.error = e

    def summary(self) -> Optional[devtrace.Summary]:
        self.thread.join(TRACE_S + 120.0)
        try:
            if self.error is not None:
                raise self.error
            return devtrace.summarize(
                devtrace.read_events(devtrace.find_xplane(self.dir)))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def _parse(argv):
    ap = argparse.ArgumentParser(description="One run of one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _say(line: str) -> None:
    print(line, file=sys.stderr, flush=True)


def main(argv=None, *, t_start: Optional[float] = None, platform: str = "tpu",
         root: Path = ROOT, out=None) -> int:
    t_start = time.monotonic() if t_start is None else t_start
    out = out or sys.stdout
    args = _parse(argv)
    cell = load_cell(args.workload, root)
    devices = jax.devices()
    if devices[0].platform != platform or len(devices) < cell.chips:
        _say(f"bench: {cell.name} needs {cell.chips} {platform} chip(s); JAX "
             f"found {len(devices)} {devices[0].platform} device(s)")
        return 2
    _say(f"bench: compile cache {setup_jax()}")

    compiles: List[float] = []

    def on_duration(event: str, secs: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            compiles.append(now_ms())

    monitoring.register_event_duration_secs_listener(on_duration)
    try:
        return _run(args, cell, t_start, devices, compiles, out)
    finally:
        monitoring.unregister_event_duration_listener(on_duration)


def prepare(cell: Cell, seed: int) -> Driver:
    """Set-up: weights from the seed, the cell's programs compiled (or
    loaded) and run once, one workflow instance end to end."""
    model = cell.config["model"]
    params = make_weights(model_config(model), seed, cell.config.get("draw"))
    stage = Stage(model, params, cell.traffic["request"], seed)
    stage.warm_up()
    driver = Driver(cell.config["workflow"], stage)
    driver.warm_up(seed)
    return driver


def setup_jax() -> str:
    """The persistent compilation cache at its fixed path, for every
    program however fast it compiles."""
    where = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return where


def _run(args, cell: Cell, t_start: float, devices, compiles: List[float],
         out) -> int:
    driver = prepare(cell, args.seed)
    stage = driver.stage

    tracer = Tracer(args.seconds) if args.trace else None
    t_open: List[float] = []

    def on_open(t0_ms: float) -> None:
        t_open.append(time.monotonic())
        if tracer is not None:
            tracer.open(t0_ms)

    window = driver.window(cell.traffic["arrivals"], args.seed, args.seconds,
                           on_open=on_open)
    setup_s = t_open[0] - t_start
    summary = tracer.summary() if tracer is not None else None
    in_window = sum(1 for t in compiles if window.t0_ms <= t <= window.t1_ms)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell.chips])

    run = Run(cell, window, setup_s, devices[0].device_kind, summary)
    metrics: Dict[str, Dict[str, Any]] = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value = m.read(run)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}

    verdict = check.check(window, run.entry, args.seed, cell.limits,
                          cell.reference)
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": cell.chips, "memory_peak_bytes": int(peak)}
    if summary is not None:
        device["busy_s"], device["window_s"] = summary.busy_s, summary.window_s
    result: Dict[str, Any] = {
        "correct": verdict.correct,
        "attempted": len(window.instances),
        "failed": verdict.failed,
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["check"] = verdict.numbers

    _say(f"bench: setup_s {setup_s}")
    _say(f"bench: instances {result['attempted']}, stage calls "
         f"{len(stage.calls)}, dropped {window.runner.drop_count}, "
         f"compiles in window {in_window}")
    for i, why in sorted(verdict.reasons.items())[:20]:
        _say(f"bench: instance {i}: {why}")
    for name, n in verdict.numbers.items():
        _say(f"check: {name} {n['value']} limit {n['limit']}")
    print(json.dumps(result), file=out, flush=True)
    return 0
