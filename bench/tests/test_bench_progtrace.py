"""The runtime's execution records laid on the device trace's clock: the
clock bridge (on hand-built spans and on a profiler trace of this host),
device idle split by the program intervals open meanwhile, the
critical-path lines, and the readers of the metrics built on them."""

import json
import math
import os
import shutil
import sys
import time
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import cli, devtrace, layers, progtrace, spec  # noqa: E402
from harness.devtrace import TraceEvents  # noqa: E402
from harness.driver import Driver, Instance  # noqa: E402
from repro.backends.shim import ExecutionRecord  # noqa: E402
from repro.core.subgraph import GC_FUNCTION  # noqa: E402

RECORDED = os.path.join(HERE, "data", "small_trace.xplane.pb.xz")

# A 50 s window opening at runner ms 1e4: the tracer is due at DUE_MS.  The
# trace clock starts 30 ms after that and runs at another rate.
DUE_MS = 1e4 + 1e3 * cli.TRACE_S * 6
RATE = 0.999e6


def to_ns(ms):
    return RATE * (ms - DUE_MS - 30.0)


def at(*ms):
    return [DUE_MS + x for x in ms]


def rec(exec_id, fn, q, s, e, phases=(), parent=None, status="done",
        reads=0, writes=0, ds_ms=0.0):
    r = ExecutionRecord(exec_id, fn, "aws/lambda", t_queued=q, t_start=s,
                        t_end=e, status=status, parent=parent)
    r.phases = list(phases)
    r.ds_reads, r.ds_writes, r.ds_ms = reads, writes, ds_ms
    return r


def instance(i, due, recs):
    inst = Instance(i, due, f"wf-{i}")
    inst.records = recs
    return inst


def window(insts, gc=(), t0=1e4, t1=6e4):
    runner = SimpleNamespace(
        executions_of=lambda fn: list(gc) if fn == GC_FUNCTION else [])
    return SimpleNamespace(
        instances=insts, runner=runner, terminal="qa", t0_ms=t0, t1_ms=t1,
        seconds=(t1 - t0) / 1e3,
        due_in_window=lambda: [i for i in insts if t0 <= i.due_ms < t1])


def summary(ops, starts, lo_ms=DUE_MS, hi_ms=DUE_MS + 100.0):
    """A summary of one device's ``ops`` (runner ms) over the traced window
    [lo_ms, hi_ms), with a 40 µs client-start span around each of
    ``starts``."""
    ev = TraceEvents(
        ops={"/device:TPU:0": [("op", to_ns(s), to_ns(e)) for s, e in ops]},
        spans=[(devtrace.WINDOW_OPEN, to_ns(lo_ms), to_ns(lo_ms)),
               (devtrace.WINDOW_CLOSE, to_ns(hi_ms), to_ns(hi_ms))]
        + [(progtrace.CLIENT_START, to_ns(q) - 20e3, to_ns(q) + 20e3)
           for q in starts])
    return devtrace.summarize(ev)


def test_fit_recovers_an_offset_and_a_rate():
    br = progtrace.fit([(ms, to_ns(ms)) for ms in (3.0, 40.0, 99.5)])
    assert br.rate == pytest.approx(RATE)
    assert br.ns(DUE_MS + 30.0) == pytest.approx(0.0, abs=1e-3)
    assert progtrace.fit([(1.0, 2.0)]) is None
    assert progtrace.fit([(1.0, 2.0), (1.0, 3.0)]) is None


def test_bridge_finds_the_traced_burst_among_repeating_ones():
    # three bursts of the same gaps, 4 s apart; the trace holds the middle
    # one, minus its first start, which came before the trace began
    gaps = [0.0, 3.0, 11.0, 12.5, 30.0]
    queued = [DUE_MS + p + g for p in (-4000.0, 0.0, 4000.0) for g in gaps]
    insts = [instance(i, q, [rec(i, "sort", q, q, q + 1)])
             for i, q in enumerate(queued)]
    br = progtrace.bridge(summary([], queued[6:10]), window(insts), "sort")
    assert br.rate == pytest.approx(RATE)
    for q in queued:
        assert br.ns(q) == pytest.approx(to_ns(q), abs=1.0)
    # one start, or none near the trace's due time: no bridge
    assert progtrace.bridge(summary([], queued[6:7]), window(insts), "sort") is None
    far = [instance(i, q, [rec(i, "sort", q + 5000.0, q, q + 1)])
           for i, q in enumerate(queued[5:10])]
    assert progtrace.bridge(summary([], queued[6:10]), window(far), "sort") is None


# Device busy over [10, 20) and [60, 70) of a [0, 100) ms traced window
# (runner ms from DUE_MS).  Instance 0: sort queued [2, 5), runs [5, 25)
# with user_exec from 8, invokes at 20; qa queued [21, 30), runs [30, 50) in
# user_exec, its GC attempt [50, 55).  Instance 1 started at 1 and ran no
# phase.  Instance 2 is a closed loop's lead-in: queued [80, 90), unwrap to
# 95.
SORT = rec(1, "sort", *at(2.0, 5.0, 25.0),
           phases=list(zip(at(5.0, 8.0, 20.0), ("unwrap", "user_exec", "invoke"))),
           reads=2, writes=3, ds_ms=0.25)
QA = rec(2, "qa", *at(21.0, 30.0, 50.0), phases=[(DUE_MS + 30.0, "user_exec")],
         parent=1, reads=1, writes=1, ds_ms=0.5)
GC = rec(3, GC_FUNCTION, *at(50.0, 51.0, 55.0), phases=[(DUE_MS + 51.0, "gc")],
         parent=2, reads=1, writes=1, ds_ms=0.125)
ENTRY = rec(4, "sort", *at(1.0, 1.0, 1.0))
LEAD = rec(5, "sort", *at(80.0, 90.0, 95.0), phases=[(DUE_MS + 90.0, "unwrap")])


def busy_run():
    insts = [instance(0, DUE_MS + 1.5, [SORT, QA]), instance(1, 0.0, [ENTRY]),
             instance(2, 0.0, [LEAD])]
    s = summary([(DUE_MS + 10.0, DUE_MS + 20.0), (DUE_MS + 60.0, DUE_MS + 70.0)],
                at(1.0, 2.0, 80.0))
    return SimpleNamespace(trace=s, window=window(insts, gc=[GC]), entry="sort")


def test_intervals_of_a_record():
    assert progtrace.intervals([SORT]) == [
        ("queued:sort", *at(2.0, 5.0)), ("sort:unwrap", *at(5.0, 8.0)),
        ("sort:user_exec", *at(8.0, 20.0)), ("sort:invoke", *at(20.0, 25.0))]
    never = rec(9, "qa", 3.0, math.nan, 4.0, status="dropped")
    assert progtrace.intervals([never]) == [("queued:qa", 3.0, 4.0)]


def test_idle_by_program_labels():
    got = progtrace.idle_by_program(busy_run())
    want = {progtrace.NOTHING: 2 + 5 + 10 + 5,
            "queued:sort": 3 + 10, "sort:unwrap": 3 + 5, "sort:user_exec": 2,
            "sort:invoke": 1, "queued:qa+sort:invoke": 4, "queued:qa": 5,
            "qa:user_exec": 20, "queued:__gc__": 1, "__gc__:gc": 4}
    assert set(got) == set(want)
    for name, ms in want.items():
        assert got[name] == pytest.approx(ms * RATE / 1e9, rel=1e-6), name
    assert sum(got.values()) == pytest.approx(80 * RATE / 1e9, rel=1e-6)


def test_device_idle_in_flight_never_exceeds_the_idle_share(capsys):
    run = busy_run()
    idle = layers.device_idle_share(run)
    inflight = progtrace.device_idle_in_flight(run)
    assert idle == pytest.approx(80.0)
    assert inflight == pytest.approx(80.0 - 22.0)
    assert inflight <= idle
    err = capsys.readouterr().err.splitlines()
    assert err[0].startswith("bench: idle by program nothing outstanding 0.0219")
    assert err[1].startswith("bench: critical path, p50 instance 0 ")
    assert err[-1].startswith("bench: program trace reduction ")
    run.trace = None
    assert progtrace.device_idle_in_flight(run) is None


def test_idle_on_the_recorded_tpu_trace():
    ev = devtrace.read_events(RECORDED)
    lo, _ = devtrace.window_of(ev)
    # two client starts 1 ms apart in the window, at runner ms DUE_MS + 1, + 2
    ev.spans += [(progtrace.CLIENT_START, lo + 1e6 * k - 2e4, lo + 1e6 * k + 2e4)
                 for k in (1, 2)]
    s = devtrace.summarize(ev)
    idle = layers.device_idle_share(SimpleNamespace(trace=s))
    starts = [rec(k, "sort", *at(k, k + 0.01, k + 0.02)) for k in (1, 2)]
    whole = rec(9, "qa", *at(-1e3, -1e3, 1e6), phases=[(DUE_MS - 1e3, "user_exec")])
    insts = [instance(0, 0.0, [starts[0], whole]), instance(1, 0.0, [starts[1]])]
    run = SimpleNamespace(trace=s, window=window(insts), entry="sort")
    # one attempt running over the whole window: all idle is in flight
    assert progtrace.device_idle_in_flight(run) == pytest.approx(idle)
    insts[0].records = [starts[0]]
    assert progtrace.device_idle_in_flight(run) < 0.1 * idle


def test_bridge_on_a_profiler_trace_of_this_host():
    """The runner's clock against the profiler's, both read on this host,
    in a traced window that ``Driver`` drives with stand-in stage
    functions."""
    stage = SimpleNamespace(sort=lambda e: {"instance": e["instance"]},
                            qa=lambda m: time.sleep(0.01) or m)
    conf = json.loads((spec.ROOT / "bench" / "configs"
                       / "qa-mamba2-370m.json").read_text())
    driver = Driver(conf["workflow"], stage)
    driver.warm_up(1)
    tracer = cli.Tracer(2.0)
    arrivals = {"process": "onoff", "period_s": 1.0, "on_s": 0.25,
                "on_rate_wf_s": 16.0, "seed": 3}
    try:
        w = driver.window(arrivals, 1, 2.0, on_open=tracer.open)
        tracer.thread.join(60.0)
        assert tracer.error is None and not tracer.thread.is_alive()
        ev = devtrace.read_events(devtrace.find_xplane(tracer.dir))
    finally:
        shutil.rmtree(tracer.dir, ignore_errors=True)
    mids = [(s + e) / 2 for n, s, e in ev.spans if n == progtrace.CLIENT_START]
    assert len(mids) >= 2
    br = progtrace.bridge(SimpleNamespace(events=ev), w, "sort")
    assert br is not None and br.rate == pytest.approx(1e6, rel=1e-3)
    # each traced start lies within 1 ms of the entry attempt it queued
    queued = [br.ns(q) for q in progtrace.entry_queued_ms(w, "sort")]
    assert all(min(abs(q - m) for q in queued) < 1e6 for m in mids)


def test_critical_path_walks_parent_links_from_the_terminal():
    sort = rec(1, "sort", 2.0, 5.0, 25.0,
               phases=[(5.0, "unwrap"), (8.0, "user_exec"), (20.0, "invoke")])
    retry = rec(5, "qa", 22.0, math.nan, 23.0, parent=1, status="crashed")
    qa = rec(2, "qa", 21.0, 30.0, 50.0, phases=[(30.0, "user_exec")], parent=1)
    line = progtrace.critical_path(instance(0, 1.0, [sort, retry, qa]), "qa", "sort")
    assert line == ("late 1.000; "
                    "sort#1 attempt 0 queue 3.000 [unwrap 3.000 user_exec "
                    "12.000 invoke 5.000]; hop 1.000; "
                    "qa#2 attempt 0 queue 9.000 [user_exec 20.000]")
    # records without the link give the terminal attempt alone
    lone = rec(2, "qa", 21.0, 30.0, 50.0, phases=[(30.0, "user_exec")])
    assert "hop" not in progtrace.critical_path(
        instance(0, 1.0, [sort, lone]), "qa", "sort")


def test_critical_paths_name_the_p50_and_the_slowest_instance():
    insts = [instance(0, 1.0, [rec(1, "sort", 2.0, 5.0, 25.0),
                               rec(2, "qa", 21.0, 30.0, 50.0, parent=1)]),
             instance(1, 10.0, [rec(6, "sort", 11.0, 12.0, 20.0),
                                rec(7, "qa", 21.0, 22.0, 30.0, parent=6)]),
             instance(2, 20.0, [rec(8, "sort", 21.0, 22.0, 23.0)]),
             instance(3, 0.0, [rec(9, "sort", 1.0, 1.0, 2.0),
                               rec(10, "qa", 3.0, 4.0, 101.0, parent=9)])]
    lines = progtrace.critical_paths(window(insts, t0=0.0, t1=1000.0), "sort")
    assert [ln.split(":")[0] for ln in lines] == [
        "critical path, p50 instance 0 (makespan 49.000 ms)",
        "critical path, slowest instance 3 (makespan 101.000 ms)"]
    assert progtrace.critical_paths(window([]), "sort") == []


def test_datastore_counts_per_instance_include_gc():
    w = window([instance(0, DUE_MS, [SORT, QA])], gc=[GC])
    assert progtrace.ds_per_wf(w, lambda r: r.ds_reads + r.ds_writes) == 9
    assert progtrace.ds_per_wf(w, lambda r: r.ds_ms) == pytest.approx(0.875)
    # records of a runner that keeps no counters give no number
    bare = SimpleNamespace(function="qa", status="done", t_end=50.0, exec_id=2)
    w = window([instance(0, DUE_MS, [bare])])
    assert progtrace.ds_per_wf(w, lambda r: r.ds_reads) is None


NEW = {"device_idle_in_flight.lat": "qa-mamba2-370m.short-burst",
       "device_idle_in_flight.tput": "qa-yi-9b.longdoc-closed8",
       "ds_ops_per_wf": "qa-mamba2-370m.short-burst",
       "ds_ms_per_wf": "qa-mamba2-370m.short-burst"}


@pytest.mark.parametrize("name", sorted(NEW))
def test_new_readers_load_through_the_spec(name, capsys):
    cell = spec.load_cell(NEW[name])
    (metric,) = [m for m in cell.per_layer if m.name == name]
    other = {c for c in NEW.values() if c != NEW[name]}
    assert all(name not in {m.name for m in spec.load_cell(c).per_layer}
               for c in other)
    run = SimpleNamespace(window=window([instance(0, DUE_MS, [SORT, QA])], gc=[GC]),
                          trace=None, entry="sort")
    value = metric.read(run)
    if name.startswith("ds_ops"):
        assert value == 9
    elif name.startswith("ds_ms"):
        assert value == pytest.approx(0.875)
    else:
        assert value is None                         # no trace, no number
        assert "bench: critical path, p50 instance 0" in capsys.readouterr().err
