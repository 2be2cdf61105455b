"""The trace reduction: on hand-built events, and on a small trace
recorded on a TPU v5e (one qa call of the mamba2-370m stage at the
short-burst cell's shapes, 4 x 128 tokens and 16 new,
``bench/tests/data/small_trace.xplane.pb.xz``)."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import devtrace  # noqa: E402
from harness.devtrace import TraceEvents  # noqa: E402

RECORDED = os.path.join(HERE, "data", "small_trace.xplane.pb.xz")


def events():
    # window [0, 100); device ops cover [10,30) ∪ [25,40) ∪ [60,70) = 40;
    # a program run [10, 40) and one that sticks out of the window
    return TraceEvents(
        ops={"/device:TPU:0": [("%while.3 = (s32[]) while()", 10, 40),
                               ("fusion.1", 10, 30), ("fusion.2", 25, 40),
                               ("copy", 60, 70), ("late", 95, 120)]},
        modules={"/device:TPU:0": [("jit_decode_step(7)", 10, 40),
                                   ("jit_decode_step(7)", 95, 120)]},
        spans=[(devtrace.WINDOW_OPEN, 0, 0), (devtrace.WINDOW_CLOSE, 100, 100),
               ("bench.qa.decode_loop", 0, 50),
               ("bench.qa.to_host", 45, 80)])


def test_union_merges_overlaps():
    assert devtrace.union([(5, 8), (1, 3), (2, 4), (8, 9), (7, 7)]) == \
        [(1, 4), (5, 9)]


def test_busy_and_window():
    s = devtrace.summarize(events())
    assert s.window_s == pytest.approx(100e-9)
    # [10,40) + [60,70) + [95,100) clipped at the window's close
    assert s.busy_s == pytest.approx(45e-9)


def test_op_names_drop_layouts():
    assert devtrace.op_name("%c.5 = bf16[48,1024]{1,0:T(8,128)(2,1)} "
                            "convert(f32[48,1024]{1,0:T(8,128)} %p)") == \
        "%c.5 = bf16[48,1024] convert(f32[48,1024] %p)"


def test_ops_ranked_by_device_time_without_loops():
    s = devtrace.summarize(events())
    assert [n for n, _ in s.device_ops] == ["fusion.1", "fusion.2", "copy", "late"]
    assert s.device_ops[0][1] == pytest.approx(20e-9)


def test_program_runs_inside_the_window_only():
    assert devtrace.summarize(events()).module_seconds("jit_decode_step") == \
        [pytest.approx(30e-9)]


def test_idle_gaps_by_the_spans_open_meanwhile():
    got = dict(devtrace.summarize(events()).idle_gaps)
    # idle: [0,10) decode_loop; [40,45) decode_loop; [45,50) both;
    # [50,60) and [70,80) to_host; [80,95) none
    assert got["bench.qa.decode_loop"] == pytest.approx(15e-9)
    assert got["bench.qa.decode_loop+bench.qa.to_host"] == pytest.approx(5e-9)
    assert got["bench.qa.to_host"] == pytest.approx(20e-9)
    assert got[devtrace.NO_SPAN] == pytest.approx(15e-9)
    assert sum(got.values()) == pytest.approx(55e-9)


def test_no_window_or_no_device_gives_nothing():
    ev = events()
    ev.spans = ev.spans[2:]
    assert devtrace.summarize(ev) is None
    assert devtrace.summarize(TraceEvents(spans=events().spans)) is None


def test_recorded_tpu_trace():
    s = devtrace.summarize(devtrace.read_events(RECORDED))
    assert s is not None
    assert 0 < s.busy_s < s.window_s
    decode = s.module_seconds("jit_decode_step")
    prefill = s.module_seconds("jit_prefill")
    assert len(decode) == 15 and len(prefill) == 1
    assert all(0 < t < s.window_s for t in decode + prefill)
    spans = {n for n, _ in s.idle_gaps}
    assert spans & {"bench.qa.decode_loop", "bench.qa.prefill"}
