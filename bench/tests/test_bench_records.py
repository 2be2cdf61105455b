"""The reductions from execution records to metrics, on hand-built
records, and the traffic generator's schedules."""

import math
import os
import sys
from types import SimpleNamespace

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import driver, records, traffic  # noqa: E402
from harness.driver import Instance  # noqa: E402
from repro.backends.shim import ExecutionRecord  # noqa: E402


def rec(fn, faas, q, s, e, status="done", phases=(), result=None):
    r = ExecutionRecord(0, fn, faas, t_queued=q, t_start=s, t_end=e,
                        status=status, result=result)
    r.phases = list(phases)
    return r


def instance(i, due, recs):
    inst = Instance(i, due, f"wf-{i}")
    inst.records = recs
    return inst


def window(insts, t0=0.0, t1=1000.0):
    return SimpleNamespace(instances=insts, t0_ms=t0, t1_ms=t1, terminal="qa",
                           seconds=(t1 - t0) / 1e3,
                           due_in_window=lambda: [i for i in insts
                                                  if t0 <= i.due_ms < t1])


A = [rec("sort", "aws/lambda", 105.0, 106.0, 116.0,
         phases=[(106.0, "unwrap"), (107.0, "user_exec"), (113.0, "invoke")]),
     rec("qa", "aliyun/fc_gpu", 117.0, 125.0, 425.0,
         phases=[(125.0, "unwrap"), (126.0, "user_exec"), (420.0, "output_ckp")]),
     rec("__gc__", "aws/lambda", 430.0, 431.0, 900.0)]
B = [rec("sort", "aws/lambda", 203.0, 203.0, 210.0),
     rec("qa", "aliyun/fc_gpu", 211.0, 212.0, 260.0, status="crashed"),
     rec("qa", "aliyun/fc_gpu", 290.0, 300.0, 700.0)]
C = [rec("sort", "aws/lambda", 950.0, 951.0, 960.0)]          # never finished


def test_makespan_runs_from_due_time_to_last_done_output_without_gc():
    a, b, c = instance(0, 100.0, A), instance(1, 200.0, B), instance(2, 940.0, C)
    assert records.makespan_from_due_ms(a, "qa") == 425.0 - 100.0
    assert records.makespan_from_due_ms(b, "qa") == 700.0 - 200.0
    assert records.makespan_from_due_ms(c, "qa") is None
    assert records.makespans_ms(window([a, b, c])) == [325.0, 500.0]


def test_percentile_is_nearest_rank():
    xs = list(range(1, 21))
    assert records.percentile(xs, 0.5) == 11
    assert records.percentile(xs, 0.95) == 19          # round(0.95 * 19) = 18
    assert records.percentile([], 0.95) is None


def test_lateness_and_queue_wait():
    w = window([instance(0, 100.0, A), instance(1, 200.0, B)])
    assert records.lateness_ms(w, "sort") == [3.0, 5.0]
    assert records.queue_wait_ms(w, "qa") == [1.0, 8.0, 10.0]


def test_orchestration_time_is_attempt_time_outside_user_exec():
    a = instance(0, 100.0, A)
    # sort: 10 ms attempt, 6 in user_exec; qa: 300 ms, 294 in user_exec
    assert records.user_exec_ms(A[0]) == 6.0
    assert records.orchestration_ms(a) == (10.0 - 6.0) + (300.0 - 294.0)


def test_usd_bills_every_attempt_invocations_and_egress():
    table = records.prices()
    b = instance(1, 200.0, B)
    memory = {"sort": None, "qa": 8.0}
    got = records.usd(b, memory, 40_000, table)
    cpu = table["flavors"]["aws/lambda"]
    gpu = table["flavors"]["aliyun/fc_gpu"]
    want = (0.5 * 0.007 * cpu["price_per_gb_s"]
            + 8.0 * 0.048 * gpu["price_per_gb_s"]
            + 8.0 * 0.400 * gpu["price_per_gb_s"]
            + 3 * table["invoke_price"]
            + 40_000 / 1e9 * table["egress_price_per_gb"])
    assert got == pytest.approx(want, rel=1e-12)
    assert cpu["price_per_gb_s"] == 1.66667e-5 and gpu["memory_gb"] == 8.0


def test_payload_bytes_is_compact_json():
    assert records.payload_bytes({"a": [1, 22]}) == len('{"a":[1,22]}')


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11, 2**40 + 3])
def test_poisson_gives_every_seed_the_same_gaps(seed):
    due = traffic.schedule({"process": "poisson", "rate_wf_s": 4.0}, seed, 50)
    ref = traffic.schedule({"process": "poisson", "rate_wf_s": 4.0}, 1, 50)
    assert len(due) == len(ref) == 200
    gaps = sorted(b - a for a, b in zip([0.0] + due, due))
    ref_gaps = sorted(b - a for a, b in zip([0.0] + ref, ref))
    assert gaps == pytest.approx(ref_gaps)
    assert all(0 < t < 50 for t in due) and due == sorted(due)
    assert due[-1] == pytest.approx(ref[-1])


def test_schedules_differ_between_seeds_and_repeat_for_one():
    a = traffic.schedule({"process": "poisson", "rate_wf_s": 4.0}, 5, 20)
    assert a == traffic.schedule({"process": "poisson", "rate_wf_s": 4.0}, 5, 20)
    assert a != traffic.schedule({"process": "poisson", "rate_wf_s": 4.0}, 6, 20)


@pytest.mark.parametrize("seed", [1, 2**31 + 9, 2**40 + 5])
def test_onoff_gives_every_seed_the_same_bursts(seed):
    arr = {"process": "onoff", "period_s": 4.0, "on_s": 1.0,
           "on_rate_wf_s": 22.6}
    due, ref = traffic.schedule(arr, seed, 50), traffic.schedule(arr, 0, 50)
    assert due != ref and len(due) == len(ref) == 13 * 23
    for k in range(13):
        burst = [t for t in due if 4 * k <= t < 4 * k + 4]
        ref_burst = [t for t in ref if 4 * k <= t < 4 * k + 4]
        assert len(burst) == 23 and burst[-1] == pytest.approx(ref_burst[-1])


def test_a_mix_can_fix_its_schedule():
    arr = {"process": "poisson", "rate_wf_s": 4.0, "seed": 12}
    assert traffic.schedule(arr, 1, 20) == traffic.schedule(arr, 2**33, 20)
    assert traffic.schedule(arr, 1, 20) == traffic.schedule(
        {"process": "poisson", "rate_wf_s": 4.0}, 12, 20)


def test_closed_loop_ramp_spreads_its_starts():
    starts, lead = driver.ramp(8, 0.5)
    assert starts == [0.5 * k for k in range(8)]
    assert lead == pytest.approx(3.5 + 8 * 0.5)
    assert driver.ramp(1, 2.0) == ([0.0], 2.0)


def test_onoff_bursts():
    arr = {"process": "onoff", "period_s": 4.0, "on_s": 1.0, "on_rate_wf_s": 10}
    due = traffic.schedule(arr, 3, 12)
    assert len(due) == 30
    for k in range(3):
        burst = [t for t in due if 4 * k <= t < 4 * k + 4]
        assert len(burst) == 10 and max(burst) < 4 * k + 1.0


def test_closed_loop_has_no_schedule():
    assert traffic.schedule({"process": "closed", "in_flight": 8}, 1, 10) is None
    with pytest.raises(ValueError):
        traffic.schedule({"process": "zipf"}, 1, 10)


def test_midpoint_gaps_average_close_to_one_over_rate():
    due = traffic.schedule({"process": "poisson", "rate_wf_s": 2.0}, 0, 100)
    assert math.isclose(due[-1], 100.0, rel_tol=0.05)
