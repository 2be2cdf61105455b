"""What LocalRunner's execution records say about causality and datastore
work: the ``parent`` link of each attempt (along a sequence, across a Map
fan-out, through redelivery) and the per-attempt datastore counters
(``ds_reads``, ``ds_writes``, ``ds_ms``), pinned for the QA workflow."""

import sys

import pytest

from repro.backends import shim
from repro.backends.localjax import LocalExecution, LocalRunner
from repro.backends.shim import Workload
from repro.core import workflow as wf
from repro.core.subgraph import GC_FUNCTION, WorkflowSpec

AWS = "aws/lambda"
ALI = "aliyun/fc"


def _run(spec, runner=None, event=0):
    runner = runner or LocalRunner(concurrency=8)
    dep = wf.deploy(runner, spec)
    wid = dep.start(event)
    runner.run(timeout_s=60.0)
    return runner, dep.executions(wid)


def _one(recs, function, status="done"):
    got = [r for r in recs if r.function == function and r.status == status]
    assert len(got) == 1, got
    return got[0]


def test_parent_along_a_sequence():
    spec = WorkflowSpec("seq", gc=False)
    for name in ("a", "b", "c"):
        spec.function(name, AWS, workload=Workload(fn=lambda x: x))
    spec.sequence("a", "b")
    spec.sequence("b", "c")
    _, recs = _run(spec)
    a, b, c = (_one(recs, n) for n in ("a", "b", "c"))
    assert a.parent is None                      # external submit
    assert b.parent == a.exec_id and c.parent == b.exec_id


def test_parent_across_a_map_fanout_counts_every_parallel_read():
    k = 16
    spec = WorkflowSpec("map", gc=False)
    spec.function("a", AWS, workload=Workload(fn=lambda x: list(range(k))))
    spec.function("w", ALI, workload=Workload(fn=lambda x: x + 1))
    spec.function("agg", AWS, workload=Workload(fn=sum))
    spec.map("a", "w")
    spec.fanin(["w"], "agg")
    _, recs = _run(spec)
    a, agg = _one(recs, "a"), _one(recs, "agg")
    ws = [r for r in recs if r.function == "w"]
    assert len(ws) == k and {w.parent for w in ws} == {a.exec_id}
    # the peer whose bitmap update completed the group invoked the aggregator
    (last,) = [w for w in ws if "invoke" in [p for _, p in w.phases]]
    assert agg.parent == last.exec_id
    # its output checkpoint read, then one read per peer on k threads
    assert (agg.ds_reads, agg.ds_writes) == (1 + k, 1)
    assert all((w.ds_reads, w.ds_writes) == (2, 5 if w is last else 4)
               for w in ws)


def test_parallel_datastore_effects_lose_no_count():
    runner = LocalRunner()
    ds = next(d for d, s in runner.stores.items() if s.kind == "table")
    runner.stores[ds].create_if_absent("k", 1)
    rec = shim.ExecutionRecord(0, "x", AWS, 0.0)
    ex = LocalExecution(runner, shim.Deployment("x", AWS, handler=lambda e: iter(())),
                        runner.faas[AWS], rec)
    width, rounds = 32, 20          # more threads than cores
    before = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)      # switch threads often, so races show
    try:
        for _ in range(rounds):
            got = runner._apply(ex, shim.Parallel(
                [shim.DsGet(ds, "k")] * width
                + [shim.DsCreate(ds, "k", 2)] * width))
            assert got == [1] * width + [False] * width
    finally:
        sys.setswitchinterval(before)
    assert (rec.ds_reads, rec.ds_writes) == (width * rounds, width * rounds)
    assert rec.ds_ms > 0


def _crash_first_attempt_of(function):
    def crash(ex, effect):
        return (ex.record.function == function and ex.record.attempt == 0
                and isinstance(effect, shim.DsCreate))
    return crash


def test_redelivered_attempt_keeps_its_parent():
    spec = WorkflowSpec("retry", gc=False)
    spec.function("a", AWS, workload=Workload(fn=lambda x: x))
    spec.function("b", ALI, workload=Workload(fn=lambda x: x))
    spec.sequence("a", "b")
    runner = LocalRunner(retry_backoff_ms=1.0)
    runner.crash_policy = _crash_first_attempt_of("b")
    _, recs = _run(spec, runner)
    a = _one(recs, "a")
    crashed, done = _one(recs, "b", "crashed"), _one(recs, "b")
    assert (crashed.attempt, done.attempt) == (0, 1)
    assert crashed.parent == done.parent == a.exec_id
    # the crashed attempt read its output checkpoint and died before writing
    assert (crashed.ds_reads, crashed.ds_writes) == (1, 0)


def test_dropped_invocation_keeps_its_parent():
    spec = WorkflowSpec("drop", gc=False)
    spec.function("a", AWS, workload=Workload(fn=lambda x: x))
    spec.function("b", ALI, workload=Workload(fn=lambda x: x))
    spec.sequence("a", "b")
    runner = LocalRunner(max_requeues=1, retry_backoff_ms=1.0)
    runner.crash_policy = lambda ex, effect: (
        ex.record.function == "b" and isinstance(effect, shim.DsCreate))
    _, recs = _run(spec, runner)
    a = _one(recs, "a")
    bs = [r for r in recs if r.function == "b"]
    assert sorted(r.status for r in bs) == ["crashed", "crashed", "dropped"]
    assert {r.parent for r in bs} == {a.exec_id}


def qa_spec():
    """The paper's QA workflow as the benchmark deploys it: sort on
    aws/lambda, then qa on aliyun/fc_gpu, with GC."""
    spec = WorkflowSpec("qa-joint", gc=True)
    spec.function("sort", AWS, workload=Workload(
        compute_ms=400.0, out_bytes=40000, accel=False,
        fn=lambda e: {"instance": e["instance"], "prompts": [[1, 2, 3]] * 4}))
    spec.function("qa", "aliyun/fc_gpu", memory_gb=8.0, workload=Workload(
        compute_ms=1500.0, out_bytes=64,
        fn=lambda m: {"instance": m["instance"], "tokens": [[7]] * 4}))
    spec.sequence("sort", "qa")
    return spec


# The QA instance's datastore work, GC included: sort reads its output
# checkpoint and its invocation list, and writes output, invocation list
# and the append of qa's invocation; qa reads and writes its output
# checkpoint; each of the two GC attempts lists its prefix and deletes it.
QA_DS = {"sort": (2, 3), "qa": (1, 1), GC_FUNCTION: (1, 1)}
QA_DS_OPS = 11


@pytest.mark.parametrize("instances", [1, 6])
def test_qa_shape_datastore_counts(instances):
    runner = LocalRunner(concurrency=8)
    dep = wf.deploy(runner, qa_spec())
    wids = [dep.start({"instance": i}) for i in range(instances)]
    runner.run(timeout_s=60.0)
    gcs = runner.executions_of(GC_FUNCTION)
    assert len(gcs) == 2 * instances
    for wid in wids:
        recs = dep.executions(wid)
        sort, qa = _one(recs, "sort"), _one(recs, "qa")
        mine = [g for g in gcs if g.parent == qa.exec_id]
        assert sort.parent is None and qa.parent == sort.exec_id
        assert len(mine) == 2
        for r in [sort, qa] + mine:
            assert (r.ds_reads, r.ds_writes) == QA_DS[r.function], r.function
            assert r.ds_ms > 0
        assert sum(r.ds_reads + r.ds_writes for r in [sort, qa] + mine) == QA_DS_OPS
