"""Benchmark aggregator — one module per paper table/figure.

    python benchmarks/run.py                      # full sim aggregation
    python benchmarks/run.py --backend local      # 4 paper workflows on the
                                                  #   concurrent local backend
    python benchmarks/run.py --backend local --smoke   # CI gate: one workflow,
                                                  #   wall budget, zero drops
    python benchmarks/run.py --backend local --open-loop [--smoke]
                                                  # Poisson arrivals on the
                                                  #   local backend, wall-clock
    python benchmarks/run.py --backend remote [--smoke]  # value-level workflows
                                                  #   on the multi-process
                                                  #   distributed substrate

The default (sim) mode prints a ``name,us_per_call,derived`` CSV line per
measurement plus the human-readable summaries each module emits; the
§Roofline/§Perf tables read ``results/dryrun.json`` (produced by
``repro.launch.dryrun --all``).  The local mode runs the same four paper
workflows end-to-end on :class:`repro.backends.localjax.LocalRunner` — real
jitted JAX callables, real thread-level ``Parallel`` fan-out — through the
identical ``core.workflow.deploy`` path, demonstrating the Backend-Shim's
portability claim (same artifact, different substrate).

The open-loop mode (``--backend local --open-loop``) is the throughput
sweep's traffic model on the *real* concurrent executor: the same
:mod:`repro.core.traffic` Poisson schedules the sim consumes in virtual
time are submitted here through the identical ``submit(t=)`` contract and
honored as wall-clock delays — overlapping workflow instances contend on
real threads.  Its ``--smoke`` variant is a CI gate: all arrivals must
complete with zero drops inside a wall budget.

The remote mode (``--backend remote``) drives *value-level* workflows (no
JAX in the forked workers — the pool inherits the parent image by ``fork``,
and jitted callables don't survive that) through the same ``deploy`` path
on :class:`repro.backends.remote.RemoteRunner`: per-cloud worker process
groups, a broker queue with visibility timeouts, and WAL-backed shared
stores.  Chaos coverage for that substrate lives in
``benchmarks/remote_chaos_smoke.py``.
"""

from __future__ import annotations

import argparse
import math
import os
import sys
import time
import traceback

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)      # the 'benchmarks' package (sim aggregation)
sys.path.insert(0, _HERE)      # bare 'common' (local arm)

LOCAL_WORKFLOWS = ("video4", "qa", "iot8", "mc6")
SMOKE_WALL_BUDGET_S = 90.0

# Open-loop local traffic: modest defaults — the point is overlapping
# real-thread instances, not saturation (wall-clock arrivals make big n slow).
OPEN_LOOP_MIX = ("qa", "iot8")
OPEN_LOOP_RATE_WF_S = 6.0
OPEN_LOOP_ARRIVALS = 18
OPEN_LOOP_SEED = 7


def _local_specs(names):
    import common
    builders = {
        "video4": lambda: common.video_spec(4, "joint"),
        "qa": lambda: common.qa_spec("joint"),
        "iot8": lambda: common.iot_spec(8),
        "mc6": lambda: common.mc_spec(6),
    }
    return [(n, builders[n]()) for n in names]


def run_local(args) -> int:
    """All four paper workflows on the concurrent local backend; non-zero
    exit on drops, non-finite makespans, or (in --smoke) a blown budget."""
    import common
    names = LOCAL_WORKFLOWS[:1] if args.smoke else LOCAL_WORKFLOWS
    n = 1 if args.smoke else args.n
    failures = 0
    t0 = time.time()
    for name, spec in _local_specs(names):
        ms, runner = common.jointlambda_run_local(
            spec, n, timeout_s=args.budget_s)
        drops = runner.drop_count
        done = sum(1 for m in ms if math.isfinite(m) and m > 0)
        ok = done == len(ms) and drops == 0
        failures += 0 if ok else 1
        print(f"local,{name},p95_ms={common.p95(ms):.1f},"
              f"runs={done}/{len(ms)},drops={drops},"
              f"{'ok' if ok else 'FAIL'}")
    wall = time.time() - t0
    if args.smoke and wall > args.budget_s:
        print(f"[smoke] FAIL: wall {wall:.1f}s exceeds budget {args.budget_s:.0f}s")
        return 1
    verdict = "OK" if failures == 0 else f"{failures} FAILURES"
    print(f"local backend {'smoke ' if args.smoke else ''}done in "
          f"{wall:.1f}s: {verdict}")
    return 1 if failures else 0


def run_local_open_loop(args) -> int:
    """Open-loop Poisson traffic on the concurrent local backend: one
    shared :class:`LocalRunner`, a round-robin mix of paper workflows, and
    a :class:`repro.core.traffic.PoissonProcess` schedule whose submit
    delays the backend honors in wall-clock time.  Non-zero exit on drops,
    incomplete workflows, or (``--smoke``) a blown wall budget."""
    import common
    from repro.backends.localjax import LocalRunner
    from repro.core import traffic
    from repro.core import workflow as wf

    rate = args.rate
    n = OPEN_LOOP_ARRIVALS if args.smoke else args.arrivals
    t0 = time.time()
    runner = LocalRunner(concurrency=8)
    deps = [wf.deploy(runner, common.localize_spec(spec))
            for _, spec in _local_specs(OPEN_LOOP_MIX)]
    schedule = traffic.PoissonProcess(rate, seed=OPEN_LOOP_SEED).schedule(
        n, streams=len(deps))
    load = traffic.LoadRunner(deps, input_value=0)
    load.submit(schedule)
    load.drain(timeout_s=args.budget_s)
    point = load.collect()
    wall = time.time() - t0
    ok = point.completed == n and point.dropped == 0
    print(f"local open-loop: {n} arrivals @ {rate:.1f} wf/s over "
          f"{'/'.join(OPEN_LOOP_MIX)}: completed={point.completed}/{n} "
          f"dropped={point.dropped} p50={point.p50_ms:.0f}ms "
          f"p99={point.p99_ms:.0f}ms wall={wall:.1f}s")
    if args.smoke and wall > args.budget_s:
        print(f"[smoke] FAIL: wall {wall:.1f}s exceeds budget "
              f"{args.budget_s:.0f}s")
        return 1
    if not ok:
        print(f"[{'smoke' if args.smoke else 'open-loop'}] FAIL: "
              f"incomplete workflows or drops")
        return 1
    print(f"local open-loop {'smoke ' if args.smoke else ''}OK: "
          f"zero drops, all arrivals completed")
    return 0


REMOTE_WORKFLOWS = ("diamond", "pipeline")


def _remote_specs(names):
    """Value-level paper shapes for the multi-process substrate: pure-python
    user functions only, safe to run in ``fork``'d workers."""
    from repro.backends.shim import Workload
    from repro.core.subgraph import WorkflowSpec

    def diamond():
        spec = WorkflowSpec("r-diamond", gc=False)
        spec.function("a", "aws/lambda", workload=Workload(fn=lambda x: x))
        for i, f in enumerate(["b", "c", "d"]):
            spec.function(f, "aliyun/fc" if i % 2 else "aws/lambda",
                          workload=Workload(fn=lambda x, i=i: x + i))
        spec.function("agg", "aliyun/fc",
                      workload=Workload(fn=lambda xs: sum(xs)))
        spec.fanout("a", ["b", "c", "d"])
        spec.fanin(["b", "c", "d"], "agg")
        return spec, "agg", lambda v: 3 * v + 3

    def pipeline():
        spec = WorkflowSpec("r-pipe", gc=True)
        spec.function("a", "aws/lambda", workload=Workload(fn=lambda x: x + 1))
        spec.function("b", "aliyun/fc", workload=Workload(fn=lambda x: x * 2))
        spec.function("c", "aws/lambda", workload=Workload(fn=lambda x: x - 3))
        spec.sequence("a", "b")
        spec.sequence("b", "c")
        return spec, "c", lambda v: (v + 1) * 2 - 3

    builders = {"diamond": diamond, "pipeline": pipeline}
    return [(n, builders[n]()) for n in names]


def run_remote(args) -> int:
    """Paper-shaped value-level workflows end-to-end on the distributed
    multi-process substrate; non-zero exit on wrong results, drops, or
    (``--smoke``) a blown wall budget."""
    from repro.backends.remote import RemoteRunner
    from repro.core import workflow as wf

    names = REMOTE_WORKFLOWS[:1] if args.smoke else REMOTE_WORKFLOWS
    n = 1 if args.smoke else args.n
    failures = 0
    t0 = time.time()
    for name, (spec, terminal, expect) in _remote_specs(names):
        runner = RemoteRunner(poll_ms=5.0)
        try:
            dep = wf.deploy(runner, spec)
            wids = [dep.start(i) for i in range(n)]
            ms = runner.run(timeout_s=args.budget_s)
            done = sum(1 for i, w in enumerate(wids)
                       if dep.result_of(w, terminal) == expect(i))
            drops = runner.drop_count
        finally:
            runner.close()
        ok = done == n and drops == 0
        failures += 0 if ok else 1
        print(f"remote,{name},wall_ms={ms:.0f},runs={done}/{n},"
              f"drops={drops},{'ok' if ok else 'FAIL'}")
    wall = time.time() - t0
    if args.smoke and wall > args.budget_s:
        print(f"[smoke] FAIL: wall {wall:.1f}s exceeds budget "
              f"{args.budget_s:.0f}s")
        return 1
    verdict = "OK" if failures == 0 else f"{failures} FAILURES"
    print(f"remote backend {'smoke ' if args.smoke else ''}done in "
          f"{wall:.1f}s: {verdict}")
    return 1 if failures else 0


def run_sim() -> int:
    failures = 0
    modules = [
        ("fig15 video analytics", "benchmarks.video_analytics"),
        ("fig16 qa inference", "benchmarks.qa_inference"),
        ("fig18 failover", "benchmarks.failover"),
        ("fig19a iot sequence", "benchmarks.iot_sequence"),
        ("fig19b mc parallel", "benchmarks.mc_parallel"),
        ("fig20 overhead breakdown", "benchmarks.overhead_breakdown"),
        ("table3 cost", "benchmarks.cost_table"),
        ("kernels", "benchmarks.kernel_bench"),
    ]
    for title, modname in modules:
        print(f"\n===== {title} ({modname}) =====")
        try:
            mod = __import__(modname, fromlist=["main"])
            mod.main()
        except Exception:
            failures += 1
            traceback.print_exc()

    print("\n===== roofline (from results/dryrun.json) =====")
    try:
        from benchmarks import roofline
        data = roofline.load()
        if data:
            roofline.table(data, mesh="16x16")
            roofline.table(data, mesh="2x16x16")
            print("\n----- §Perf variants -----")
            roofline.compare(data)
    except Exception:
        failures += 1
        traceback.print_exc()
    print(f"\nbenchmarks done; {failures} module failures")
    return 1 if failures else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--backend", choices=("sim", "local", "remote"),
                    default="sim",
                    help="sim: full figure/table aggregation on SimCloud; "
                         "local: the 4 paper workflows on the concurrent "
                         "real-execution backend; remote: value-level "
                         "workflows on the multi-process distributed "
                         "substrate")
    ap.add_argument("--smoke", action="store_true",
                    help="(local/remote) CI gate: one workflow, wall budget, "
                         "zero drops")
    ap.add_argument("--n", type=int, default=3,
                    help="(local/remote) instances per workflow")
    ap.add_argument("--budget-s", type=float, default=SMOKE_WALL_BUDGET_S,
                    help="(local) wall-clock budget per run() / smoke total")
    ap.add_argument("--open-loop", action="store_true",
                    help="(local) Poisson arrivals in wall-clock time "
                         "through the shared traffic subsystem")
    ap.add_argument("--rate", type=float, default=OPEN_LOOP_RATE_WF_S,
                    help="(local --open-loop) offered load in workflows/sec")
    ap.add_argument("--arrivals", type=int, default=OPEN_LOOP_ARRIVALS,
                    help="(local --open-loop) total arrivals")
    args = ap.parse_args(argv)
    if args.backend == "local":
        from repro.compile_cache import use_compile_cache
        use_compile_cache()
        if args.open_loop:
            return run_local_open_loop(args)
        return run_local(args)
    if args.backend == "remote":
        if args.open_loop:
            ap.error("--open-loop is a local-backend mode")
        return run_remote(args)
    if args.open_loop:
        ap.error("--open-loop requires --backend local (the sim arm lives "
                 "in benchmarks/throughput_sweep.py)")
    return run_sim()


if __name__ == "__main__":
    sys.exit(main())
