"""Find a cell's knee: the highest steady Poisson rate its deployment
sustains, at the cell's request shape, in one process on the chip.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 2,4,6

One line of JSON per rate: instances, the makespan median and 95th
percentile, the completion rate, and the median makespan of the last
quarter of arrivals over that of the first (above 1 when a queue grows
through the window).
"""

import os
import sys

sys.path[:0] = [os.path.dirname(os.path.abspath(__file__)),
                os.path.join(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "src")]

import argparse  # noqa: E402
import json  # noqa: E402
import statistics  # noqa: E402

import jax  # noqa: E402

from harness import cli, records  # noqa: E402
from harness.spec import load_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != "tpu":
        print("sweep: no TPU", file=sys.stderr)
        return 2
    cli.setup_jax()
    cell = load_cell(args.workload)
    driver = cli.prepare(cell, args.seed)
    for rate in [float(r) for r in args.rates.split(",")]:
        w = driver.window({"process": "poisson", "rate_wf_s": rate},
                          args.seed, args.seconds)
        ms = [records.makespan_from_due_ms(i, w.terminal)
              for i in w.due_in_window()]
        ok = sorted(m for m in ms if m is not None)
        q = max(1, len(ms) // 4)
        first = [m for m in ms[:q] if m is not None]
        last = [m for m in ms[-q:] if m is not None]
        print(json.dumps({
            "rate_wf_s": rate, "instances": len(ms), "completed": len(ok),
            "completed_per_s": len(ok) / ((w.drained_ms - w.t0_ms) / 1e3),
            "p50_ms": records.percentile(ok, 0.5),
            "p95_ms": records.percentile(ok, 0.95),
            "growth": (statistics.median(last) / statistics.median(first)
                       if first and last else None)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
