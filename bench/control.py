"""Readings for a cell's correctness limit, in one process on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed: the cell's set-up and a short window at its own load, then
the check against the cell's limit file, with the control judged in the
program's place (``harness.check``).  One line of JSON per seed: each
verdict, ``correct`` and every number compared beside its limit.  The
program's widest gap gives the limit's lower reading (the largest over a
dozen seeds or more), the control's its upper one (the smallest over three
seeds or more).  Exits 1 when a control comes out correct or a sound run
does not: the limit then separates nothing.
"""

import os
import sys

sys.path[:0] = [os.path.dirname(os.path.abspath(__file__)),
                os.path.join(os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "src")]

import argparse  # noqa: E402
import json  # noqa: E402

import jax  # noqa: E402

from harness import check, cli  # noqa: E402
from harness.spec import load_cell  # noqa: E402


def main(argv=None, *, platform: str = "tpu", root=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    if jax.devices()[0].platform != platform:
        print(f"control: no {platform} device", file=sys.stderr)
        return 2
    cli.setup_jax()
    cell = load_cell(args.workload, *([root] if root else []))
    rc = 0
    for seed in [int(s) for s in args.seeds.split(",")]:
        driver = cli.prepare(cell, seed)
        w = driver.window(cell.traffic["arrivals"], seed, args.seconds)
        v = check.check(w, cell.config["workflow"]["functions"][0]["name"],
                        seed, cell.limits, cell.reference, control=True)
        if v.control.correct or not v.correct:
            rc = 1
        print(json.dumps({"seed": seed, "instances": len(w.instances),
                          "program": {"correct": v.correct, **v.numbers},
                          "control": {"correct": v.control.correct,
                                      **v.control.numbers}}), flush=True)
        del driver, w
    return rc


if __name__ == "__main__":
    sys.exit(main())
