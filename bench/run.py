"""One run of one benchmark cell, from the root of a checkout:

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json``; see ``bench/harness/cli.py`` for what a run does.
"""

import time

T_START = time.monotonic()      # set-up is timed from here

import os  # noqa: E402
import sys  # noqa: E402

_BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [_BENCH, os.path.join(os.path.dirname(_BENCH), "src")]

from harness import cli  # noqa: E402

if __name__ == "__main__":
    sys.exit(cli.main(t_start=T_START))
