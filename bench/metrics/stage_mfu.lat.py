"""stage_mfu (%): model operations of the qa calls that ended in the
window over the window times the bf16 peak (bench/flops.py, peaks.json)."""

from harness import layers


def read(run):
    return layers.stage_mfu(run)
