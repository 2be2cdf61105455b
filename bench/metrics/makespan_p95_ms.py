"""makespan_p95_ms (ms): 95th percentile of due time to the commit of the
last output, over the instances due in the window (GC excluded)."""

from harness import records


def read(run):
    return records.percentile(records.makespans_ms(run.window), 0.95)
