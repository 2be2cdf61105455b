"""makespan_p50_ms (ms): median, over the instances due in the window, of
due time to the commit of their last output (GC excluded)."""

from harness import records


def read(run):
    return records.percentile(records.makespans_ms(run.window), 0.5)
