"""arrival_lateness_ms_p95 (ms): 95th percentile of how late the entry
invocation was queued after it was due (the client and the runner share
the host)."""

from harness import records


def read(run):
    return records.percentile(records.lateness_ms(run.window, run.entry), 0.95)
