"""ds_ops_per_wf (ops): mean over the completed instances due in the window
of the datastore effects their attempts performed (ExecutionRecord
ds_reads + ds_writes, GC attempts included)."""

from harness import progtrace


def read(run):
    return progtrace.ds_per_wf(run.window, lambda r: r.ds_reads + r.ds_writes)
