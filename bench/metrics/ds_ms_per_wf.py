"""ds_ms_per_wf (ms): mean over the completed instances due in the window
of the time their attempts spent performing datastore effects, lock waits
included (ExecutionRecord ds_ms, GC attempts included)."""

from harness import progtrace


def read(run):
    return progtrace.ds_per_wf(run.window, lambda r: r.ds_ms)
