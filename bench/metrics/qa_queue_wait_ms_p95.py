"""qa_queue_wait_ms_p95 (ms): 95th percentile of t_start - t_queued of the
qa stage's attempts in the window: the wait for a slot of its FaaS pool."""

from harness import records


def read(run):
    return records.percentile(records.queue_wait_ms(run.window, "qa"), 0.95)
