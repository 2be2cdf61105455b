"""throughput_wf_s (wf/s): instances whose terminal output was committed
inside the window, over the window's seconds."""

from harness import records


def read(run):
    w = run.window
    done = [records.completed_ms(i.records, w.terminal) for i in w.instances]
    n = sum(1 for t in done if t is not None and w.t0_ms <= t <= w.t1_ms)
    return n / w.seconds if n else None
