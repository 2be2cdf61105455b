"""orch_ms_per_wf (ms): mean over the completed instances due in the
window of the attempt time spent outside the user functions (Trace phases
of ExecutionRecord: everything but user_exec)."""

from harness import records


def read(run):
    w = run.window
    xs = [records.orchestration_ms(i) for i in w.due_in_window()
          if records.completed_ms(i.records, w.terminal) is not None]
    return sum(xs) / len(xs) if xs else None
