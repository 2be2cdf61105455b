"""device_idle_in_flight (%): share of the traced window in which no
operation ran on the device while some attempt of the window's instances
was queued or running (its records laid on the trace's clock).  Also
writes the idle time by program interval and the critical paths of the
window's p50 and slowest instance to standard error."""

from harness import progtrace


def read(run):
    return progtrace.device_idle_in_flight(run)
