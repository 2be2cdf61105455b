"""Per-layer numbers that more than one reader shares.

Operations and bytes come from the cell's counts module
(``spec.counts_of``: ``bench/flops.py`` unless the configuration file names
its own), so a configuration with layers of its own brings their counts
and its roofline readers stay three lines."""

from __future__ import annotations

from typing import Optional

DECODE_PROGRAM = "jit_decode_step"     # the program's jitted decode step


def stage_mfu(run) -> Optional[float]:
    """Model operations of the ``qa`` calls that ended inside the window,
    over the window's length times the chip's bf16 peak, in %."""
    w = run.window
    calls = [c for c in w.stage.calls if w.t0_ms <= c[2] <= w.t1_ms]
    if not calls:
        return None
    ops = len(calls) * run.cell.counts.request_flops(run.model, run.request)
    peak = run.peaks()["bf16_flops_per_s"] * run.cell.chips
    return 100.0 * ops / (w.seconds * peak)


def decode_roofline(run) -> Optional[float]:
    """Least time a decode step could take (the bytes it must move at peak
    HBM bandwidth) over the mean device time of the decode program's runs
    in the trace, in %.

    A step needs each weight matrix in the dtype it multiplies in, so the
    bytes count matrices at the compute dtype even where the program keeps
    a wider master copy: reading and casting that copy on every step is
    time the program spends, not bytes the model needs
    (``flops.decode_weight_bytes`` in the default counts)."""
    if run.trace is None:
        return None
    times = run.trace.module_seconds(DECODE_PROGRAM)
    if not times:
        return None
    need = run.cell.counts.mean_decode_bytes(run.model, run.request) \
        / run.peaks()["hbm_bytes_per_s"]
    return 100.0 * need / (sum(times) / len(times))


def device_idle_share(run) -> Optional[float]:
    """Share of the traced window in which no operation ran on the device."""
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
