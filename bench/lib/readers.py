"""Readings that more than one per-layer metric takes, each metric for the
end-to-end metric of its own cells."""
from __future__ import annotations

from lib.trace import busy_s


def mean_step_ms(layer):
    """Mean milliseconds of one ``ScoringEngine.step`` that ended inside
    the traced part of the window (the benchmark's span around each step,
    host clock). Steps after it are left out: stopping the profiler blocks
    the loop for seconds, and the backlog that piles up meanwhile would
    put a load on the steps that the cell never offers."""
    steps = [s for s in layer["steps"] if s[1] <= layer["traced_s"]]
    if not steps:
        return None
    return 1e3 * sum(s1 - s0 for s0, s1, _ in steps) / len(steps)


def idle_share(layer):
    """Share of the traced window in which no operation ran on the device
    (one minus the union of operation intervals), in %."""
    window = (layer["hi"] - layer["lo"]) / 1e9
    return 100.0 * (1.0 - busy_s(layer["trace"], layer["lo"], layer["hi"])
                    / window)
