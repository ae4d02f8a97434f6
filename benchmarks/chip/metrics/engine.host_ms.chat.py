"""Milliseconds per engine step in which the chip ran nothing while the
host worked (the engine's own host work and the benchmark's loop): the
traced window's device idle time over the engine steps traced.  Only
durations are used, so the host and device clocks need not agree."""
from chipbench import view


def read(run):
    spans = view.traced_step_spans(run)
    if not spans or not run.trace.device_planes:
        return None
    return (run.trace.window_s - run.trace.busy_s()) / len(spans) * 1e3
