"""What a per-layer metric's reader is given: one run's record, its trace,
the configuration and the chip's peaks, with the reductions that several
readers share.  A reader that finds nothing to read returns ``None``."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

from chipbench import flops


@dataclasses.dataclass
class RunView:
    cell: Any                      # chipbench.spec.Cell
    record: Any                    # ServeRecord or TrainRecord of the window
    trace: Any                     # chipbench.trace.Trace, or None
    peaks: Dict[str, Any]
    chips: int

    @property
    def cfg(self):
        return self.cell.config.model


# -- serving -------------------------------------------------------------------

def engine_step_ms(run: RunView) -> Optional[float]:
    """Mean host ms of one ``step_once`` over the measured window."""
    steps = run.record.window_steps()
    if not steps:
        return None
    return sum(e - s for s, e, _ in steps) / len(steps) * 1e3


def trace_spans(run: RunView, name: str) -> List:
    """The trace's annotations called ``name`` (trace clock)."""
    if run.trace is None or run.trace.window is None:
        return []
    return [a for a in run.trace.annotations if a.name == name]


def traced_step_spans(run: RunView) -> List:
    return trace_spans(run, "bench.engine_step")


def decode_runs(run: RunView) -> List:
    """Runs of the decode program wholly inside the traced window on the
    first chip: the program that took most device time there."""
    t = run.trace
    if t is None or not t.device_planes:
        return []
    per_name: Dict[str, float] = {}
    for m in t.modules(t.device_planes[0]):
        per_name[m.name] = per_name.get(m.name, 0.0) + m.dur
    if not per_name:
        return []
    top = max(per_name, key=per_name.get)
    return t.module_runs(lambda n: n == top)


def decode_ms(run: RunView) -> Optional[float]:
    """Mean device ms of operations inside one run of the decode program."""
    runs = decode_runs(run)
    return run.trace.module_busy_s(runs) / len(runs) * 1e3 if runs else None


def traced_contexts(run: RunView) -> List[List[int]]:
    """Per traced engine step, the live slots' attended positions."""
    return [pos for _, _, pos in run.record.traced_steps]


def decode_flops(run: RunView) -> float:
    """FLOPs the traced engine steps need for their live contexts."""
    return sum(flops.dense_decode_flops(run.cfg, c)
               for c in traced_contexts(run))


def decode_roofline_ms(run: RunView) -> Optional[float]:
    """Least time the chip could take for one traced decode step, on
    average: per step the larger of FLOPs over peak and bytes over
    bandwidth."""
    ctx, pk = traced_contexts(run), run.peaks
    if not ctx:
        return None
    return sum(max(flops.dense_decode_flops(run.cfg, c)
                   / pk["bf16_flops_per_s"],
                   flops.dense_decode_bytes(run.cfg, c)
                   / pk["hbm_bytes_per_s"]) for c in ctx) / len(ctx) * 1e3


def idle_pct(run: RunView) -> Optional[float]:
    t = run.trace
    if t is None or t.window is None or not t.device_planes:
        return None
    return (1.0 - t.busy_s() / t.window_s) * 100.0


# -- training ------------------------------------------------------------------

def train_step_ms(run: RunView) -> Optional[float]:
    """Mean host ms of a window step at the configuration's full width."""
    full = run.cell.config.meta["train"]["mesh"]["data"]
    ms = [(e - s) * 1e3 for s, e, dp, _, _ in run.record.window_steps()
          if dp == full]
    return sum(ms) / len(ms) if ms else None
