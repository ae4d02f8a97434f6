"""Whole-step model FLOP utilisation of serving: the FLOPs the traced
decode steps need for their live contexts (flops.dense_decode_flops), over
the traced steps' wall time on the benchmark's clock (first step's start
to last step's end, the loop between steps included) and the chip's bf16
peak."""
from chipbench import view


def read(run):
    steps = run.record.traced_steps
    if not steps:
        return None
    wall = steps[-1][1] - steps[0][0]
    return view.decode_flops(run) / (wall * run.peaks["bf16_flops_per_s"]) \
        * 100.0
