"""Whole-step model FLOP utilisation of training at the full data width:
the forward and backward FLOPs the configuration needs per step
(flops.ssd_train_flops_per_token, recomputation not counted), over the
mean host time of a step, the chips in use and the chip's bf16 peak."""
from chipbench import train, view


def read(run):
    ms = view.train_step_ms(run)
    if ms is None:
        return None
    f = train.flops_per_step(run.cfg, run.cell.config.meta["train"])
    return f / (ms * 1e-3 * run.chips * run.peaks["bf16_flops_per_s"]) * 100.0
