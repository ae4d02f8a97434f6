#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip, at
the cell's own size: for each seed, the program's numbers beside the
control's (the reference computed in float8, the step below the bfloat16
the configurations state) and, for training, those of planted faults.

    python benchmarks/chip/control.py --workload minitron-8b.chat \\
        --seconds 10 --seeds 11 12 13

A serving cell runs a short window at its own load first (long enough to
finish its longest requests); training needs no window.  One line per
seed: ``readings: {...}``.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(1, str(Path(__file__).resolve().parents[2] / "src"))

from chipbench import device, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.find_cell(args.workload)
    devices = device.require_chips(cell.chips)
    from run import prepare_jax
    prepare_jax()
    counter = device.CompileCounter()
    for seed in args.seeds:
        if cell.traffic["driver"] == "serve":
            from chipbench import serve as D
            drv = D.Driver(cell, args.seconds, seed, devices=devices,
                           counter=counter)
            drv.setup()
            rec = drv.run(trace=False)
            r = drv.check(rec, control="fp8")
        else:
            from chipbench import train as D
            drv = D.Driver(cell, args.seconds, seed, devices=devices,
                           counter=counter)
            drv.setup()
            r = D.control_readings(drv)
        print("readings: " + json.dumps(dict(seed=seed, **r)), flush=True)
        del drv
    return 0


if __name__ == "__main__":
    sys.exit(main())
