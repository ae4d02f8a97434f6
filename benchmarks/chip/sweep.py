#!/usr/bin/env python3
"""Find the knee of a serving cell once, on the chip: one process serves
the cell's open-loop mix at each given rate in turn and prints, per rate,
the queue at the window's start and end (a queue that grows all through
the window is past the knee), the 90th-percentile time to first token and
the mean number of busy slots.

    python benchmarks/chip/sweep.py --workload minitron-8b.chat \\
        --seconds 51 --seed 5 --rates 2.0 2.4 2.8 3.2 3.6

The chosen rate, about four fifths of the knee, goes into the mix file.
"""
from __future__ import annotations

import argparse
import copy
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(1, str(Path(__file__).resolve().parents[2] / "src"))

from chipbench import device, serve, spec  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = spec.find_cell(args.workload)
    devices = device.require_chips(cell.chips)
    from run import prepare_jax
    prepare_jax()
    drv = serve.Driver(cell, args.seconds, args.seed, devices=devices,
                       counter=device.CompileCounter())
    drv.setup()
    for rate in args.rates:
        drv.mix = copy.deepcopy(cell.traffic)
        drv.mix["arrivals"]["rate_per_s"] = rate
        rec = drv.run(trace=False)
        e2e = serve.end_to_end(rec, args.seconds)
        print("rate: " + json.dumps(dict(rate_per_s=rate, **e2e,
                                         **serve.counts(rec),
                                         **serve.summary(rec))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
