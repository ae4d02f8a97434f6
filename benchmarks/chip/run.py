#!/usr/bin/env python3
"""One run of one cell of the chip benchmark.

    python benchmarks/chip/run.py --workload minitron-8b.chat --seed 7 \\
        --seconds 45 --trace 0

The cell (``BENCHMARK.json``) names a configuration (``configs/``), a
traffic mix (``traffic/``) and its per-layer metrics (``metrics/``); each
is found by that name.  The run checks for the chips the cell needs before
any work, sets up (weights from ``--seed`` on the device, every program the
window runs compiled or loaded from JAX's persistent cache), measures for
``--seconds``, then compares what the timed path produced with the plain
reference.  ``--trace 1`` runs the same cell with the profiler on over a
short steady part of the window and reports the per-layer metrics in
place of the end-to-end ones.

Earlier lines say how the run went; the numbers compared with their limits
are the last lines on standard error; the last line on standard output is
the result: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``
(and ``breakdown`` when traced), then ``check``.  Without a TPU, or with
fewer chips than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(1, str(BENCH_DIR.parents[1] / "src"))

from chipbench import device, spec  # noqa: E402

TRACE_DIR = BENCH_DIR / ".out" / "trace"


def say(tag: str, **fields):
    print(f"{tag}: {json.dumps(fields)}", flush=True)


def prepare_jax():
    """The program's own persistent compilation cache, with every program
    written to it (JAX skips those that compile in under a second), so that
    only a checkout's first run compiles."""
    import jax
    from repro.launch import compile_cache
    where = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return where


def judge(readings, limits):
    """Each compared number beside its limit; ``correct`` if all hold."""
    check = {k: {"value": readings[k], "limit": lim}
             for k, lim in limits.items()}
    ok = all(v["value"] <= v["limit"] for v in check.values())
    return ok, check


def run_cell(cell, devices, seed: int, seconds: float, trace: bool,
             t_start: float = T_START, trace_dir: Path = TRACE_DIR):
    """Set up, measure and check one cell; returns the result object."""
    from chipbench import trace as T
    from chipbench.view import RunView
    say("cache", dir=prepare_jax())
    counter = device.CompileCounter()
    kind = cell.traffic["driver"]
    if kind == "serve":
        from chipbench import serve as D
    elif kind == "train":
        from chipbench import train as D
    else:
        raise spec.SpecError(f"unknown driver {kind!r}")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        trace_dir.mkdir(parents=True)
    drv = D.Driver(cell, seconds, seed, trace_dir=trace_dir, devices=devices,
                   counter=counter)
    drv.setup()
    say("setup", **counter.snapshot(),
        seconds_before_traffic=time.perf_counter() - t_start)
    rec = drv.run(trace)
    setup_s = rec.window[0] - t_start
    say("window", compiles_in_window=rec.window_compiles,
        **D.summary(rec))
    peak = device.peak_bytes(devices)
    kind0 = devices[0].device_kind
    dev = {"platform": devices[0].platform, "kind": kind0,
           "count": len(devices), "memory_peak_bytes": peak}
    counts = D.counts(rec)
    t_check = time.perf_counter()
    readings = drv.check(rec)
    say("check", seconds=time.perf_counter() - t_check, **readings)
    ok, check = judge(readings, cell.config.meta["check"])

    metrics, breakdown = {}, None
    if trace:
        tr = T.Trace(T.load(trace_dir))
        dev["busy_s"] = tr.busy_s()
        dev["window_s"] = tr.window_s
        breakdown = tr.breakdown()
        view = RunView(cell, rec, tr, device.peaks(kind0, cell.bench_dir),
                       len(devices))
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"], cell.bench_dir)(view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = dict(D.end_to_end(rec, seconds), setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    out = {"correct": ok, "attempted": counts["attempted"],
           "failed": counts["failed"], "metrics": metrics, "device": dev}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = check
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.find_cell(args.workload)
    try:
        devices = device.require_chips(cell.chips)
    except device.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    result = run_cell(cell, devices, args.seed, args.seconds,
                      bool(args.trace))
    for name, c in result["check"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
