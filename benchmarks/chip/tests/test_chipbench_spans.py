"""The engine's spans against the device trace: the clock offset bounded by
the causal anchors, each idle interval put down to the innermost program
span, the readers of the five engine metrics, and nothing read from a
program without spans or stamps."""
import gzip
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parents[1] / "src")]

from chipbench import spans, spec  # noqa: E402
from chipbench import trace as T  # noqa: E402

DATA = BENCH / "tests" / "data"
HOST, DEV = "/host:CPU", "/device:TPU:0"
MS = 1e-3


def _host(name, s, e):
    return T.Event(HOST, "python", name, s * MS, (e - s) * MS)


def _dev(line, name, s, e):
    return T.Event(DEV, line, name, s * MS, (e - s) * MS)


# One engine step, in ms.  The device clock runs 0.2 ms ahead of the host's;
# the anchors bound the offset to [-0.1, 0.7] ms, whose midpoint is 0.3.
SYNTHETIC = [
    _host("bench.engine_step", 0.0, 10.0),
    _host("engine.step", 0.5, 9.5),
    _host("engine.admit", 1.0, 3.0),
    _host("engine.reset_slot", 1.5, 2.5),
    _host("engine.decode", 3.0, 3.5),
    _host("engine.sample", 3.5, 4.0),
    _host("engine.readback", 4.0, 8.0),
    _host("engine.emit", 8.0, 9.0),
    _host("host.gc", 8.5, 8.8),
    _dev("XLA Ops", "reset", 2.0, 2.2),
    _dev("XLA Ops", "while.1", 3.7, 7.0),
    _dev("XLA Modules", "jit_decode", 3.7, 7.0),
    _dev("XLA Ops", "argmax", 7.1, 7.9),
]
# idle (device clock) [0, 2], [2.2, 3.7], [7.0, 7.1], [7.9, 10], shifted
# by -0.3 ms and cut at the host spans' edges
EXPECTED_MS = {"unspanned": 1.0, "engine.step": 1.0, "engine.admit": 1.0,
               "engine.reset_slot": 0.8, "engine.decode": 0.4,
               "engine.readback": 0.5, "engine.emit": 0.7, "host.gc": 0.3}


def _view(events):
    return SimpleNamespace(trace=T.Trace(events))


def test_offset_is_bounded_by_the_anchors_and_idle_is_put_down_by_span():
    run = _view(SYNTHETIC)
    a = spans.attribute_run(run)
    assert a.bounds == pytest.approx((-0.1 * MS, 0.7 * MS))
    assert a.offset == pytest.approx(0.3 * MS)
    assert a.steps == 1
    assert {k: v / MS for k, v in a.idle_s.items() if v > 1e-12} == \
        pytest.approx(EXPECTED_MS)
    tr = run.trace
    assert sum(a.idle_s.values()) == pytest.approx(tr.window_s - tr.busy_s(),
                                                   abs=1e-12)
    read = {n: spec.metric_reader(n, BENCH) for n in (
        "engine.admit_idle_ms.chat", "engine.sample_idle_ms.batch",
        "engine.unspanned_idle_ms.batch")}
    assert read["engine.admit_idle_ms.chat"](run) == pytest.approx(1.8)
    # the round trip: the launch under engine.decode, the read-back's tail
    assert read["engine.sample_idle_ms.batch"](run) == pytest.approx(0.9)
    assert read["engine.unspanned_idle_ms.batch"](run) == pytest.approx(1.0)


@pytest.mark.parametrize("shift_ms", [-3.0, 0.0, 2.5])
def test_idle_sums_to_the_window_idle_whatever_the_offset(shift_ms):
    # the device clock moved against the host's: the anchors follow it
    ev = [e if e.plane == HOST else
          T.Event(e.plane, e.line, e.name, e.start + shift_ms * MS, e.dur)
          for e in SYNTHETIC]
    tr = T.Trace(ev)
    a = spans.attribute(tr, tr.module_runs(lambda n: n == "jit_decode"))
    assert sum(a.idle_s.values()) == pytest.approx(tr.window_s - tr.busy_s(),
                                                   abs=1e-12)
    assert a.offset == pytest.approx((0.3 + shift_ms) * MS)


@pytest.mark.parametrize("at", [0.0, 0.25, 0.5, 0.75, 1.0])
def test_only_the_round_trip_sum_moves_with_the_offset(at):
    """Anywhere in the feasible interval the round trip's sum, and the
    spans inside an idle gap with no operation in it, read the same; the
    offset moves the split between the decode launch and the read-back's
    tail, and the idle around the reset's operation."""
    run = _view(SYNTHETIC)
    lo, hi = spans.attribute_run(run).bounds
    runs = run.trace.module_runs(lambda n: n == "jit_decode")
    a = spans.attribute(run.trace, runs, offset=lo + at * (hi - lo))
    assert a.per_step_ms(*spans.ROUND_TRIP) == pytest.approx(0.9)
    for name in ("engine.emit", "host.gc"):
        assert a.idle_s.get(name, 0.0) / MS == pytest.approx(
            EXPECTED_MS[name])
    assert a.idle_s["engine.readback"] / MS == pytest.approx(
        0.2 - 0.1 + at * 0.8)


def _recorded(name):
    rows = json.load(gzip.open(DATA / name, "rt"))
    return T.Trace([T.Event(p, ln, n, s * 1e-9, d * 1e-9)
                    for p, ln, n, s, d in rows])


def test_a_program_without_spans_or_stamps_gives_nothing():
    run = SimpleNamespace(trace=_recorded("decode_trace.json.gz"))
    assert spans.attribute_run(run) is None
    for name in ("engine.admit_idle_ms.chat", "engine.sample_idle_ms.batch",
                 "engine.unspanned_idle_ms.batch"):
        assert spec.metric_reader(name, BENCH)(run) is None
    old = SimpleNamespace(t_submit=1.0, t_first_token=2.0)   # no t_admit
    rec = SimpleNamespace(window=(0.0, 10.0), trace_window=None,
                          tracked=[SimpleNamespace(due=1.0, req=old)])
    for name in ("engine.queue_wait_p90_s.chat", "engine.prefill_p90_s.chat"):
        assert spec.metric_reader(name, BENCH)(
            SimpleNamespace(record=rec)) is None


def _tracked(due, wait, feed):
    t = due + wait + feed
    return SimpleNamespace(due=due, admit_t=due + wait, stamps=[t],
                           req=SimpleNamespace(t_submit=due,
                                               t_admit=due + wait,
                                               t_first_token=t))


def test_stamp_percentiles_cover_the_requests_due_in_the_window():
    tracked = [_tracked(-1.0, 9.0, 9.0)] + \
        [_tracked(float(i), 0.1 * i, 1.0 + i) for i in range(10)] + \
        [_tracked(10.0, 9.0, 9.0)]
    run = SimpleNamespace(record=SimpleNamespace(
        window=(0.0, 10.0), trace_window=None, tracked=tracked))
    # due in [0, 10): waits 0.0 .. 0.9 and feeds 1 .. 10; the 90th
    # percentile lies a tenth of the way from the 9th to the 10th
    assert spec.metric_reader("engine.queue_wait_p90_s.chat", BENCH)(run) \
        == pytest.approx(0.81)
    assert spec.metric_reader("engine.prefill_p90_s.chat", BENCH)(run) \
        == pytest.approx(9.1)


def test_a_traced_run_leaves_the_profiler_pause_out():
    # the last step before the session ends at 20; starting the session
    # holds the loop to 21; three traced steps run 21-24; stopping it holds
    # the loop to 34, and the queue it leaves lasts past that
    steps = [(19.0, 20.0, 1), (21.0, 22.0, 1), (22.0, 23.0, 1),
             (23.0, 24.0, 1), (34.0, 35.0, 1)]
    tracked = [_tracked(1.0, 0.0, 1.0),     # before the session
               _tracked(17.0, 2.0, 0.5),    # queued 17-19, fed 19-19.5
               _tracked(18.0, 0.5, 17.5),   # fed 18.5-36: 11 s of it held,
                                            # the traced steps not
               _tracked(19.8, 1.7, 2.0),    # queued 19.8-21.5, 1 s held
               _tracked(25.0, 5.0, 1.0),    # due in the stop's hold
               _tracked(35.0, 2.0, 3.0),    # queued behind it
               SimpleNamespace(due=19.5, admit_t=None, stamps=[],
                               req=SimpleNamespace(t_submit=19.5,
                                                   t_admit=None,
                                                   t_first_token=None))]
    rec = SimpleNamespace(window=(0.0, 40.0), trace_window=(20.5, 33.5),
                          steps=steps, tracked=tracked,
                          traced_steps=[(s, e, [1]) for s, e, _ in
                                        steps[1:4]])
    assert spans.profiler_holds(rec) == [(20.0, 21.0), (24.0, 34.0)]
    run = SimpleNamespace(record=rec)
    # queue waits 0, 2, 0.5, 0.7; prompt feeds 1, 0.5, 17.5 - 11, 2
    assert spans.stamp_p90(run, "t_submit", "t_admit") == \
        pytest.approx(0.7 + 0.7 * 1.3)
    assert spans.stamp_p90(run, "t_admit", "t_first_token") == \
        pytest.approx(2.0 + 0.7 * 4.5)
    untraced = SimpleNamespace(record=SimpleNamespace(
        window=(0.0, 40.0), trace_window=None, tracked=tracked))
    # every request due in the window: waits 0, 0.5, 1.7, 2, 2, 5
    assert spans.stamp_p90(untraced, "t_submit", "t_admit") == \
        pytest.approx(2.0 + 0.5 * 3.0)


def test_recorded_chip_trace_has_a_feasible_offset():
    """Three engine steps of minitron-8b.chat on one TPU v5e, one of them
    admitting a request, with the engine's spans: the anchors leave a
    non-empty interval, and every idle nanosecond is put down to a span or
    to none."""
    tr = _recorded("span_trace.json.gz")
    a = spans.attribute(tr, tr.module_runs(
        lambda n: n.startswith("jit__lambda")))
    lo, hi = a.bounds
    assert lo is not None and hi is not None and lo <= hi
    assert abs(a.offset) < 2e-3
    assert a.steps == 3
    assert sum(a.idle_s.values()) == pytest.approx(tr.window_s - tr.busy_s(),
                                                   abs=1e-9)
    assert a.idle_s["engine.reset_slot"] > 0.0
