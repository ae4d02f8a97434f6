"""The trace reduction on a small recorded chip trace: two engine steps of
minitron-8b.chat on one TPU v5e (decode program, a slot reset and the
sampling ops), checked against numbers worked out by hand from a
nanosecond timeline of the same events."""
import gzip
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parents[1] / "src")]

from chipbench import trace as T  # noqa: E402

# from a 1-ns boolean timeline of the fixture's "XLA Ops" events
WINDOW_NS = 81_911_969
BUSY_NS = 72_665_204
LONGEST_GAPS_NS = [3_576_100, 2_230_966, 1_550_242]


@pytest.fixture(scope="module")
def recorded():
    rows = json.load(gzip.open(BENCH / "tests" / "data" /
                               "decode_trace.json.gz", "rt"))
    return T.Trace([T.Event(p, ln, n, s * 1e-9, d * 1e-9)
                    for p, ln, n, s, d in rows])


def test_busy_and_window_match_the_hand_count(recorded):
    assert recorded.device_planes == ["/device:TPU:0"]
    assert recorded.window_s == pytest.approx(WINDOW_NS * 1e-9, abs=2e-9)
    assert recorded.busy_s() == pytest.approx(BUSY_NS * 1e-9, abs=2e-9)


def test_idle_gaps_are_the_longest_and_named_by_the_host(recorded):
    gaps = recorded.idle_gaps(3)
    assert [g[1] for g in gaps] == pytest.approx(
        [g * 1e-9 for g in LONGEST_GAPS_NS], abs=2e-9)
    assert all(name.startswith("bench.") or name.startswith("host:")
               for name, _ in gaps)


def test_per_op_seconds_add_up_to_the_op_time(recorded):
    per_op = recorded.op_seconds()
    lo, hi = recorded.window
    total = sum(min(e.end, hi) - max(e.start, lo) for e in recorded.ops())
    assert sum(per_op.values()) == pytest.approx(total, rel=1e-9)
    # the layer loop of the decode program dominates a decode step
    top = max(per_op, key=per_op.get)
    assert top.startswith("while")
    assert all(" = " not in k for k in per_op)


def test_only_program_runs_wholly_inside_the_window_count(recorded):
    # three decode runs overlap the two steps; one lies wholly inside
    runs = recorded.module_runs(lambda n: n.startswith("jit__lambda"))
    assert len(runs) == 1
    busy = recorded.module_busy_s(runs)
    assert 0 < busy <= sum(r.dur for r in runs) + 1e-12


def test_synthetic_overlaps_and_clipping():
    ev = [T.Event("/host:CPU", "python", "bench.engine_step", 1.0, 1.0),
          T.Event("/device:TPU:0", "XLA Ops", "a", 0.5, 0.7),   # clipped
          T.Event("/device:TPU:0", "XLA Ops", "b", 1.1, 0.2),   # inside a
          T.Event("/device:TPU:0", "XLA Ops", "c", 1.6, 0.1),
          T.Event("/device:TPU:1", "XLA Ops", "c", 1.0, 0.5),
          T.Event("/device:TPU:0", "XLA Ops", "d", 2.5, 0.3)]   # outside
    tr = T.Trace(ev)
    assert tr.window == (1.0, 2.0)
    # chip 0: [1.0, 1.3] and [1.6, 1.7] -> 0.4; chip 1: 0.5
    assert tr.busy_s() == pytest.approx(0.45)
    assert tr.op_seconds() == pytest.approx({"a": 0.1, "b": 0.1,
                                             "c": 0.3})
    gaps = tr.idle_gaps()
    assert [n for n, _ in gaps] == ["bench.engine_step"] * 2
    assert [g for _, g in gaps] == pytest.approx([0.3, 0.3])
    assert T.short_name("%fusion.3 = bf16[2,4]{1,0:T(8,128)} fusion(x), "
                        "kind=kLoop") == "fusion.3 bf16[2,4] fusion"
    assert T.short_name("%while.1 = (s32[], bf16[2]{0}) while(t)") == \
        "while.1"
