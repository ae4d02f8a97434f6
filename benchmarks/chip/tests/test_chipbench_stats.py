"""Exact percentiles, the seeded traffic generator, and open-loop stamping
from the due time on a stalled fake engine."""
import collections
import dataclasses
import statistics
import sys
import time
from pathlib import Path
from typing import List

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parents[1] / "src")]

from chipbench import serve, spec, stats, traffic  # noqa: E402

DATA = BENCH / "tests" / "data"


@pytest.mark.parametrize("p", [0, 10, 50, 85, 90, 99, 100])
def test_percentile_is_exact_over_every_sample(p):
    xs = np.random.default_rng(1).lognormal(size=257)
    assert stats.percentile(list(xs), p) == pytest.approx(
        float(np.percentile(xs, p)), rel=1e-12)
    assert stats.percentile([3.0, 1.0, 2.0], 50) == 2.0


def test_spread_uses_the_statistics_quartiles():
    xs = [10.0, 10.2, 9.9, 10.4, 10.1, 9.8]
    q1, q2, q3 = statistics.quantiles(xs, n=4)
    assert stats.spread(xs) == pytest.approx((q3 - q1) / q2)


def test_every_seed_gets_the_same_sizes_in_another_order():
    mix = spec.load_traffic("chat", BENCH)
    spans = [20.0, 51.0]
    a = traffic.schedule(mix, spans, 2 ** 40 + 1, 1000)
    b = traffic.schedule(mix, spans, 7, 1000)
    rate = mix["arrivals"]["rate_per_s"]

    def window(sched):
        return [r for r in sched if r.due_s >= spans[0]]
    # the window holds the same work for every seed, whatever the warm-up
    for part in (lambda s: [r for r in s if r.due_s < spans[0]], window):
        pa, pb = part(a), part(b)
        assert sorted(len(r.prompt) for r in pa) == \
            sorted(len(r.prompt) for r in pb)
        assert sorted(r.max_new for r in pa) == sorted(r.max_new for r in pb)
        assert [len(r.prompt) for r in pa] != [len(r.prompt) for r in pb]
    assert len(window(a)) == round(rate * spans[1])
    assert window(a)[0].due_s == spans[0]
    assert [r.rid for r in a] == list(range(len(a)))

    def gaps(sched):     # between arrivals, and from the last to the end
        due = [r.due_s for r in window(sched)]
        return sorted(list(np.diff(due)) + [sum(spans) - due[-1]])
    assert gaps(a) == pytest.approx(gaps(b))
    assert a[0].due_s == 0.0 and a[-1].due_s < sum(spans)
    lengths = [len(r.prompt) for r in a]
    lo, hi = mix["prompt_len"]["min"], mix["prompt_len"]["max"]
    assert min(lengths) >= lo and max(lengths) <= hi
    # the same seed gives the same inputs
    c = traffic.schedule(mix, spans, 2 ** 40 + 1, 1000)
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, c))


def test_a_backlog_is_submitted_at_once():
    mix = spec.load_traffic("batch", BENCH)
    s = traffic.schedule(mix, [0.0], 5, 1000)
    assert len(s) == mix["arrivals"]["requests"]
    assert all(r.due_s == 0.0 for r in s)


def test_sample_takes_the_longest_then_draws_by_seed():
    positions = {1: 10, 2: 50, 3: 20, 4: 30}
    served = {1: 5, 2: 10, 3: 5, 4: 5}
    pick = traffic.sample_ids(positions, served, 15, seed=3)
    assert pick[0] == 2 and len(pick) == 2
    assert pick == traffic.sample_ids(positions, served, 15, seed=3)


# -- open-loop stamping -----------------------------------------------------------

@dataclasses.dataclass
class Req:
    rid: int
    prompt: np.ndarray
    max_new: int
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


class StalledEngine:
    """Two slots, one token per slot per step, 5 ms a step; step number
    ``stall_at`` takes ``stall_s``."""

    def __init__(self, stall_at, stall_s):
        self.q, self.active, self.n = collections.deque(), [], 0
        self.stall_at, self.stall_s = stall_at, stall_s

    def submit(self, r):
        r._left = len(r.prompt)
        self.q.append(r)
        return True

    def queue_depth(self):
        return len(self.q)

    def active_count(self):
        return len(self.active)

    def step_once(self):
        while self.q and len(self.active) < 2:
            self.active.append(self.q.popleft())
        time.sleep(self.stall_s if self.n == self.stall_at else 0.005)
        self.n += 1
        for r in self.active:
            if r._left > 1:
                r._left -= 1
            else:
                r._left = 0
                r.out_tokens.append(1)
                r.done = len(r.out_tokens) >= r.max_new
        self.active = [r for r in self.active if not r.done]


def test_time_to_first_token_counts_from_the_due_time_through_a_stall():
    cell = spec.find_cell("tiny-dense.chat", root=DATA, bench_dir=DATA)
    cell.traffic.update(arrivals={"kind": "poisson", "rate_per_s": 40},
                        warmup_s=0.0, drain_cap_s=5.0)
    drv = serve.Driver(cell, 1.0, seed=5, counter=_Counter())
    drv.engine, drv.Request = StalledEngine(stall_at=20, stall_s=0.5), Req
    rec = drv.run(trace=False)
    ws, we = rec.window
    arrived = [tk for tk in rec.tracked if ws <= tk.due < we]
    assert arrived and all(tk.stamps for tk in arrived)
    # each stamp is the benchmark's clock after the step that made it
    ends = {round(e, 9) for _, e, _ in rec.steps}
    assert all(round(t, 9) in ends for tk in rec.tracked for t in tk.stamps)
    # a request due during the stall waits for it in full
    stall_start, stall_end = next((s, e) for s, e, _ in rec.steps
                                  if e - s > 0.4)
    during = [tk for tk in arrived if stall_start < tk.due < stall_end]
    assert during
    for tk in during:
        assert tk.stamps[0] - tk.due >= stall_end - tk.due > 0
    e2e = serve.end_to_end(rec, 1.0)
    ttft = sorted(tk.stamps[0] - tk.due for tk in arrived)
    assert e2e["ttft_p90_s"] == pytest.approx(stats.percentile(ttft, 90))
    assert serve.counts(rec) == {"attempted": len(arrived), "failed": 0}


class _Counter:
    def snapshot(self):
        return {"compiles": 0}

    def since(self, snap):
        return {"compiles": 0}


def _tracked(stamps, due=0.0):
    plan = traffic.Planned(0, due, np.zeros(3, np.int32), len(stamps))
    return serve.Tracked(plan, None, due, stamps=list(stamps))


def test_the_gap_between_tokens_is_read_over_eight_gaps_in_the_window():
    # 20 tokens 10 ms apart, one 90 ms stall before token 12
    t = list(np.cumsum([0.01] * 11 + [0.1] + [0.01] * 8))
    st = serve.itl_stretches(_tracked(t), 0.0, 1.0)
    assert len(st) == len(t) - serve.ITL_GAPS
    assert min(st) == pytest.approx(0.01)
    assert max(st) == pytest.approx(0.01 + 0.09 / serve.ITL_GAPS)
    # only tokens stamped inside the window count
    assert serve.itl_stretches(_tracked(t), t[5], 1.0) == st[5:]
    assert serve.itl_stretches(_tracked(t[:8]), 0.0, 1.0) == []


def test_tokens_per_second_counts_prompt_and_output_tokens():
    # two slots for 10 steps of 0.1 s; request 0: prompt 3, 4 out (steps
    # 0-5); request 1: prompt 5, 6 out (steps 0-9)
    steps = [(i * 0.1, i * 0.1 + (0.2 if i == 9 else 0.1), 2 if i < 6 else 1)
             for i in range(10)]
    a = _tracked([0.3, 0.4, 0.5, 0.6])
    b = _tracked([0.5, 0.6, 0.7, 0.8, 0.9, 1.0])
    rec = serve.ServeRecord((0.0, 1.1), steps, [a, b],
                            admitted=[2] + [0] * 8 + [1])
    e2e = serve.end_to_end(rec, 1.1)
    assert e2e["tokens_per_s"] == pytest.approx(((3 + 4) + (5 + 6)) / 1.1)
    summary = serve.summary(rec)
    assert summary["output_tokens"] == 10
    # the longest step first, with the requests it admitted
    assert summary["longest_steps"][0] == pytest.approx([200.0, 1, 0.9])
    assert len(summary["longest_steps"]) == 5
