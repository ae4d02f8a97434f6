"""The serving engine's own spans and stamps, read beside the device trace.

The engine writes one span per step phase (``engine.step`` around
``engine.admit`` / ``engine.reset_slot``, ``engine.decode``,
``engine.sample``, ``engine.readback``, ``engine.emit``, and ``host.gc``
for each garbage collection) as profiler annotations on the host plane,
and stamps each request when it takes a slot (``t_admit``).  A program
without them gives no spans and no stamp, and every function here then
returns ``None`` or nothing.

The profiler stamps host and device events with clocks that disagree by
about a millisecond, too coarse for spans of a fraction of one.  So the
offset ``d`` (device time = host time + ``d``) is bounded from causal
anchors in the traced steps:

* a run of the decode program cannot start before its ``engine.decode``
  span starts: ``d <= run.start - decode.start``;
* an ``engine.readback`` cannot end before the last device operation that
  precedes it ends: ``d >= op.end - readback.end``, where the operations
  that precede it are those that start before ``readback.end`` plus the
  upper bound.

The midpoint of the feasible interval is applied, and each device-idle
interval of the traced window on the first chip is put down to the
innermost program span open there on the host (the one that started
last); idle time under no program span is ``UNSPANNED``.

How far this is sure: each idle stretch between two operations starts at
an operation's end and ends at the next one's start, and the offset moves
idle only across those edges.  The anchors place every decode run's start
after its ``engine.decode`` span starts, and each step's last operation's
end before ``engine.readback`` ends, at every offset in the interval.  So
in a step that admits nothing, the idle the offset takes from the
read-back's tail it gives to the decode launch: the sum over
``ROUND_TRIP``, and each span between (``engine.emit``, ``engine.admit``,
the caller's loop), read the same at every feasible offset, while the
split among the three moves by up to the interval's width (1.5-1.9 ms on
a TPU v5e).  In a step that admits, the reset's operations still run
when the decode is launched, so up to that width trades between
``engine.reset_slot`` and the read-back's tail instead: on a TPU v5e,
with about one step in eight admitting, the sum moved 0.1-0.25 ms a step
from one end of the interval to the other.  The window's two edges cut
idle stretches the anchors do not bound, and add at most twice the width
over the window.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, List, Optional, Tuple

from chipbench import stats, view
from chipbench.trace import DEVICE_PLANE, Event

PROGRAM_SPAN = re.compile(r"^(engine\.[a-z_]+|host\.gc)$")
UNSPANNED = "unspanned"
STEP = "engine.step"
# the decode program's round trip: its launch, the arg-max dispatch and
# the read-back; only their sum is fixed by the anchors
ROUND_TRIP = ("engine.decode", "engine.sample", "engine.readback")
# How far before its ``engine.decode`` span a decode run may seem to start
# and still be paired with it: above the clocks' disagreement, well under
# a step.
PAIR_SLACK_S = 0.005


@dataclasses.dataclass
class Attribution:
    idle_s: Dict[str, float]      # device-idle seconds per innermost span
    steps: int                    # ``engine.step`` spans in the window
    bounds: Tuple[Optional[float], Optional[float]]   # offset interval
    offset: float                 # applied: device = host + offset

    def per_step_ms(self, *names: str) -> float:
        return sum(self.idle_s.get(n, 0.0) for n in names) / \
            self.steps * 1e3


def program_spans(trace) -> List[Event]:
    """The program's spans on the host planes, in order of start."""
    return sorted((e for e in trace.events if PROGRAM_SPAN.match(e.name)
                   and not DEVICE_PLANE.match(e.plane)),
                  key=lambda e: e.start)


def offset_bounds(spans: List[Event], decode_runs: List[Event],
                  ops: List[Event]) -> Tuple[Optional[float],
                                             Optional[float]]:
    """The feasible interval ``(lo, hi)`` of the host-to-device offset;
    either end is ``None`` where no anchor bounds it."""
    runs = sorted(decode_runs, key=lambda e: e.start)
    starts = [r.start for r in runs]
    hi = None
    for d in (s for s in spans if s.name == "engine.decode"):
        k = bisect.bisect_left(starts, d.start - PAIR_SLACK_S)
        if k < len(runs):
            b = runs[k].start - d.start
            hi = b if hi is None else min(hi, b)
    if hi is None:
        return None, None
    by_start = sorted(ops, key=lambda e: e.start)
    op_starts = [o.start for o in by_start]
    last_end, t = [], float("-inf")     # latest end among the first k ops
    for o in by_start:
        t = max(t, o.end)
        last_end.append(t)
    lo = None
    for rb in (s for s in spans if s.name == "engine.readback"):
        k = bisect.bisect_right(op_starts, rb.end + hi)
        if k:
            b = last_end[k - 1] - rb.end
            lo = b if lo is None else max(lo, b)
    return lo, hi


def innermost(spans: List[Event]) -> List[Tuple[float, float, str]]:
    """The host timeline cut where any span opens or closes, each piece
    named by the innermost span open in it (the one that started last);
    pieces under no span are left out."""
    cuts = sorted({t for s in spans for t in (s.start, s.end)})
    out, j, open_ = [], 0, []
    for a, b in zip(cuts, cuts[1:]):
        while j < len(spans) and spans[j].start <= a:
            open_.append(spans[j])
            j += 1
        open_ = [s for s in open_ if s.end > a]
        if open_:
            out.append((a, b, max(open_, key=lambda s: s.start).name))
    return out


def idle_intervals(trace) -> List[Tuple[float, float]]:
    """Stretches of the traced window with no operation on the first
    chip (device clock)."""
    lo, hi = trace.window
    gaps, t = [], lo
    for s, e in trace.busy_intervals(trace.device_planes[0]):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def attribute(trace, decode_runs: List[Event],
              offset: Optional[float] = None) -> Optional[Attribution]:
    """Device-idle seconds of the traced window per innermost program
    span, at ``offset`` or else the anchors' midpoint; ``None`` where the
    trace holds no engine step or no chip."""
    if trace is None or trace.window is None or not trace.device_planes:
        return None
    spans = program_spans(trace)
    lo, hi = trace.window
    steps = sum(1 for s in spans if s.name == STEP
                and lo <= s.start and s.end <= hi)
    if not steps:
        return None
    bounds = offset_bounds(spans, decode_runs,
                           trace.ops(trace.device_planes[0]))
    if offset is None:
        known = [b for b in bounds if b is not None]
        offset = sum(known) / len(known) if known else 0.0
    pieces = innermost(spans)
    ends = [b for _, b, _ in pieces]
    idle: Dict[str, float] = {}
    total = 0.0
    for s, e in idle_intervals(trace):
        total += e - s
        s, e = s - offset, e - offset
        for k in range(bisect.bisect_right(ends, s), len(pieces)):
            a, b, name = pieces[k]
            if a >= e:
                break
            idle[name] = idle.get(name, 0.0) + min(b, e) - max(a, s)
    idle[UNSPANNED] = total - sum(idle.values())
    return Attribution(idle, steps, bounds, offset)


def attribute_run(run) -> Optional[Attribution]:
    """``attribute`` for a reader's ``RunView``."""
    return attribute(run.trace, view.decode_runs(run))


# -- stamps --------------------------------------------------------------------

def profiler_holds(rec) -> List[Tuple[float, float]]:
    """On the benchmark's clock, the two stretches in which a traced run's
    profiler held the benchmark's loop: from the last engine step before
    the session to its first traced step (starting it), and from its last
    traced step to the first step after it (stopping it, seconds).  The
    traced steps between are real work, slowed only by the tracing."""
    if rec.trace_window is None or not rec.traced_steps:
        return []
    first, last = rec.traced_steps[0][0], rec.traced_steps[-1][1]
    return [(max((te for _, te, _ in rec.steps if te <= first),
                 default=first), first),
            (last, min((ts for ts, _, _ in rec.steps if ts >= last),
                       default=rec.trace_window[1]))]


def _on_bench_clock(tk, stamp: str) -> Optional[float]:
    """When the benchmark saw the moment an engine stamp marks."""
    if stamp == "t_submit":
        return tk.due
    if stamp == "t_admit":
        return tk.admit_t
    return tk.stamps[0] if tk.stamps else None      # t_first_token


def stamp_p90(run, first: str, last: str) -> Optional[float]:
    """90th percentile of ``req.<last> - req.<first>`` over the requests
    due in the window that have both stamps.  In a traced run: over those
    due before the profiler's session, each less the part of it spent in
    the profiler's two holds (the queue that builds in the holds lasts
    through the rest of the window)."""
    rec = run.record
    ws, we = rec.window
    holds = profiler_holds(rec)
    xs = []
    for tk in rec.tracked:
        a, b = getattr(tk.req, first, None), getattr(tk.req, last, None)
        if a is None or b is None or not ws <= tk.due < we:
            continue
        if holds:
            if tk.due >= holds[0][0]:
                continue
            ha, hb = _on_bench_clock(tk, first), _on_bench_clock(tk, last)
            for h0, h1 in holds:
                b -= max(0.0, min(hb, h1) - max(ha, h0))
        xs.append(b - a)
    return stats.percentile(xs, 90) if xs else None
