"""Reduce a JAX profiler trace to device busy time, per-operation time,
module time and idle gaps named by what the host was doing.

A trace is read into plain events ``(plane, line, name, start_s, dur_s)``
so that the reduction can be checked on a small recorded trace.  Device
planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per
operation run and their ``XLA Modules`` line one per program run.  The
benchmark's own calls into the program are ``bench.*`` annotations on the
host plane, on the same clock.  The traced window runs from the first
annotation's start to the last one's end.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start: float      # seconds
    dur: float        # seconds

    @property
    def end(self) -> float:
        return self.start + self.dur


def load(trace_dir: Path) -> List[Event]:
    """Every event of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    return [Event(pl.name, ln.name, short_name(ev.name), ev.start_ns * 1e-9,
                  ev.duration_ns * 1e-9)
            for pl in pd.planes for ln in pl.lines for ev in ln.events]


_TYPE_OP = re.compile(r"([a-z0-9]+\[[0-9,]*\])\S*\s+([\w-]+)\(")


def short_name(name: str) -> str:
    """An operation's event carries its whole HLO instruction; keep its
    name, result type and opcode (``%copy.7 = bf16[8,32]{1,0} copy(...)``
    -> ``copy.7 bf16[8,32] copy``), or the name alone for a tuple result."""
    head, sep, rest = name.partition(" = ")
    head = head.lstrip("%")
    m = _TYPE_OP.match(rest) if sep else None
    return f"{head} {m.group(1)} {m.group(2)}" if m else head


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


class Trace:
    def __init__(self, events: List[Event]):
        self.events = events
        self.annotations = sorted(
            (e for e in events if e.name.startswith("bench.")
             and not DEVICE_PLANE.match(e.plane)), key=lambda e: e.start)
        self.device_planes = sorted(
            {e.plane for e in events if DEVICE_PLANE.match(e.plane)},
            key=lambda p: int(p.rsplit(":", 1)[1]))
        if self.annotations:
            self.window = (self.annotations[0].start,
                           max(e.end for e in self.annotations))
        else:
            self.window = None
        self._ops = {p: [] for p in self.device_planes}
        self._mods = {p: [] for p in self.device_planes}
        if self.window is not None:
            lo, hi = self.window
            for e in events:
                if e.plane not in self._ops:
                    continue
                if e.line == OPS_LINE and e.end > lo and e.start < hi:
                    self._ops[e.plane].append(e)
                elif e.line == MODULES_LINE and lo <= e.start and e.end <= hi:
                    self._mods[e.plane].append(e)

    # -- selections ----------------------------------------------------------
    def ops(self, plane: Optional[str] = None) -> List[Event]:
        """Operations in the window, on one chip or on all."""
        if plane is not None:
            return self._ops[plane]
        return [e for p in self.device_planes for e in self._ops[p]]

    def modules(self, plane: str) -> List[Event]:
        """Program runs wholly inside the window on one chip."""
        return self._mods[plane]

    # -- reductions ----------------------------------------------------------
    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_intervals(self, plane: str) -> List[Tuple[float, float]]:
        lo, hi = self.window
        return clip(union((e.start, e.end) for e in self.ops(plane)), lo, hi)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.device_planes:
            return 0.0
        return sum(sum(e - s for s, e in self.busy_intervals(p))
                   for p in self.device_planes) / len(self.device_planes)

    def op_seconds(self) -> Dict[str, float]:
        """Device seconds per operation name, averaged over the chips."""
        out: Dict[str, float] = {}
        lo, hi = self.window
        for e in self.ops():
            d = min(e.end, hi) - max(e.start, lo)
            out[e.name] = out.get(e.name, 0.0) + d
        n = max(1, len(self.device_planes))
        return {k: v / n for k, v in out.items()}

    def module_runs(self, match) -> List[Event]:
        """Runs of the programs whose name ``match`` (a callable) accepts,
        on the first chip."""
        if not self.device_planes:
            return []
        return [e for e in self.modules(self.device_planes[0])
                if match(e.name)]

    def module_busy_s(self, runs: List[Event]) -> float:
        """Device seconds of operations inside the given program runs, on
        the first chip (a run's span less its idle gaps)."""
        busy = self.busy_intervals(self.device_planes[0])
        total = 0.0
        for r in runs:
            total += sum(e - s for s, e in clip(busy, r.start, r.end))
        return total

    def collective_s(self) -> float:
        """Device seconds in collective operations, averaged over chips."""
        return sum(v for k, v in self.op_seconds().items()
                   if COLLECTIVE.search(k))

    def host_label(self, t: float) -> str:
        """The innermost ``bench.*`` annotation open at host time ``t``."""
        best = None
        for a in self.annotations:
            if a.start <= t <= a.end and (best is None or a.dur < best.dur):
                best = a
        return best.name if best is not None else "host:outside-annotations"

    def idle_gaps(self, top: int = 10) -> List[Tuple[str, float]]:
        """The longest stretches with no operation on the first chip, each
        named by what the host was doing in its middle."""
        if not self.device_planes:
            return []
        lo, hi = self.window
        busy = self.busy_intervals(self.device_planes[0])
        gaps, t = [], lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
        return [(self.host_label((s + e) / 2), e - s) for s, e in gaps[:top]]

    def breakdown(self, top: int = 10) -> Dict[str, List]:
        ops = sorted(self.op_seconds().items(), key=lambda kv: kv[1],
                     reverse=True)[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in self.idle_gaps(top)]}
