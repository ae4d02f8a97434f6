"""Tick-phase tracing: a ring-buffer flight recorder with Perfetto export.

Observability pillar 2 (see docs/OBSERVABILITY.md).  A ``Tracer`` records
nested spans — ``with tracer.span("sched.placement_drain"): ...`` — into a
fixed-capacity ring buffer of plain tuples, so a 100k-VM eviction storm
can run with the recorder armed and only ever hold the last N spans (the
flight-recorder property: overflow overwrites the oldest spans, and the
``dropped`` counter says how many).

Exports:

  * ``to_chrome_trace()`` — the Chrome/Perfetto ``trace_event`` JSON object
    format (``"X"`` complete events, microsecond ``ts``/``dur``), openable
    directly at https://ui.perfetto.dev or chrome://tracing;
  * ``phase_breakdown()`` — per-span-name wall-clock totals
    (count/total/mean/max), the per-phase profile ``benchmarks/run.py
    --profile`` commits into BENCH_sched.json.

A tracer has up to two sinks.  The **ring** (``enabled=True``) is the
flight recorder above.  The **profiler sink** (``profiler=True``) writes
each span as a ``jax.profiler.TraceAnnotation`` (a ``step()`` as a
``StepTraceAnnotation``), so the spans land in any running JAX profiler
session on the same timeline as the device's operations; outside a
session an annotation costs about a microsecond.  Only a tracer with the
profiler sink imports jax.  With both sinks, a whole run's flight record
can be exported to Perfetto with no profiler session.

A tracer with neither sink returns one shared no-op context manager from
``span()`` (no allocation), and ``begin``/``end`` return immediately —
the scheduler instruments unconditionally against the process-wide
default tracer, which starts disabled, so the hot path pays a handful of
attribute checks per tick and nothing per VM.

Ring timestamps are wall-clock (``time.perf_counter``) because the point
is profiling real cost; pass the sim clock via span args when the sim
instant matters (``tracer.span("x", t_sim=engine.clock.t)``).
"""
from __future__ import annotations

import gc
import json
import time
import weakref
from typing import Any, Callable, Dict, List, Optional


class _NullSpan:
    """Shared no-op span for disabled tracers (identity == proof of cost)."""
    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **args) -> "_NullSpan":
        return self


NULL_SPAN = _NullSpan()


class _Span:
    __slots__ = ("_tr", "name", "cat", "args", "_t0", "_depth", "_ann",
                 "_stepped")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 args: Optional[Dict[str, Any]], stepped: bool = False):
        self._tr = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._stepped = stepped
        self._ann = None

    def set(self, **args) -> "_Span":
        """Attach/merge args after the span opened (e.g. batch sizes that
        are only known mid-phase)."""
        if self.args is None:
            self.args = args
        else:
            self.args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**args)
        return self

    def __enter__(self) -> "_Span":
        tr = self._tr
        if tr.profiler:
            self._ann = tr._annotation(self.name, self.args, self._stepped)
            self._ann.__enter__()
        if tr.enabled:
            self._depth = len(tr._stack)
            tr._stack.append(self.name)
            self._t0 = tr._clock()
        return self

    def __exit__(self, *exc) -> bool:
        tr = self._tr
        if tr.enabled:
            t1 = tr._clock()
            tr._stack.pop()
            tr._record(self.name, self.cat, self._t0, t1 - self._t0,
                       self._depth, self.args)
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        return False


class Tracer:
    """Ring-buffer flight recorder; see the module docstring."""

    def __init__(self, capacity: int = 65536, enabled: bool = True,
                 clock: Callable[[], float] = time.perf_counter,
                 profiler: bool = False):
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self.enabled = enabled          # the ring records
        self.profiler = profiler        # spans go to the JAX profiler too
        if profiler:
            from jax.profiler import StepTraceAnnotation, TraceAnnotation
            self._ann_cls = (TraceAnnotation, StepTraceAnnotation)
        self._gc_hook = None
        self.capacity = capacity
        self._clock = clock
        self._ring: List[Optional[tuple]] = [None] * capacity
        self._n = 0                     # spans ever recorded
        self._stack: List[str] = []     # active span names (nesting depth)
        self._begin_stack: List[tuple] = []     # open begin()/end() spans
        self._t0 = clock()              # trace epoch

    # -- recording -----------------------------------------------------------
    def span(self, name: str, cat: str = "sched", **args):
        """Context manager recording one span on exit.  ``args`` land in
        the trace event's ``args`` payload."""
        if not (self.enabled or self.profiler):
            return NULL_SPAN
        return _Span(self, name, cat, args or None)

    def step(self, name: str, step_num: int, cat: str = "sched", **args):
        """A span for one step of a loop, with arg ``step_num``: to the
        profiler a ``StepTraceAnnotation``, which its step tools read."""
        if not (self.enabled or self.profiler):
            return NULL_SPAN
        return _Span(self, name, cat, dict(args, step_num=step_num), True)

    def begin(self, name: str, cat: str = "sched", **args) -> None:
        """Imperative open (for spans that cannot wrap a ``with`` block)."""
        if not (self.enabled or self.profiler):
            return
        ann = None
        if self.profiler:
            ann = self._annotation(name, args)
            ann.__enter__()
        if self.enabled:
            self._stack.append(name)
        self._begin_stack.append((name, cat, self._clock(),
                                  len(self._stack) - 1, args or None, ann))

    def end(self, **args) -> None:
        """Close the innermost ``begin()``; ``args`` join its own."""
        if not (self.enabled or self.profiler) or not self._begin_stack:
            return
        name, cat, t0, depth, a0, ann = self._begin_stack.pop()
        if self.enabled:
            self._stack.pop()
            if args:
                a0 = dict(a0 or (), **args)
            self._record(name, cat, t0, self._clock() - t0, depth, a0)
        if ann is not None:
            if args:
                ann.set_metadata(**args)
            ann.__exit__(None, None, None)

    def instant(self, name: str, cat: str = "sched", **args) -> None:
        """Zero-duration marker event (ring only)."""
        if not self.enabled:
            return
        self._record(name, cat, self._clock(), 0.0, len(self._stack),
                     args or None)

    def trace_gc(self) -> None:
        """Record each Python garbage collection as a ``host.gc`` span
        (args ``gen``, ``collected``) for as long as this tracer lives:
        a collection stops the interpreter, and with it the host's part
        of every step."""
        if not (self.enabled or self.profiler) or self._gc_hook is not None:
            return
        ref = weakref.ref(self)

        def hook(phase, info):
            tr = ref()
            if tr is None:
                return
            if phase == "start":
                tr.begin("host.gc", cat="host", gen=info["generation"])
            else:
                tr.end(collected=info["collected"])
        gc.callbacks.append(hook)
        self._gc_hook = hook
        weakref.finalize(self, gc.callbacks.remove, hook)

    def _annotation(self, name: str, args: Optional[Dict[str, Any]],
                    stepped: bool = False):
        return self._ann_cls[stepped](name, **(args or {}))

    def _record(self, name: str, cat: str, t0: float, dur: float,
                depth: int, args: Optional[Dict[str, Any]]) -> None:
        self._ring[self._n % self.capacity] = (name, cat, t0, dur, depth,
                                               args)
        self._n += 1

    # -- introspection -------------------------------------------------------
    @property
    def recorded(self) -> int:
        """Spans currently held in the ring."""
        return min(self._n, self.capacity)

    @property
    def dropped(self) -> int:
        """Spans overwritten by ring wraparound."""
        return max(0, self._n - self.capacity)

    def events(self) -> List[tuple]:
        """Held spans, oldest first: (name, cat, t0, dur, depth, args)."""
        if self._n <= self.capacity:
            return [e for e in self._ring[: self._n]]
        head = self._n % self.capacity
        return self._ring[head:] + self._ring[:head]

    def clear(self) -> None:
        self._ring = [None] * self.capacity
        self._n = 0
        self._stack.clear()
        self._begin_stack.clear()
        self._t0 = self._clock()

    # -- export --------------------------------------------------------------
    def to_chrome_trace(self, process_name: str = "wi-sched") -> Dict:
        """Chrome/Perfetto ``trace_event`` JSON object format: complete
        (``"X"``) events with microsecond timestamps relative to the trace
        epoch, sorted by start time so wrapped rings still load."""
        events: List[Dict[str, Any]] = [{
            "name": "process_name", "ph": "M", "pid": 1, "tid": 1,
            "args": {"name": process_name}}]
        rows = sorted(self.events(), key=lambda r: r[2])
        for name, cat, t0, dur, depth, args in rows:
            ev: Dict[str, Any] = {
                "name": name, "cat": cat or "sched", "ph": "X",
                "ts": (t0 - self._t0) * 1e6, "dur": dur * 1e6,
                "pid": 1, "tid": 1}
            if args:
                ev["args"] = dict(args)
            events.append(ev)
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"recorded": self.recorded,
                              "dropped": self.dropped}}

    def write(self, path: str, process_name: str = "wi-sched") -> str:
        with open(path, "w") as fh:
            json.dump(self.to_chrome_trace(process_name), fh)
            fh.write("\n")
        return path

    def phase_breakdown(self) -> Dict[str, Dict[str, float]]:
        """Per-span-name wall-clock profile over the held spans.

        Nested spans each report their own wall time, so a parent phase's
        total includes its children's (self time = parent - sum(children)
        is left to the trace viewer, which computes it exactly).
        """
        out: Dict[str, Dict[str, float]] = {}
        for name, _cat, _t0, dur, _depth, _args in self.events():
            row = out.get(name)
            if row is None:
                row = out[name] = {"count": 0, "total_s": 0.0, "max_s": 0.0}
            row["count"] += 1
            row["total_s"] += dur
            if dur > row["max_s"]:
                row["max_s"] = dur
        for row in out.values():
            row["mean_s"] = row["total_s"] / row["count"]
        return out
