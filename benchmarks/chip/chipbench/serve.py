"""The serving driver: the engine's public ``submit`` / ``step_once`` under
an open-loop schedule or a backlog, timed by the benchmark's own clock.

A request's due time is when the schedule says it arrives; it is
submitted at the first step boundary after that, and its time to first
token is counted from the due time, so a stalled engine delays every later
request in full.  Tokens are stamped by the benchmark when ``step_once``
returns (the step ends in a host read of the sampled tokens), never with
the engine's own stamps.

Which requests the engine admitted in a step is read from its public
queue depth (admission is FIFO), so the benchmark knows each live slot's
cache position without reading the engine's private state.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import time
from typing import Dict, List, Optional

import jax
import numpy as np

from chipbench import stats, traffic, weights


@dataclasses.dataclass
class Tracked:
    plan: traffic.Planned
    req: object                   # repro.serve.engine.Request
    due: float                    # absolute, host clock
    admit_step: int = -1
    admit_t: Optional[float] = None
    stamps: List[float] = dataclasses.field(default_factory=list)
    done_t: Optional[float] = None


@dataclasses.dataclass
class ServeRecord:
    """What the window did, for the end-to-end metrics and the readers."""
    window: tuple                 # (start, end), host clock
    steps: List[tuple]            # (t_start, t_end, n_live) per engine step
    tracked: List[Tracked]
    traced_steps: List[tuple] = dataclasses.field(default_factory=list)
    # ^ (t_start, t_end, [cache positions after the step of live slots])
    trace_window: Optional[tuple] = None
    lateness_s: List[float] = dataclasses.field(default_factory=list)
    drain_s: float = 0.0
    backlog: bool = False
    window_compiles: Dict[str, float] = dataclasses.field(
        default_factory=dict)
    admitted: List[int] = dataclasses.field(default_factory=list)
    # ^ requests admitted to slots in each engine step, beside ``steps``

    def window_steps(self):
        ws, we = self.window
        return [s for s in self.steps if s[0] >= ws and s[1] <= we]


class Driver:
    """Runs one serving cell on one chip and checks what it served."""

    def __init__(self, cell, seconds: float, seed: int, trace_dir=None,
                 devices=None, counter=None):
        self.cell, self.seconds, self.seed = cell, float(seconds), int(seed)
        self.devices, self.counter = devices, counter
        self.cfg = cell.config.model
        self.sv = cell.config.meta["serve"]
        self.mix = cell.traffic
        self.trace_dir = trace_dir
        self.clock = time.perf_counter

    # -- set-up ----------------------------------------------------------------
    def setup(self):
        from repro.launch.serve import SERVE_PCFG
        from repro.serve.engine import Request, ServingEngine
        self.Request = Request
        self.params = weights.make(self.cfg, self.seed)
        self.engine = ServingEngine(
            self.cfg, SERVE_PCFG, self.params, batch_slots=self.sv["slots"],
            max_len=self.sv["max_len"], seed=self.seed % 2 ** 31)
        # every program the window runs: the decode step, the slot reset
        # and the sampling, on one short request per slot index
        warm = traffic.rng(self.seed, 99).integers(
            0, self.cfg.vocab_size, size=(self.sv["slots"], 2), dtype=np.int32)
        for i in range(self.sv["slots"]):
            self.engine.submit(Request(-1 - i, warm[i], max_new=2))
        while self.engine.active_count() or self.engine.queue_depth():
            self.engine.step_once()

    # -- the window ------------------------------------------------------------
    def run(self, trace: bool) -> ServeRecord:
        mix, seconds = self.mix, self.seconds
        backlog = mix["arrivals"]["kind"] == "backlog"
        warmup_s = 0.0 if backlog else float(mix["warmup_s"])
        plan = traffic.schedule(mix, [0.0] if backlog else
                                [warmup_s, seconds], self.seed,
                                self.cfg.vocab_size)
        eng, clock = self.engine, self.clock
        queued = collections.deque()
        active: List[Tracked] = []
        tracked: List[Tracked] = []
        steps, traced, lateness, admits = [], [], [], []
        t0 = clock()
        ws = t0 + warmup_s if not backlog else None
        we = ws + seconds if ws is not None else None
        trace_s = float(mix["trace_s"])
        tr_start = tr_end = None
        tracing = False
        nxt, n_steps = 0, 0
        drain_cap = float(mix.get("drain_cap_s", 0.0))
        snap, win_compiles = None, {}
        while True:
            now = clock()
            if ws is not None and snap is None and now >= ws:
                snap = self.counter.snapshot()
            if snap is not None and not win_compiles and now >= we:
                win_compiles = self.counter.since(snap)
            while nxt < len(plan) and t0 + plan[nxt].due_s <= now and \
                    (we is None or t0 + plan[nxt].due_s < we):
                p = plan[nxt]
                r = self.Request(p.rid, p.prompt, max_new=p.max_new)
                with jax.profiler.TraceAnnotation("bench.submit"):
                    eng.submit(r)
                tk = Tracked(p, r, t0 + p.due_s)
                lateness.append(now - tk.due)
                queued.append(tk)
                tracked.append(tk)
                nxt += 1
            if we is not None and now >= we:
                if self._drained(tracked, ws, we, backlog) or \
                        now >= we + drain_cap:
                    break
            if ws is not None and tr_start is None:
                tr_start = ws + seconds / 2 - trace_s / 2
                tr_end = tr_start + trace_s
            if trace and not tracing and tr_start is not None and \
                    tr_start <= now < tr_end:
                jax.profiler.start_trace(str(self.trace_dir))
                tracing, trace_t0 = True, clock()
            if not (active or queued):
                if nxt >= len(plan):
                    if we is None:
                        raise RuntimeError("the backlog ran dry before the "
                                           "window closed")
                    time.sleep(0.001)
                    continue
                time.sleep(max(0.0, min(t0 + plan[nxt].due_s - clock(),
                                        0.01)))
                continue
            q_before = eng.queue_depth()
            ts = clock()
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                eng.step_once()
            te = clock()
            admitted = q_before - eng.queue_depth()
            for _ in range(admitted):
                tk = queued.popleft()
                tk.admit_step, tk.admit_t = n_steps, ts
                active.append(tk)
            live_pos = []
            still = []
            for tk in active:
                live_pos.append(n_steps - tk.admit_step + 1)
                k = len(tk.req.out_tokens) - len(tk.stamps)
                tk.stamps.extend([te] * k)
                if tk.req.done:
                    tk.done_t = te
                else:
                    still.append(tk)
            active = still
            steps.append((ts, te, len(live_pos)))
            admits.append(admitted)
            if tracing:
                traced.append((ts, te, live_pos))
                if te >= tr_end:
                    jax.profiler.stop_trace()
                    tracing = False
                    trace_window = (trace_t0, clock())
            n_steps += 1
            if backlog and ws is None and n_steps >= mix["warmup_steps"]:
                ws, we = te, te + seconds
        if tracing:
            jax.profiler.stop_trace()
            trace_window = (trace_t0, clock())
        return ServeRecord((ws, we), steps, tracked, traced,
                           trace_window if traced else None, lateness,
                           max(0.0, clock() - we), backlog, win_compiles,
                           admits)

    @staticmethod
    def _drained(tracked, ws, we, backlog) -> bool:
        if backlog:
            return True
        return all(tk.stamps for tk in tracked if ws <= tk.due < we)

    # -- after the window ------------------------------------------------------
    def check(self, rec: ServeRecord, control: Optional[str] = None) -> Dict:
        """Free the engine (its KV cache), then compare what it served with
        the reference (``check`` below)."""
        self.engine = None
        gc.collect()
        return check(self, rec, int(self.mix["check_tokens"]), control)


# Time to first token: at the 131 requests due in a 51 s window of the
# chat cell, the 90th percentile has 13 samples beyond it.
TTFT_TAIL = 90
# The gap between tokens is read over 8 consecutive gaps of one request,
# about 300 ms at the chat cell's steps: a host-clock reading spans
# 250 ms or more.  A stall in one step still moves its stretches by an
# eighth of its length, and at about 24,000 stretches in a window the
# 99th percentile has some 240 beyond it.
ITL_GAPS = 8
ITL_TAIL = 99


def itl_stretches(tk: Tracked, ws: float, we: float) -> List[float]:
    """Mean gap between tokens over every run of ``ITL_GAPS`` consecutive
    gaps of one request whose tokens were all stamped in the window."""
    t = [x for x in tk.stamps if ws <= x <= we]
    return [(t[i + ITL_GAPS] - t[i]) / ITL_GAPS
            for i in range(len(t) - ITL_GAPS)]


def end_to_end(rec: ServeRecord, seconds: float) -> Dict[str, float]:
    """Every serving end-to-end metric this record supports: time to first
    token over the requests due in the window, from the due time; the gap
    between tokens over every stretch of ``ITL_GAPS`` gaps inside the
    window; and the tokens the window took in and put out over its
    length: a prompt token is taken in at each live slot's step until its
    first output token, so the window's prompt and output tokens are its
    slot-steps plus the first tokens stamped in it."""
    ws, we = rec.window
    arrived = [tk for tk in rec.tracked if ws <= tk.due < we]
    ttft = [tk.stamps[0] - tk.due for tk in arrived if tk.stamps]
    itl = [g for tk in rec.tracked for g in itl_stretches(tk, ws, we)]
    firsts = sum(1 for tk in rec.tracked
                 if tk.stamps and ws <= tk.stamps[0] <= we)
    slot_steps = sum(n for _, _, n in rec.window_steps())
    e2e = {"tokens_per_s": (slot_steps + firsts) / seconds}
    if ttft:
        e2e["ttft_p90_s"] = stats.percentile(ttft, TTFT_TAIL)
    if itl:
        e2e["itl8_p99_ms"] = stats.percentile(itl, ITL_TAIL) * 1e3
    return e2e


def counts(rec: ServeRecord) -> Dict[str, int]:
    """(attempted, failed): open loop, the requests due in the window and
    those of them with no first token by the end of the drain; backlog,
    the requests that emitted a token in the window (none can fail)."""
    ws, we = rec.window
    if rec.backlog:
        n = sum(1 for tk in rec.tracked
                if any(ws <= t <= we for t in tk.stamps))
        return {"attempted": n, "failed": 0}
    arrived = [tk for tk in rec.tracked if ws <= tk.due < we]
    return {"attempted": len(arrived),
            "failed": sum(1 for tk in arrived if not tk.stamps)}


def queued_at(rec: ServeRecord, t: float) -> int:
    """Requests due by ``t`` and not yet admitted to a slot at ``t``."""
    return sum(1 for tk in rec.tracked if tk.due <= t
               and (tk.admit_t is None or tk.admit_t > t))


def summary(rec: ServeRecord) -> Dict[str, float]:
    """Earlier-line facts about the window: steps, slots, queue, how late
    the generator ran, and its five longest steps as [ms, requests
    admitted in the step, seconds into the window]."""
    w = rec.window_steps()
    ws0 = rec.window[0]
    longest = sorted(((e - s) * 1e3, a, s - ws0)
                     for (s, e, _), a in zip(rec.steps, rec.admitted)
                     if s >= ws0 and e <= rec.window[1])[-5:][::-1]
    live = [s[2] for s in w]
    ws, we = rec.window
    out_tokens = sum(1 for tk in rec.tracked for t in tk.stamps
                     if ws <= t <= we)
    return {"window_steps": len(w),
            "output_tokens": out_tokens,
            "queued_at_start": queued_at(rec, rec.window[0]),
            "queued_at_end": queued_at(rec, rec.window[1]),
            "mean_live_slots": float(np.mean(live)) if live else 0.0,
            "mean_step_ms": float(np.mean([s[1] - s[0] for s in w]) * 1e3)
            if w else 0.0,
            "generator_late_p99_ms": stats.percentile(rec.lateness_s, 99)
            * 1e3 if rec.lateness_s else 0.0,
            "drain_s": rec.drain_s,
            "longest_steps": [list(x) for x in longest]}


def check(driver: Driver, rec: ServeRecord, tokens: int,
          control: Optional[str] = None) -> Dict:
    """The served tokens of a seeded sample of finished requests (the
    longest among them) against the reference run over each prompt with
    its served tokens: per served token, how far its reference logit lies
    below the reference's best.  Returns the widest such gap (and, for a
    control, the gap of the token the control puts first instead)."""
    from reference import dense
    cfg, T = driver.cfg, driver.sv["max_len"]
    done = [tk for tk in rec.tracked if tk.req.done]
    positions = {tk.plan.rid: len(tk.plan.prompt) + len(tk.req.out_tokens)
                 for tk in done}
    served = {tk.plan.rid: len(tk.req.out_tokens) for tk in done}
    pick = traffic.sample_ids(positions, served, tokens, driver.seed)
    by_id = {tk.plan.rid: tk for tk in done}
    toks = np.zeros((len(pick), T), np.int32)
    tgt = np.zeros((len(pick), T), np.int32)
    mask = np.zeros((len(pick), T), bool)
    bad = 0
    for j, rid in enumerate(pick):
        tk = by_id[rid]
        seq = np.concatenate([tk.plan.prompt,
                              np.asarray(tk.req.out_tokens, np.int32)])
        if len(tk.req.out_tokens) != tk.plan.max_new or \
                not ((0 <= seq) & (seq < cfg.vocab_size)).all():
            bad += 1
        n = min(len(seq), T)
        toks[j, :n - 1] = seq[:n - 1]
        tgt[j, :n - 1] = seq[1:n]
        P = len(tk.plan.prompt)
        mask[j, P - 1:n - 1] = True
    targets = [tgt]
    if control is not None:
        _, _, carg = dense.logits_at(cfg, driver.params, toks, tgt[..., None],
                                     weights=control)
        targets.append(carg.astype(np.int32))
    best, tl, _ = dense.logits_at(cfg, driver.params, toks,
                                  np.stack(targets, -1))
    gaps = [float(np.max((best - tl[..., m])[mask])) if mask.any()
            else float("inf") for m in range(len(targets))]
    out = {"requests": len(pick), "served_tokens": int(mask.sum()),
           "bad_requests": bad, "served_gap_max": gaps[0]}
    if control is not None:
        out["control_gap_max"] = gaps[1]
    return out
