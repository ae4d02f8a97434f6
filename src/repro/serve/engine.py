"""Batched serving engine: request queue -> prefill -> decode loop.

Slot-based continuous batching lite: a fixed-size batch of decode slots;
finished sequences free their slot, queued requests prefill into free slots
FIFO.  The engine is a WI *workload* with a public elastic surface the
serving tenant (``repro.agents.serving_agent``) drives:

  * ``drain()`` — stop admitting, reject new submits, hand queued requests
    back for re-routing; in-flight decodes run to completion.
  * ``resize_slots(n)`` — grow immediately (harvest ``SCALE_UP_OFFER``);
    shrink is *deferred* until the active set fits, then the surviving
    sequences are compacted into the smaller batch (throttle = compute
    shed: the batch shrinks, demand hints stay put).
  * ``step_once()`` — one batched decode step, the unit the tenant's pump
    loop and the trainer-style ``run()`` interleave with sim time.

Time is injected (``now=``, defaulting to ``time.time`` for standalone
use) so latency accounting works under the sim clock, and stats live in an
``obs.MetricDict`` with per-engine collectors (queue depth, active slots,
tokens/s) plus a token latency histogram on the injected registry.  A
request's stamps (submit, admit to a slot, first token, done) split its
time to first token into queue wait and prompt feed.

Each step writes one span per phase through an ``obs.Tracer`` (``tracer=``;
docs/OBSERVABILITY.md): ``engine.step`` around ``engine.shrink``,
``engine.admit`` (with ``engine.reset_slot``), ``engine.decode``,
``engine.sample``, ``engine.readback`` and ``engine.emit``, plus
``host.gc`` for each garbage collection.  The real backend's default
tracer writes them to the JAX profiler only, so a profiler session puts
them on the device's timeline; outside one they cost a microsecond or two each.

Two decode backends share every bit of the admission/slot/drain logic:

  * **real** (``params`` given) — jit-compiled batched decode over a jax
    KV cache (per-slot positions diverge; ``cache['index']`` is a vector);
  * **synthetic** (``params is None``) — a deterministic pure-python
    next-token function and per-slot position counters.  No jax import
    anywhere on this path, so the scheduler-tenant case studies and the
    choreography tests serve "tokens" at simulation speed.
"""
from __future__ import annotations

import dataclasses
import queue
import time
from typing import Callable, List, Optional

import numpy as np

from repro import obs

_SYNTH_VOCAB = 256      # synthetic-mode token space


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray              # [S] int32
    max_new: int = 16
    temperature: float = 0.0
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # latency stamps (engine ``now()`` timebase; submit may pre-stamp)
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None         # took a slot
    t_first_token: Optional[float] = None
    t_done: Optional[float] = None


def sample(logits, temperature: float, key):
    import jax
    import jax.numpy as jnp
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(key, logits / temperature, axis=-1)


def decode_fn(cfg, pcfg):
    """The engine's jitted batched decode step.  The cache argument is
    donated: the step writes the new cache into the old one's buffers, so
    the device holds one KV cache, not two."""
    import jax
    from repro.models import model as M
    return jax.jit(lambda p, c, t: M.decode_step(cfg, pcfg, p, c, t),
                   donate_argnums=1)


class ServingEngine:
    """Single-device engine, used by the launcher (``launch/serve.py``),
    the tests, the examples and the serving tenant."""

    def __init__(self, cfg, pcfg, params, batch_slots: int = 4,
                 max_len: int = 256, seed: int = 0,
                 now: Optional[Callable[[], float]] = None,
                 registry: Optional[obs.MetricsRegistry] = None,
                 name: str = "engine",
                 on_complete: Optional[Callable[[Request], None]] = None,
                 tracer: Optional[obs.Tracer] = None):
        self.cfg, self.pcfg, self.params = cfg, pcfg, params
        self.slots = batch_slots
        self.max_len = max_len
        self.name = name
        self._now = now if now is not None else time.time
        self._on_complete = on_complete
        self._queue: "queue.Queue[Request]" = queue.Queue()
        self._active: List[Optional[Request]] = [None] * batch_slots
        self._last_emit: List[Optional[float]] = [None] * batch_slots
        self._draining = False
        self._target_slots: Optional[int] = None    # pending deferred shrink
        self._synthetic = params is None
        if tracer is None:
            if self._synthetic:
                tracer = obs.default_tracer()
            else:
                # the engine's own tracer: only it gets the gc hook; whoever
                # builds a shared or passed-in tracer decides that for it
                tracer = obs.Tracer(capacity=1, enabled=False, profiler=True)
                tracer.trace_gc()
        self.tracer = tracer
        if self._synthetic:
            self._pos = [0] * batch_slots
        else:
            import jax
            from repro.models import model as M
            self._key = jax.random.PRNGKey(seed)
            self._cache = M.init_cache(cfg, batch_slots, max_len)
            self._decode = decode_fn(cfg, pcfg)
        reg = registry if registry is not None \
            else obs.MetricsRegistry(enabled=False)
        self._registry = reg
        self._t0 = self._now()
        # defaultdict(float)-compatible stats, mirrored into registry gauges
        self.stats = obs.MetricDict(reg, prefix="wi_serving_", replica=name)
        for k in ("requests", "tokens", "batches", "admitted",
                  "prompt_tokens"):
            self.stats[k] = 0
        # a shared series (no replica label) so one percentile read covers
        # the whole fleet
        self._tok_lat = reg.histogram(
            "wi_serving_token_latency_s",
            "submit/last-emit to token emit (includes queue wait)")
        reg.add_collector(f"serving.{name}", self._collect)

    def _collect(self):
        dt = max(self._now() - self._t0, 1e-9)
        return {"queue_depth": self.queue_depth(),
                "active_slots": self.active_count(),
                "slots": self.slots,
                "tokens_per_s": self.stats["tokens"] / dt}

    # -- API -----------------------------------------------------------------
    def submit(self, req: Request) -> bool:
        """Queue a request; a draining engine rejects it (the router must
        send it elsewhere)."""
        if self._draining:
            self.stats["rejected"] += 1
            return False
        if req.t_submit is None:
            req.t_submit = self._now()
        self._queue.put(req)
        self.stats["requests"] += 1
        return True

    def utilization(self) -> float:
        return self.active_count() / self.slots

    def queue_depth(self) -> int:
        return self._queue.qsize()

    def active_count(self) -> int:
        return sum(r is not None for r in self._active)

    @property
    def admitting(self) -> bool:
        return not self._draining

    def p99_token_latency(self) -> float:
        """Bucket-estimated p99 of the shared token-latency series (NaN
        until anything was observed or when the registry is disabled)."""
        if getattr(self._tok_lat, "count", 0) == 0:
            return float("nan")
        return self._tok_lat.percentile(99)

    @staticmethod
    def _steps_left(r: Request) -> int:
        """Upper bound on decode steps to finish ``r`` (prompt feed-through
        plus remaining generation; the max_len cap can only end earlier)."""
        return len(getattr(r, "_pending", ())) + \
            max(0, r.max_new - len(r.out_tokens))

    # -- elastic surface -----------------------------------------------------
    def drain(self):
        """Eviction notice: stop admitting and reject new submits.  Returns
        ``(steps_left, requeued)`` — the worst-case decode steps to finish
        every in-flight sequence (the tenant converts that to the modeled
        ack latency) and the queued-but-unstarted requests, handed back so
        the router re-routes them to surviving replicas."""
        self._draining = True
        requeued: List[Request] = []
        while not self._queue.empty():
            requeued.append(self._queue.get())
        steps = max((self._steps_left(r) for r in self._active
                     if r is not None), default=0)
        self.stats["drains"] += 1
        self.stats["drain_requeued"] += len(requeued)
        return steps, requeued

    def resize_slots(self, n: int) -> int:
        """Grow/shrink the decode batch.  Grows apply immediately (new
        slots admit from the queue on the next step); shrinks defer until
        the active set fits, then compact surviving sequences — an active
        sequence is never dropped by a resize.  Returns the batch size in
        effect right now (the target, once a pending shrink lands)."""
        n = max(1, int(n))
        if n >= self.slots:
            if n > self.slots:
                self._grow(n)
            self._target_slots = None
            return self.slots
        self._target_slots = n
        self._maybe_apply_shrink()
        return self.slots if self._target_slots is None else n

    def _grow(self, n: int):
        old = self.slots
        self._active.extend([None] * (n - old))
        self._last_emit.extend([None] * (n - old))
        if self._synthetic:
            self._pos.extend([0] * (n - old))
        else:
            import jax
            from repro.models import model as M
            new_cache = M.init_cache(self.cfg, n, self.max_len)

            def cp(o, nl):
                return nl.at[:, :o.shape[1]].set(o) if nl.ndim >= 2 else nl
            self._cache = {
                "groups": [jax.tree.map(cp, og, ng) for og, ng in
                           zip(self._cache["groups"], new_cache["groups"])],
                "index": new_cache["index"].at[:old].set(
                    self._cache["index"]),
            }
        self.slots = n
        self.stats["resizes"] += 1

    def _maybe_apply_shrink(self):
        n = self._target_slots
        if n is None:
            return
        keep = [i for i, r in enumerate(self._active) if r is not None]
        if len(keep) > n:
            return          # still too many in flight: stay deferred
        # surviving sequences first, then free rows to pad out the batch
        perm = keep + [i for i in range(self.slots)
                       if self._active[i] is None][:n - len(keep)]
        self._active = [self._active[i] for i in perm]
        self._last_emit = [self._last_emit[i] for i in perm]
        if self._synthetic:
            self._pos = [self._pos[i] for i in perm]
        else:
            import jax
            import jax.numpy as jnp
            idx = jnp.asarray(perm)

            def take(leaf):
                return leaf[:, idx] if leaf.ndim >= 2 else leaf
            self._cache = {
                "groups": [jax.tree.map(take, g)
                           for g in self._cache["groups"]],
                "index": self._cache["index"][idx],
            }
        self.slots = n
        self._target_slots = None
        self.stats["resizes"] += 1

    # -- loop ----------------------------------------------------------------
    def _admit(self) -> int:
        """Fill free slots FIFO from the queue.  The prompt is fed
        token-by-token through the batched decode step (slot-level prefill
        interleaves with other slots' generation — continuous batching).
        A pending shrink caps admissions at the target batch size.
        Returns the number admitted."""
        cap = self._target_slots if self._target_slots is not None \
            else self.slots
        n_active = self.active_count()
        n = 0
        for i in range(self.slots):
            if n_active >= cap or self._queue.empty():
                break
            if self._active[i] is None:
                req = self._queue.get()
                req.t_admit = self._now()
                req._pending = list(int(t) for t in req.prompt)
                req._last = req._pending[-1]
                self._active[i] = req
                self._last_emit[i] = None
                with self.tracer.span("engine.reset_slot", cat="serve",
                                      slot=i):
                    self._reset_slot(i)
                n += 1
                n_active += 1
        if n:
            self.stats["admitted"] += n
        return n

    def _reset_slot(self, i: int):
        if self._synthetic:
            self._pos[i] = 0
            return
        import jax
        import jax.numpy as jnp

        def zero_rows(c):
            def z(leaf):
                return leaf.at[:, i].set(jnp.zeros_like(leaf[:, i])) \
                    if leaf.ndim >= 2 else leaf
            return jax.tree.map(z, c)
        self._cache = {
            "groups": [zero_rows(g) for g in self._cache["groups"]],
            "index": self._cache["index"].at[i].set(0),
        }

    def _decode_step(self, live: List[int]):
        """The batched decode step over the live slots: each slot's next
        token and its cache position after the step, on the host, and the
        number of slots that fed a prompt token."""
        tr = self.tracer
        with tr.span("engine.decode", cat="serve"):
            toks = np.zeros((self.slots, 1), np.int32)
            prompt = 0
            for i in live:
                r = self._active[i]
                if r._pending:
                    toks[i, 0] = r._pending[0]
                    prompt += 1
                else:
                    toks[i, 0] = r._last
            if self._synthetic:
                # deterministic pure-python "greedy decode": the next token
                # is a fixed function of the fed token, independent of
                # co-batched slots — same determinism contract as the jax
                # path
                for i in live:
                    self._pos[i] += 1
                return (5 * toks[:, 0] + 7) % _SYNTH_VOCAB, \
                    np.asarray(self._pos), prompt
            import jax
            import jax.numpy as jnp
            logits, self._cache = self._decode(self.params, self._cache,
                                               jnp.asarray(toks))
        with tr.span("engine.sample", cat="serve"):
            self._key, sub = jax.random.split(self._key)
            nxt = sample(logits[:, 0], 0.0, sub)
        # the host waits here for the device to finish the step
        with tr.span("engine.readback", cat="serve"):
            return np.asarray(nxt), np.asarray(self._cache["index"]), prompt

    def step_once(self) -> int:
        """One batched decode step across all active slots (per-slot cache
        positions diverge; cache['index'] is a per-slot vector)."""
        tr = self.tracer
        with tr.step("engine.step", int(self.stats["batches"]),
                     cat="serve") as sp:
            if self._target_slots is not None:
                with tr.span("engine.shrink", cat="serve",
                             to=self._target_slots):
                    self._maybe_apply_shrink()
            with tr.span("engine.admit", cat="serve") as asp:
                admitted = self._admit()
                asp.set(n=admitted)
            live = [i for i, r in enumerate(self._active) if r is not None]
            if not live:
                sp.set(live=0, admitted=admitted, prompt=0)
                return 0
            nxt, idx, prompt = self._decode_step(live)
            sp.set(live=len(live), admitted=admitted, prompt=prompt)
            # one clock read after the tokens reached the host stamps every
            # token and completion of this step
            now = self._now()
            with tr.span("engine.emit", cat="serve") as esp:
                emitted, done = self._emit(live, nxt, idx, now)
                esp.set(emitted=emitted, done=done)
            self.stats["tokens"] += len(live)
            self.stats["prompt_tokens"] += prompt
            self.stats["batches"] += 1
        return len(live)

    def _emit(self, live: List[int], nxt: np.ndarray, idx: np.ndarray,
              now: float):
        """Hand each slot its token; finish the requests that are done.
        Returns (tokens emitted, requests finished)."""
        emitted = done = 0
        for i in live:
            r = self._active[i]
            if r._pending:
                r._pending.pop(0)
                emit = not r._pending   # prompt consumed: first real token
            else:
                emit = True
            if emit:
                emitted += 1
                r.out_tokens.append(int(nxt[i]))
                r._last = int(nxt[i])
                # token latency: gap since the previous emit, or the full
                # queue-included wait for the first token
                prev = self._last_emit[i]
                if prev is None:
                    r.t_first_token = now
                    prev = r.t_submit if r.t_submit is not None else now
                self._tok_lat.observe(max(0.0, now - prev))
                self._last_emit[i] = now
            if len(r.out_tokens) >= r.max_new or idx[i] >= self.max_len - 1:
                done += 1
                r.done = True
                r.t_done = now
                self._active[i] = None
                self._last_emit[i] = None
                self.stats["completed"] += 1
                self.stats["tokens_out"] += len(r.out_tokens)
                if self._on_complete is not None:
                    self._on_complete(r)
        return emitted, done

    def run_until_drained(self, max_steps: int = 10_000):
        steps = 0
        while (any(self._active) or not self._queue.empty()) \
                and steps < max_steps:
            self.step_once()
            steps += 1
        return steps
