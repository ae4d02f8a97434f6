"""The serving engine's spans and stamps: written through ``obs.Tracer``'s
profiler sink into a JAX profiler session, nested per step phase; no
annotation and no jax for a disabled tracer or the synthetic engine;
``host.gc`` around a collection; request stamps in order and taken after
the tokens reach the host."""
import gc
import json
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs.archs import smoke_config
from repro.configs.base import ParallelConfig
from repro.models import model as M
from repro.serve.engine import Request, ServingEngine

ROOT = Path(__file__).resolve().parents[1]
PCFG = ParallelConfig(data=1, model=1, attn_impl="dense", fsdp=False,
                      seq_shard_acts=False)
PHASES = ("engine.admit", "engine.decode", "engine.sample",
          "engine.readback", "engine.emit")


@pytest.fixture(scope="module")
def model():
    cfg = smoke_config("minitron-8b")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def _requests(n, vocab, seed=1, max_new=4):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, vocab, size=rng.integers(2, 6))
                    .astype(np.int32), max_new=max_new) for i in range(n)]


def _engine(model, backend, **kw):
    if backend == "synthetic":
        return ServingEngine(None, None, None, **kw)
    cfg, params = model
    return ServingEngine(cfg, PCFG, params, **kw)


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    pb = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(pb))
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
             dict(ev.stats)) for pl in pd.planes if pl.name.startswith("/host")
            for ln in pl.lines for ev in ln.events
            if ev.name.startswith(("engine.", "host."))]


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_profiler_sink_puts_nested_engine_spans_in_the_trace(model,
                                                             tmp_path):
    cfg, _ = model
    eng = _engine(model, "real", batch_slots=2, max_len=32)
    assert eng.tracer.profiler and not eng.tracer.enabled
    assert eng.tracer._gc_hook is not None  # the engine's own tracer
    for r in _requests(3, cfg.vocab_size):
        eng.submit(r)
    eng.step_once()                     # compile outside the session
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(8):
            eng.step_once()
        gc.collect()
    finally:
        jax.profiler.stop_trace()
    ev = _host_events(tmp_path)
    steps = [e for e in ev if e[0] == "engine.step"]
    assert len(steps) == 8
    assert [s[3]["step_num"] for s in steps] == list(range(1, 9))
    assert all({"live", "admitted", "prompt"} <= set(s[3]) for s in steps)
    for name in PHASES:
        spans = [e for e in ev if e[0] == name]
        assert len(spans) == 8, name
        assert all(any(_inside(s, st) for st in steps) for s in spans)
    # the third request takes the slot the first frees: a reset inside an
    # admit inside a step
    resets = [e for e in ev if e[0] == "engine.reset_slot"]
    assert resets and all("slot" in r[3] for r in resets)
    admits = [e for e in ev if e[0] == "engine.admit"]
    for r in resets:
        a = next(a for a in admits if _inside(r, a))
        assert a[3]["n"] >= 1
        assert any(_inside(a, st) for st in steps)
    emits = [e for e in ev if e[0] == "engine.emit"]
    assert sum(e[3]["done"] for e in emits) >= 1
    assert any(e[0] == "host.gc" and {"gen", "collected"} <= set(e[3])
               for e in ev)


NO_JAX = r"""
import sys
import numpy as np
from repro import obs
from repro.serve.engine import Request, ServingEngine
off = obs.Tracer(capacity=4, enabled=False)
assert off.span("x") is obs.NULL_SPAN
assert off.step("x", 0) is obs.NULL_SPAN
off.begin("y"); off.end(); off.instant("z"); off.trace_gc()
eng = ServingEngine(None, None, None, batch_slots=2)
assert eng.tracer is obs.default_tracer()
assert eng.tracer._gc_hook is None      # a shared tracer is not hooked
for i in range(3):
    eng.submit(Request(i, np.arange(4, dtype=np.int32), max_new=3))
eng.run_until_drained()
ring = obs.Tracer(capacity=256)
eng = ServingEngine(None, None, None, batch_slots=2, tracer=ring)
assert ring._gc_hook is None
eng.submit(Request(9, np.arange(4, dtype=np.int32), max_new=3))
eng.run_until_drained()
print(sorted({e[0] for e in ring.events()}))
print(sorted(m for m in sys.modules if m.split(".")[0] in ("jax", "jaxlib")))
"""


def test_disabled_tracer_and_synthetic_engine_import_no_jax():
    out = subprocess.run([sys.executable, "-c", NO_JAX], capture_output=True,
                         text=True, timeout=120,
                         env={"PYTHONPATH": str(ROOT / "src"),
                              "PATH": "/usr/bin:/bin"})
    assert out.returncode == 0, out.stderr[-2000:]
    names, mods = out.stdout.strip().splitlines()[-2:]
    assert json.loads(mods.replace("'", '"')) == []
    assert set(json.loads(names.replace("'", '"'))) >= {
        "engine.step", "engine.admit", "engine.reset_slot", "engine.decode",
        "engine.emit"}


def test_host_gc_span_around_a_forced_collection():
    tr = obs.Tracer(capacity=64)
    tr.trace_gc()
    tr.trace_gc()                       # one hook however often it is asked
    with tr.span("outer"):
        gc.collect()
    ev = tr.events()
    full = [e for e in ev if e[0] == "host.gc" and e[5]["gen"] == 2]
    assert len(full) == 1
    name, cat, t0, dur, depth, args = full[0]
    assert cat == "host" and depth == 1 and "collected" in args
    outer = next(e for e in ev if e[0] == "outer")
    assert outer[2] <= t0 and t0 + dur <= outer[2] + outer[3]
    hook = tr._gc_hook
    assert hook in gc.callbacks
    del tr
    assert hook not in gc.callbacks     # the hook goes with the tracer


@pytest.mark.parametrize("backend", ["real", "synthetic"])
def test_served_requests_are_stamped_in_order(model, backend):
    cfg, _ = model
    reg = obs.MetricsRegistry()
    eng = _engine(model, backend, batch_slots=2, max_len=32, registry=reg)
    reqs = _requests(5, 256 if backend == "synthetic" else cfg.vocab_size)
    for r in reqs:
        eng.submit(r)
    eng.run_until_drained()
    for r in reqs:
        assert r.done
        assert r.t_submit <= r.t_admit <= r.t_first_token <= r.t_done
    # the new counts, mirrored into registry gauges like the old ones
    assert eng.stats["admitted"] == 5
    assert eng.stats["prompt_tokens"] == sum(len(r.prompt) for r in reqs)
    gauges = {k.split("{")[0] for k in reg.snapshot()["gauges"]}
    assert {"wi_serving_admitted", "wi_serving_prompt_tokens"} <= gauges
    assert "wi_serving_request_latency_s" not in reg.snapshot()["histograms"]


def test_tokens_are_stamped_after_they_reach_the_host(model):
    """Every token of a step, and a request's first token and completion,
    carry one clock read taken after the read-back, not one taken before
    the decode was dispatched."""
    cfg, _ = model
    ticks = iter(range(1, 10 ** 6))
    calls = []
    eng = _engine(model, "real", batch_slots=2, max_len=32,
                  now=lambda: float(next(ticks)))
    decode = eng._decode

    def spy(*a):
        calls.append(next(ticks))
        return decode(*a)
    eng._decode = spy
    req = Request(0, np.arange(3, dtype=np.int32), max_new=2)
    eng.submit(req)
    eng.run_until_drained()
    first_fed = calls[len(req.prompt) - 1]  # the step that ends the prompt
    assert req.t_first_token > first_fed
    assert req.t_done > calls[-1]
