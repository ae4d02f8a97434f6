"""Tests for the observability layer (src/repro/obs/): metrics registry,
tick-phase tracer with Perfetto export, and the bus-fed lifecycle
observer — including the proof that default (disabled) instrumentation
stays far under the 2% placement-throughput budget."""
import json
import time

from repro import obs
from repro.agents import STATEFUL, STATELESS, AgentPolicy, AgentRuntime
from repro.core import hints as H
from repro.core.bus import Bus
from repro.sched import Scheduler
from repro.sim.cluster import VM


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_registry_counters_gauges_and_label_series():
    reg = obs.MetricsRegistry(enabled=True)
    c = reg.counter("ev_total", "events by kind", event="notice")
    c.inc(3)
    c.labels(event="evicted").inc()
    # repeated lookups return the same cached series
    assert reg.counter("ev_total", event="notice") is c
    assert reg.counter("ev_total", event="notice").value == 3.0
    assert reg.counter("ev_total", event="evicted").value == 1.0
    g = reg.gauge("depth")
    g.set(4)
    g.dec()
    assert g.value == 3.0


def test_histogram_percentiles_are_clamped_to_observed_extrema():
    reg = obs.MetricsRegistry(enabled=True)
    h = reg.histogram("lat_s", buckets=(1.0, 2.0, 5.0, 10.0))
    for v in (0.4, 1.5, 1.6, 3.0, 7.0):
        h.observe(v)
    assert h.count == 5 and h.sum == 13.5
    assert h.percentile(0) == 0.4          # exact min
    assert h.percentile(100) == 7.0        # exact max
    assert 0.4 <= h.percentile(50) <= h.percentile(95) <= 7.0
    s = h.summary()
    assert s["count"] == 5 and s["min"] == 0.4 and s["max"] == 7.0


def test_prometheus_exposition_has_buckets_sum_and_count():
    reg = obs.MetricsRegistry(enabled=True)
    reg.counter("ev_total", "events", event="notice").inc(3)
    reg.gauge("depth").set(4)
    reg.histogram("lat_s", buckets=(1.0, 10.0)).observe(0.5)
    text = reg.render_prometheus()
    assert "# TYPE ev_total counter" in text
    assert 'ev_total{event="notice"} 3.0' in text
    assert "# TYPE lat_s histogram" in text
    assert 'lat_s_bucket{le="1.0"} 1' in text
    assert 'lat_s_bucket{le="+Inf"} 1' in text
    assert "lat_s_sum 0.5" in text and "lat_s_count 1" in text


def test_collectors_are_pulled_only_at_snapshot_time():
    reg = obs.MetricsRegistry(enabled=True)
    calls = []
    reg.add_collector("sched", lambda: (calls.append(1), {"placed": 7})[1])
    assert calls == []                     # registration costs nothing
    snap = reg.snapshot()
    assert calls == [1]
    assert snap["collected"]["sched"] == {"placed": 7}


def test_disabled_registry_hands_out_one_shared_null_instrument():
    reg = obs.MetricsRegistry(enabled=False)
    # identity is the proof: no allocation per call site
    assert reg.counter("a") is obs.NULL_INSTRUMENT
    assert reg.gauge("b") is reg.histogram("c", buckets=(1.0,))
    obs.NULL_INSTRUMENT.inc()
    obs.NULL_INSTRUMENT.observe(1.0)
    assert obs.NULL_INSTRUMENT.labels(x=1) is obs.NULL_INSTRUMENT
    reg.add_collector("x", lambda: {"never": "called"})
    assert reg.snapshot() == {"counters": {}, "gauges": {},
                              "histograms": {}}


def test_metricdict_keeps_defaultdict_semantics_and_mirrors_gauges():
    reg = obs.MetricsRegistry(enabled=True)
    m = obs.MetricDict(reg, prefix="wi_agents_")
    m["acks"] += 1
    m["acks"] += 2
    m["lost_s"] = 4.5
    assert m["acks"] == 3.0
    assert m.get("missing") == 0.0 and "missing" not in m
    assert dict(m) == {"acks": 3.0, "lost_s": 4.5}
    assert reg.snapshot()["gauges"]["wi_agents_acks"] == 3.0


def test_process_defaults_start_disabled_and_swap_cleanly():
    assert not obs.default_registry().enabled
    assert not obs.default_tracer().enabled
    reg = obs.MetricsRegistry(enabled=True)
    prev = obs.set_default_registry(reg)
    try:
        assert obs.default_registry() is reg
    finally:
        assert obs.set_default_registry(prev) is reg
    assert obs.default_registry() is prev


# ---------------------------------------------------------------------------
# tick-phase tracer
# ---------------------------------------------------------------------------


def test_tracer_records_nested_spans_with_depths_and_args():
    tr = obs.Tracer(capacity=16)
    with tr.span("sched.tick", t_sim=5.0):
        with tr.span("sched.placement_drain") as sp:
            sp.set(placed=12, unplaced=0)
    inner, outer = tr.events()             # inner exits (records) first
    assert inner[0] == "sched.placement_drain" and inner[4] == 1
    assert inner[5] == {"placed": 12, "unplaced": 0}
    assert outer[0] == "sched.tick" and outer[4] == 0
    assert outer[5] == {"t_sim": 5.0}
    bd = tr.phase_breakdown()
    assert bd["sched.tick"]["count"] == 1
    assert bd["sched.tick"]["total_s"] >= bd["sched.placement_drain"][
        "total_s"]


def test_tracer_ring_wraparound_keeps_newest_and_counts_dropped():
    tr = obs.Tracer(capacity=8)
    for i in range(20):
        with tr.span(f"s{i}"):
            pass
    assert tr.recorded == 8 and tr.dropped == 12
    assert [e[0] for e in tr.events()] == [f"s{i}" for i in range(12, 20)]


def test_chrome_trace_export_is_valid_trace_event_json(tmp_path):
    tr = obs.Tracer(capacity=4)
    for i in range(6):                     # wraps: keeps s2..s5
        with tr.span(f"s{i}", cat="evict", v=i):
            pass
    path = tr.write(str(tmp_path / "t.trace.json"), process_name="wi-test")
    with open(path) as fh:
        doc = json.load(fh)
    evs = doc["traceEvents"]
    assert evs[0]["ph"] == "M" and evs[0]["args"]["name"] == "wi-test"
    xs = evs[1:]
    assert len(xs) == 4
    assert all(e["ph"] == "X" for e in xs)
    required = {"name", "cat", "ph", "ts", "dur", "pid", "tid"}
    assert all(required <= set(e) for e in xs)
    assert all(e["ts"] >= 0 and e["dur"] >= 0 for e in xs)
    assert [e["ts"] for e in xs] == sorted(e["ts"] for e in xs)
    assert doc["otherData"] == {"recorded": 4, "dropped": 2}


def test_disabled_tracer_returns_the_shared_null_span():
    tr = obs.Tracer(capacity=4, enabled=False)
    assert tr.span("x") is obs.NULL_SPAN
    with tr.span("x") as sp:
        sp.set(anything=1)
    tr.begin("y")
    tr.end()
    tr.instant("z")
    assert tr.recorded == 0 and tr.dropped == 0


def test_tracer_sinks_ring_and_profiler_apart_and_together():
    only = obs.Tracer(capacity=4, enabled=False, profiler=True)
    with only.span("x", a=1) as sp:
        assert sp is not obs.NULL_SPAN
        sp.set(b=2)
    with only.step("s", 3):
        pass
    assert only.recorded == 0
    both = obs.Tracer(capacity=8, profiler=True)
    with both.step("engine.step", 7, live=2) as sp:
        sp.set(admitted=1)
        both.begin("host.gc", cat="host", gen=0)
        both.end(collected=5)
    both.instant("mark", k=1)
    gc_ev, step_ev, mark = both.events()
    assert gc_ev[:2] == ("host.gc", "host") and gc_ev[4] == 1
    assert gc_ev[5] == {"gen": 0, "collected": 5}
    assert step_ev[0] == "engine.step"
    assert step_ev[5] == {"live": 2, "step_num": 7, "admitted": 1}
    assert mark[0] == "mark" and mark[3] == 0.0


# ---------------------------------------------------------------------------
# lifecycle observer
# ---------------------------------------------------------------------------


def _eviction(bus, **kw):
    bus.publish(H.TOPIC_EVICTIONS, kw)


def test_lifecycle_observer_derives_histograms_from_raw_records():
    bus = Bus()
    o = obs.LifecycleObserver(bus)
    _eviction(bus, event="notice", vm="v0", workload="web-3",
              t=10.0, notice_s=30.0)
    bus.publish(H.TOPIC_EVENT_ACKS, {
        "vm": "v0", "t": 12.5, "event": H.PlatformEvent.EVICTION_NOTICE.value})
    _eviction(bus, event="early_released", vm="v0", workload="web-3", t=13.0)
    _eviction(bus, event="notice", vm="v1", workload="web-7",
              t=10.0, notice_s=30.0)
    _eviction(bus, event="evicted", vm="v1", workload="web-7",
              t=40.0, notice_s=30.0, lead_time_s=30.0)
    s = o.summary()
    assert s["notices"] == 2 and s["early_released"] == 1 and s["killed"] == 1
    assert s["violations"] == 0 and s["late_acks"] == 0
    assert s["outstanding"] == 0
    assert s["notice_to_ack_s"]["count"] == 1
    assert abs(s["notice_to_ack_s"]["max"] - 2.5) < 1e-9
    assert abs(s["ack_to_release_s"]["max"] - 0.5) < 1e-9
    assert abs(s["kill_lead_s"]["min"] - 30.0) < 1e-9
    # both replicas pooled under one workload class
    snap = o.registry.snapshot()
    assert ('wi_lifecycle_events_total{event="notice",'
            'workload_class="web"}') in snap["counters"]


def test_lifecycle_observer_handles_release_record_beating_the_ack():
    # bus delivery is synchronous in subscription order: the scheduler's
    # ack handler (subscribed first) can publish the early_released record
    # before the ack record itself reaches the observer
    bus = Bus()
    o = obs.LifecycleObserver(bus)
    _eviction(bus, event="notice", vm="v0", workload="web-1",
              t=10.0, notice_s=30.0)
    _eviction(bus, event="early_released", vm="v0", workload="web-1", t=15.0)
    bus.publish(H.TOPIC_EVENT_ACKS, {
        "vm": "v0", "t": 14.0, "event": H.PlatformEvent.EVICTION_NOTICE.value})
    s = o.summary()
    assert s["notice_to_ack_s"]["count"] == 1
    assert abs(s["notice_to_ack_s"]["max"] - 4.0) < 1e-9
    assert s["ack_to_release_s"]["count"] == 1
    assert abs(s["ack_to_release_s"]["max"] - 1.0) < 1e-9


def test_lifecycle_observer_reconciles_against_a_live_storm():
    reg = obs.MetricsRegistry(enabled=True)
    s = Scheduler(default_notice_s=30.0, metrics=reg)
    o = obs.LifecycleObserver(s.gm.bus, registry=reg)
    for i in range(2):
        s.cluster.add_server(f"region-0/s{i}", 32)
    s.gm.register_workload("web", {
        "scale_out_in": True, "preemptibility_pct": 70.0,
        "availability_nines": 2.0, "delay_tolerance_ms": 5_000.0})
    s.gm.register_workload("batch", {"preemptibility_pct": 90.0})
    for i in range(3):
        s.submit(VM(f"v{i}", "web", "", 8, spot=True))
    s.submit(VM("b0", "batch", "", 8, spot=True))
    s.schedule_pending()
    # web acks immediately and early-releases; batch's checkpoint (30 GB at
    # 0.2 GB/s, 150 s) cannot beat the 30 s window, so it rides the ladder
    # to a full-lead kill
    AgentRuntime(s, policies={
        "web": AgentPolicy(statefulness=STATELESS, scale_out_in=True),
        "batch": AgentPolicy(statefulness=STATEFUL, state_gb=30.0,
                             ckpt_gbps=0.2)})
    s.capacity_crunch("region-0", 32)
    s.run_until(200.0)
    recon = o.reconcile(s.evictor)
    assert recon["ok"], recon["diffs"]
    life = o.summary()
    assert life["notices"] >= 2
    assert life["early_released"] == s.evictor.stats["early_releases"] > 0
    assert life["killed"] == s.evictor.stats["kills"] > 0
    assert life["violations"] == 0 and life["outstanding"] == 0
    # every ladder kill honored the full hinted window
    assert life["kill_lead_s"]["min"] >= 30.0 - 1e-9
    # every web ack was observed and landed inside its window
    assert life["notice_to_ack_s"]["count"] == life["early_released"]
    assert life["min_ack_margin_s"] >= 0.0
    # decision records flowed: the placement batch was counted
    assert reg.counter("wi_sched_decisions_total", kind="place").value >= 4
    o.close()


# ---------------------------------------------------------------------------
# overhead budget
# ---------------------------------------------------------------------------


def test_disabled_instrumentation_is_under_the_two_percent_budget():
    # a real pending-queue drain with everything at defaults (disabled
    # registry + tracer) -- the configuration the sched_scale benchmark
    # times
    s = Scheduler()
    assert not s.metrics.enabled and not s.tracer.enabled
    for i in range(32):
        s.cluster.add_server(f"s{i}", 64)
    for i in range(1000):
        s.submit(VM(f"v{i}", f"w-{i % 20}", "", 2))
    t0 = time.perf_counter()
    s.schedule_pending()
    drain_s = time.perf_counter() - t0
    assert s.stats["placed"] >= 500

    # per-drain instrumentation cost: one span plus the placed/unplaced
    # counter handouts.  Measure it directly on the disabled defaults and
    # project against the measured drain -- flake-safe because the no-op
    # path is ~1e5x cheaper than the drain itself.
    tracer, reg = s.tracer, s.metrics
    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        with tracer.span("sched.placement_drain") as sp:
            sp.set(placed=1, unplaced=0)
        reg.counter("wi_sched_placed_total").inc(1)
        reg.counter("wi_sched_unplaced_total").inc(1)
    per_drain_overhead = (time.perf_counter() - t0) / n
    assert per_drain_overhead < 0.02 * drain_s, (
        f"disabled instrumentation {per_drain_overhead * 1e6:.2f}us/drain "
        f"vs drain {drain_s * 1e3:.2f}ms")
