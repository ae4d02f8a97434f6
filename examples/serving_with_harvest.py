"""Serve a small LM with batched requests while reacting to WI platform
hints: harvest offers grow the decode batch slots, eviction notices drain.

    PYTHONPATH=src python examples/serving_with_harvest.py
"""
import sys
sys.path.insert(0, "src")

import numpy as np


def main():
    import jax
    from repro.configs.archs import smoke_config
    from repro.configs.base import ParallelConfig
    from repro.core import hints as H
    from repro.core.global_manager import GlobalManager
    from repro.core.local_manager import LocalManager
    from repro.models import model as M
    from repro.serve.engine import Request, ServingEngine

    cfg = smoke_config("minitron-8b")
    pcfg = ParallelConfig(data=1, model=1, attn_impl="dense", fsdp=False,
                          seq_shard_acts=False)
    params = M.init_params(cfg, jax.random.PRNGKey(0))

    gm = GlobalManager(hint_rate_per_s=1e6, hint_burst=1e6)
    gm.register_workload("llm-serve", {
        "scale_up_down": True, "scale_out_in": True,
        "delay_tolerance_ms": 500.0, "preemptibility_pct": 30.0})
    lm = LocalManager("rack0/srv0", gm.bus, clock=gm.clock,
                      vm_hint_rate_per_s=1e6, vm_hint_burst=1e6)
    ep = lm.attach_vm("vm-serve", "llm-serve")

    eng = ServingEngine(cfg, pcfg, params, batch_slots=2, max_len=96)

    def on_event(e):
        if e["event"] == H.PlatformEvent.SCALE_UP_OFFER.value:
            # grow decode slots onto harvested capacity: new engine with
            # more slots; in-flight requests keep their caches... here we
            # drain-then-grow for simplicity
            print(f"  [serve] harvest offer: growing slots 2 -> 4")
            eng.grow_requested = True
    ep.on_event(on_event)
    eng.grow_requested = False

    rng = np.random.default_rng(0)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, size=6)
                    .astype(np.int32), max_new=8) for i in range(10)]
    for r in reqs[:6]:
        eng.submit(r)

    # the engine is a WI workload: utilization + queue depth become hints
    for tick in range(200):
        eng.step_once()
        if tick % 10 == 0:
            ep.set_runtime_hints({
                "x-utilization": eng.utilization(),
                "x-queue-depth": eng.queue_depth(),
                "preemptibility_pct": 20.0 if eng.utilization() > 0.5
                else 80.0})
        if tick == 20:
            # platform sees queue pressure -> harvest offer
            gm.publish_platform_hint(H.PlatformHint(
                event=H.PlatformEvent.SCALE_UP_OFFER.value,
                workload="llm-serve", resource="rack0/srv0/vm-serve",
                payload={"n_devices": 2}, source_opt="harvest"))
            for r in reqs[6:]:
                eng.submit(r)
        if eng.grow_requested:
            # migrate: finish current, rebuild with 4 slots
            eng.run_until_drained()
            done_tokens = {r.rid: r.out_tokens for r in reqs if r.done}
            eng2 = ServingEngine(cfg, pcfg, params, batch_slots=4, max_len=96)
            for r in reqs:
                if not r.done:
                    eng2.submit(r)
            eng2.stats.update(requests=eng.stats["requests"])
            eng = eng2
            eng.grow_requested = False
        if all(r.done for r in reqs):
            break
    eng.run_until_drained()
    assert all(r.done for r in reqs)
    print(f"served {len(reqs)} requests; engine stats: {eng.stats}")
    print("sample completion:", reqs[0].out_tokens)
    print("OK")


if __name__ == "__main__":
    main()
