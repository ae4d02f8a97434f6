"""Serving launcher: batched decode for any assigned architecture.

    PYTHONPATH=src python -m repro.launch.serve --arch minitron-8b --smoke \\
        --requests 12 --slots 4 --max-new 16

Serves synthetic prompts through the continuous-batching engine and prints
throughput; the engine publishes WI runtime hints (utilization-based
preemptibility) through a local manager, exactly like the training runtime.
``serve`` is the one serving path: ``main`` and ``chip_smoke.py`` call it.
"""
from __future__ import annotations

import argparse
import dataclasses
import sys
import time
from typing import List, Tuple

import numpy as np

from repro.configs.base import ModelConfig, ParallelConfig
from repro.serve.engine import Request, ServingEngine

# one device, no mesh: the engine's decode and the reference prefill
SERVE_PCFG = ParallelConfig(data=1, model=1, attn_impl="dense", fsdp=False,
                            seq_shard_acts=False)


@dataclasses.dataclass
class ServeRun:
    requests: List[Request]
    engine: ServingEngine
    step_s: List[float]        # host seconds per engine step
    hints_forwarded: int

    @property
    def served(self) -> int:
        return sum(r.done for r in self.requests)

    @property
    def tokens_out(self) -> int:
        return sum(len(r.out_tokens) for r in self.requests)


def random_params(cfg: ModelConfig, seed: int):
    """Seeded random weights, drawn on the device in one jitted program
    (eagerly, every leaf would pass through a float32 copy first)."""
    import jax
    from repro.models import model as M
    return jax.jit(M.init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed))


def make_requests(cfg: ModelConfig, n: int, prompt_len: Tuple[int, int],
                  max_new: Tuple[int, int], seed: int) -> List[Request]:
    """``n`` requests with prompt and answer lengths drawn uniformly from
    the inclusive ranges."""
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, cfg.vocab_size,
                                    size=int(rng.integers(prompt_len[0],
                                                          prompt_len[1] + 1)))
                    .astype(np.int32),
                    max_new=int(rng.integers(max_new[0], max_new[1] + 1)))
            for i in range(n)]


def serve(cfg: ModelConfig, params, requests: List[Request], *, slots: int,
          max_len: int, seed: int = 0) -> ServeRun:
    """Serve ``requests`` to completion on one engine, publishing WI runtime
    hints every 16 steps.  Each step ends by reading its sampled tokens back
    to the host, so a step's host time covers its device work."""
    from repro.core.global_manager import GlobalManager
    from repro.core.local_manager import LocalManager

    gm = GlobalManager(hint_rate_per_s=1e6, hint_burst=1e6)
    gm.register_workload("serve-job", {"scale_out_in": True,
                                       "delay_tolerance_ms": 500.0,
                                       "preemptibility_pct": 30.0})
    lm = LocalManager("rack0/srv0", gm.bus, clock=gm.clock,
                      vm_hint_rate_per_s=1e6, vm_hint_burst=1e6)
    ep = lm.attach_vm("vm0", "serve-job")

    eng = ServingEngine(cfg, SERVE_PCFG, params, batch_slots=slots,
                        max_len=max_len, seed=seed)
    for r in requests:
        eng.submit(r)
    step_s = []
    while (eng.active_count() or eng.queue_depth()) \
            and len(step_s) < 100_000:
        t0 = time.perf_counter()
        eng.step_once()
        step_s.append(time.perf_counter() - t0)
        if len(step_s) % 16 == 0:
            ep.set_runtime_hints({
                "preemptibility_pct": 20.0 if eng.utilization() > 0.5
                else 80.0,
                "x-utilization": eng.utilization(),
                "x-queue-depth": eng.queue_depth()})
    return ServeRun(requests, eng, step_s, lm.stats["vm_hints_forwarded"])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.configs.archs import ARCHS, smoke_config
    from repro.launch import compile_cache

    compile_cache.enable()
    cfg = smoke_config(args.arch) if args.smoke else ARCHS[args.arch]
    reqs = make_requests(cfg, args.requests, (args.prompt_len,) * 2,
                         (args.max_new,) * 2, args.seed)
    t0 = time.perf_counter()
    run = serve(cfg, random_params(cfg, args.seed), reqs, slots=args.slots,
                max_len=args.max_len, seed=args.seed)
    dt = time.perf_counter() - t0
    print(f"served {run.served}/{len(reqs)} requests, {run.tokens_out} "
          f"tokens in {dt:.2f}s ({run.tokens_out / dt:.1f} tok/s, "
          f"{len(run.step_s)} engine steps)")
    print(f"engine stats: {run.engine.stats}; hints forwarded: "
          f"{run.hints_forwarded}")
    print("sample:", reqs[0].out_tokens[:10])
    return 0 if run.served == len(reqs) else 1


if __name__ == "__main__":
    sys.exit(main())
