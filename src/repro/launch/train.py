"""Training launcher: run any assigned architecture under the WI runtime.

    PYTHONPATH=src python -m repro.launch.train --arch minitron-8b --smoke \\
        --steps 50 [--devices 8] [--model-axis 2] [--ckpt-dir /tmp/ck] \\
        [--inject-eviction-at 20] [--batch 16] [--seq 128]

--smoke uses the reduced config (CPU-friendly); without it the full config
is used (requires a real TPU slice — the production mesh shardings come
from launch/steps.py).  ``--devices N`` forces N virtual host devices
(set before jax import, so it must be the launcher, not the library).
``elastic_trainer`` builds the standalone trainer and its fault injector
for ``main`` and ``chip_smoke.py --chips 4``.
"""
import argparse
import os
import sys


def elastic_trainer(cfg, *, ckpt_dir, steps, model_axis=1, batch=8, seq=64,
                    lr=1e-3, ckpt_every=20, seed=0, data_cfg=None):
    """A standalone ``WITrainer`` over every visible device, and the
    ``FaultInjector`` that sends it platform events.  ``seed`` draws the
    initial weights and, unless ``data_cfg`` is given, the token stream."""
    from repro.configs.base import RunConfig
    from repro.core.global_manager import GlobalManager
    from repro.data.pipeline import DataConfig
    from repro.runtime.faults import FaultInjector
    from repro.runtime.trainer import WITrainer

    rcfg = RunConfig(model=cfg, seed=seed, learning_rate=lr,
                     warmup_steps=max(steps // 10, 1), total_steps=steps)
    gm = GlobalManager(hint_rate_per_s=1e6, hint_burst=1e6)
    tr = WITrainer(rcfg, gm, ckpt_dir=ckpt_dir, model_axis=model_axis,
                   ckpt_every=ckpt_every, batch_override=batch,
                   seq_override=seq,
                   data_cfg=data_cfg or DataConfig(seed=seed))
    return tr, FaultInjector(gm, tr.workload)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--inject-eviction-at", type=int, default=0)
    ap.add_argument("--inject-harvest-at", type=int, default=0)
    ap.add_argument("--data", default=None, help="tokenized binary file")
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    import tempfile
    from repro.configs.archs import ARCHS, smoke_config
    from repro.data.pipeline import DataConfig
    from repro.launch import compile_cache

    compile_cache.enable()
    cfg = smoke_config(args.arch) if args.smoke else ARCHS[args.arch]
    dcfg = (DataConfig(kind="file", path=args.data) if args.data
            else DataConfig())
    tr, inj = elastic_trainer(
        cfg, ckpt_dir=args.ckpt_dir or tempfile.mkdtemp(), steps=args.steps,
        model_axis=args.model_axis, batch=args.batch, seq=args.seq,
        lr=args.lr, ckpt_every=args.ckpt_every, data_cfg=dcfg)

    def hooks(t):
        if args.inject_eviction_at and t.step == args.inject_eviction_at:
            print(f"[wi] injecting eviction at step {t.step}", flush=True)
            inj.evict(n_devices=t.model_axis)
        if args.inject_harvest_at and t.step == args.inject_harvest_at:
            print(f"[wi] injecting harvest offer at step {t.step}",
                  flush=True)
            inj.offer_capacity(n_devices=t.model_axis)

    tr.run(args.steps, step_callback=hooks)
    for m in tr.metrics_log[:: max(1, args.steps // 20)]:
        print(f"step {m['step']:5d} loss {m['loss']:.4f} dp {m['dp']} "
              f"{m['ms']:.0f} ms")
    print(f"final loss {tr.metrics_log[-1]['loss']:.4f}; "
          f"events: {[e['kind'] for e in tr.events_log]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
