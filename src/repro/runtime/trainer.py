"""WI-integrated elastic trainer.

The training job is a WI *workload*:
  * at deployment it publishes hints derived from its own config — elastic
    width => scale_out_in, checkpoint cadence => preemptibility, restart
    latency => deploy_time,
  * at runtime the per-host local manager publishes x-step-time (straggler
    telemetry) and flips preemptibility low while a checkpoint is stale,
  * it subscribes to platform hints and reacts:
      EVICTION_NOTICE / SCALE_DOWN_NOTICE -> emergency checkpoint, shrink the
        data-parallel width (drop the evicted hosts), re-jit, reshard, resume;
      SCALE_UP_OFFER -> grow DP width onto offered hosts;
      THROTTLE_NOTICE / UNDERCLOCK_NOTICE -> halve microbatch (less compute
        per unit time) until the event clears.

Elasticity is real: the mesh is rebuilt over the surviving device set and
params/opt state are resharded with device_put.  The data pipeline is
stateless-per-step, so no sample is lost or repeated across resizes.

The trainer runs in one of two modes:

  * **standalone** (default, ``standalone=True``) — it owns a
    ``LocalManager``/``VMEndpoint`` pair for a single synthetic VM and
    drains platform events itself.  This is the unit-test path driven by
    ``repro.chaos.FaultInjector``.
  * **scheduler tenant** (``standalone=False``) — the training job's VMs
    are placed, noticed, and killed by the real platform scheduler
    (``repro.sched``), and ``repro.agents.trainer_agent.TrainerTenant``
    owns the endpoints (one per placed VM, through the agent runtime) and
    the VM->device mapping.  The tenant calls the public elastic surface
    below (``emergency_checkpoint`` / ``resize_to_devices`` /
    ``set_throttled`` / ``step_once``); runtime hints flow out through
    ``hint_sink`` (wired to the leader agent's guest channel).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.ckpt.checkpoint import Checkpointer, CheckpointCorruptError
from repro.configs.base import (ModelConfig, ParallelConfig, RunConfig,
                                pconfig_replace)
from repro.core import hints as H
from repro.core.global_manager import GlobalManager
from repro.core.local_manager import LocalManager, VMEndpoint
from repro.data.pipeline import make_dataset, DataConfig
from repro.launch import steps as ST
from repro.models import model as Mdl
from repro.models import sharding as SH
from repro.runtime.straggler import StragglerDetector
from repro.train import optimizer as opt


def deployment_hints_from(rcfg: RunConfig, ckpt_every: int,
                          elastic: bool) -> Dict:
    """The WI mapping for training jobs (DESIGN.md §2 table)."""
    return {
        "scale_out_in": bool(elastic),
        "scale_up_down": bool(elastic),
        # a job that checkpoints every N steps tolerates losing < N steps:
        # high preemptibility, bounded by how much compute a restart wastes
        "preemptibility_pct": 80.0 if elastic else 20.0,
        "delay_tolerance_ms": 60_000.0,
        "deploy_time_ms": 300_000.0,      # tolerant restart latency
        "availability_nines": 2.0,
        "region_independent": True,
    }


def parallel_config(dp: int, model_axis: int,
                    throttled: bool = False) -> ParallelConfig:
    """The trainer's layout on a dp x model_axis mesh.  Rematerialisation
    stays at ``ParallelConfig``'s default: without it the activations of a
    published-width step do not fit one chip."""
    return ParallelConfig(pod=1, data=dp, model=model_axis, fsdp=False,
                          seq_shard_acts=False, attn_impl="dense",
                          microbatch=2 if throttled else 0)


def build_step(cfg: ModelConfig, rcfg: RunConfig, pcfg: ParallelConfig,
               mesh: Mesh, batch_like: Dict):
    """The jitted train step on ``mesh`` (params and optimizer state
    donated) with its shardings: (step, pshard, oshard, bshard, rules).
    Tracing needs ``SH.set_mesh(mesh, rules)`` in effect."""
    pshard, oshard, rules = ST.train_shardings(cfg, pcfg, mesh)
    bshard = {k: NamedSharding(mesh, P("data", *([None] * (v.ndim - 1))))
              for k, v in batch_like.items()}
    step = jax.jit(ST.build_train_fn(cfg, pcfg, rcfg, mesh),
                   in_shardings=(pshard, oshard, bshard),
                   out_shardings=(pshard, oshard, None),
                   donate_argnums=(0, 1))
    return step, pshard, oshard, bshard, rules


class WITrainer:
    def __init__(self, rcfg: RunConfig, gm: GlobalManager,
                 ckpt_dir: str, devices: Optional[Sequence] = None,
                 model_axis: int = 1, ckpt_every: int = 20,
                 min_dp: int = 1, data_cfg: DataConfig = DataConfig(),
                 workload: str = "train-job", server: str = "rack0/host0",
                 batch_override: Optional[int] = None,
                 seq_override: Optional[int] = None,
                 standalone: bool = True,
                 hint_sink: Optional[Callable[[Dict], None]] = None):
        self.rcfg, self.gm = rcfg, gm
        self.cfg: ModelConfig = rcfg.model
        self.workload = workload
        self.ckpt = Checkpointer(ckpt_dir)
        self.ckpt_every = ckpt_every
        self.min_dp = min_dp
        self.model_axis = model_axis
        self.detector = StragglerDetector()
        self.devices = list(devices if devices is not None
                            else jax.devices())
        self.excluded: List = []
        self.batch = batch_override or 8
        self.seq = seq_override or 64
        self.data = make_dataset(self.cfg, self.batch, self.seq, data_cfg)
        self.metrics_log: List[Dict] = []
        self.events_log: List[Dict] = []
        self.step = 0
        self._throttled = False

        self.hint_sink = hint_sink
        self.local: Optional[LocalManager] = None
        self.endpoint: Optional[VMEndpoint] = None
        if standalone:
            # legacy single-VM mode: the trainer owns its guest channel
            gm.register_workload(workload, deployment_hints_from(
                rcfg, ckpt_every, elastic=True))
            self.local = LocalManager(server, gm.bus, clock=gm.clock,
                                      vm_hint_rate_per_s=1e6,
                                      vm_hint_burst=1e6)
            self.endpoint = self.local.attach_vm("vm0", workload)
            self.endpoint.on_event(self._on_platform_event)
        self._pending_events: List[Dict] = []

        self._build(self.devices)
        self._init_state()

    # -- mesh / jit lifecycle --------------------------------------------------
    def _build(self, devices: Sequence):
        dp = max(self.min_dp, len(devices) // self.model_axis)
        devices = list(devices)[: dp * self.model_axis]
        self.active_devices = devices
        dev_array = np.asarray(devices).reshape(dp, self.model_axis)
        self.mesh = Mesh(dev_array, ("data", "model"))
        self.pcfg = parallel_config(dp, self.model_axis, self._throttled)
        (self._train_step, self.pshard, self.oshard, self.bshard,
         rules) = build_step(self.cfg, self.rcfg, self.pcfg, self.mesh,
                             self.data.batch_at(0))
        SH.set_mesh(self.mesh, rules)
        self.dp = dp

    def _init_state(self):
        # newest committed checkpoint first; a corrupt/torn one (crash mid
        # emergency checkpoint) falls back to the previous durable
        # generation — lost work is bounded by the checkpoint interval, the
        # job never bricks on a bad restore
        for ck_step in reversed(self.ckpt.committed_steps()):
            try:
                self._restore(ck_step)
                return
            except CheckpointCorruptError:
                self.events_log.append({"kind": "corrupt_checkpoint_skipped",
                                        "step": ck_step})
        self.params = jax.device_put(
            Mdl.init_params(self.cfg, jax.random.PRNGKey(self.rcfg.seed)),
            self.pshard)
        self.opt_state = jax.device_put(
            opt.init_opt_state(self.rcfg, self.params, self.pcfg),
            self.oshard)

    def _restore(self, ck_step: int):
        like_p = Mdl.abstract_params(self.cfg)
        like_o = opt.init_opt_state(self.rcfg, like_p, self.pcfg,
                                    abstract=True)
        tree = self.ckpt.restore(
            ck_step, {"params": like_p, "opt": like_o},
            {"params": self.pshard, "opt": self.oshard})
        self.params, self.opt_state = tree["params"], tree["opt"]
        self.step = self.ckpt.metadata(ck_step).get("step", ck_step)

    def _checkpoint(self, sync=False):
        tree = {"params": self.params, "opt": self.opt_state}
        md = {"step": self.step, "dp": self.dp}
        if sync:
            self.ckpt.save(self.step, tree, md)
        else:
            self.ckpt.save_async(self.step, tree, md)
        self.events_log.append({"kind": "checkpoint", "step": self.step,
                                "sync": sync})

    # -- WI event handling -----------------------------------------------------
    def _on_platform_event(self, event: Dict):
        self._pending_events.append(event)

    def poll_events(self):
        """Standalone mode: apply the platform events received so far
        (resize, throttle) without taking a step; ``run`` calls it before
        every step."""
        evs, self._pending_events = self._pending_events, []
        for e in evs:
            kind = e.get("event")
            self.events_log.append({"kind": kind, "step": self.step,
                                    "payload": e.get("payload", {})})
            if kind in (H.PlatformEvent.EVICTION_NOTICE.value,
                        H.PlatformEvent.SCALE_DOWN_NOTICE.value):
                n_lost = int(e.get("payload", {}).get("n_devices", 0)) or \
                    self.model_axis
                self._resize(len(self.active_devices) - n_lost)
                self.endpoint.ack_event(e.get("seq", 0))
            elif kind == H.PlatformEvent.SCALE_UP_OFFER.value:
                n_new = int(e.get("payload", {}).get("n_devices", 0)) or \
                    self.model_axis
                target = min(len(self.devices),
                             len(self.active_devices) + n_new)
                self._resize(target)
                self.endpoint.ack_event(e.get("seq", 0))
            elif kind in (H.PlatformEvent.THROTTLE_NOTICE.value,
                          H.PlatformEvent.UNDERCLOCK_NOTICE.value):
                self.set_throttled(True)
            elif kind == H.PlatformEvent.OVERCLOCK_OFFER.value:
                self.set_throttled(False)

    def _rebuild_same_devices(self):
        self._checkpoint(sync=True)
        self.ckpt.wait()
        self._build(self.active_devices)
        self._reshard()

    def _resize(self, n_devices: int):
        """Elastic resize to n_devices (floor at min_dp x model_axis)."""
        n_devices = max(self.min_dp * self.model_axis,
                        (n_devices // self.model_axis) * self.model_axis)
        if n_devices == len(self.active_devices):
            return
        self._checkpoint(sync=True)
        self.ckpt.wait()
        usable = [d for d in self.devices if d not in self.excluded]
        self._build(usable[:n_devices])
        self._reshard()
        self.events_log.append({"kind": "resize", "step": self.step,
                                "dp": self.dp,
                                "devices": len(self.active_devices)})

    def _reshard(self):
        self.params = jax.device_put(
            jax.tree.map(np.asarray, self.params), self.pshard)
        self.opt_state = jax.device_put(
            jax.tree.map(np.asarray, self.opt_state), self.oshard)

    # -- public elastic surface (scheduler-tenant mode) ----------------------
    def emergency_checkpoint(self):
        """Eviction notice: make the state durable *now* (sync save + join)
        so the guest can ack the notice and hand the VM back early."""
        self._checkpoint(sync=True)
        self.ckpt.wait()
        self.events_log.append({"kind": "emergency_checkpoint",
                                "step": self.step})

    def resize_to_devices(self, devices: Sequence) -> bool:
        """Elastic resize onto an explicit device set (the tenant's VM ->
        device mapping after a kill / replacement / harvest grant).  Returns
        False — and leaves the current mesh untouched — when the set is too
        small for even the minimum mesh; the caller pauses stepping until
        capacity returns."""
        devices = list(devices)
        if len(devices) < self.min_dp * self.model_axis:
            return False
        # _build floors the mesh to dp*model_axis devices, so compare the
        # usable prefix — an odd-sized set must not re-jit an identical mesh
        dp = max(self.min_dp, len(devices) // self.model_axis)
        if devices[: dp * self.model_axis] == self.active_devices:
            return True
        self._checkpoint(sync=True)
        self.ckpt.wait()
        self._build(devices)
        self._reshard()
        self.events_log.append({"kind": "resize", "step": self.step,
                                "dp": self.dp,
                                "devices": len(self.active_devices)})
        return True

    def set_throttled(self, on: bool):
        """Platform throttle/underclock notice (or its clearing): halve the
        microbatch (less compute per unit time) until the event clears."""
        if bool(on) == self._throttled:
            return
        self._throttled = bool(on)
        self._rebuild_same_devices()
        self.events_log.append({"kind": "throttle" if on else "restore",
                                "step": self.step})

    def state_bytes(self) -> int:
        """Checkpointable state size (params + optimizer), for modeling
        checkpoint write latency in simulated time."""
        leaves = jax.tree.leaves({"params": self.params,
                                  "opt": self.opt_state})
        return int(sum(np.asarray(l).nbytes for l in leaves))

    # -- runtime hints -----------------------------------------------------------
    def _publish_runtime_hints(self, step_ms: float):
        fresh = (self.step % self.ckpt_every) < max(1, self.ckpt_every // 4)
        hints = {
            "preemptibility_pct": 90.0 if fresh else 40.0,
            "x-step-time-ms": step_ms,
            "x-dp-width": self.dp,
        }
        if self.endpoint is not None:
            self.endpoint.set_runtime_hints(hints)
        elif self.hint_sink is not None:
            self.hint_sink(hints)
        self.detector.record(f"host-dp{self.step % max(self.dp, 1)}", step_ms)

    # -- main loop -----------------------------------------------------------
    def step_once(self) -> Dict:
        """One training step on the current mesh (the tenant interleaves
        these with the platform's simulated clock)."""
        batch = {k: jax.device_put(v, self.bshard[k])
                 for k, v in self.data.batch_at(self.step).items()}
        t0 = time.perf_counter()
        self.params, self.opt_state, metrics = self._train_step(
            self.params, self.opt_state, batch)
        loss = float(metrics["loss"])
        dt_ms = (time.perf_counter() - t0) * 1e3
        self.step += 1
        rec = {"step": self.step, "loss": loss, "dp": self.dp, "ms": dt_ms}
        self.metrics_log.append(rec)
        self._publish_runtime_hints(dt_ms)
        if self.step % self.ckpt_every == 0:
            self._checkpoint()
        return rec

    def run(self, n_steps: int, step_callback: Optional[Callable] = None):
        while self.step < n_steps:
            self.poll_events()
            self.step_once()
            if step_callback:
                step_callback(self)
        self.ckpt.wait()
        return self.metrics_log
