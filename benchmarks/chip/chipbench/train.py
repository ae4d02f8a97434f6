"""The training driver: the elastic trainer's public ``step_once`` and
``poll_events`` on a data x model mesh, through an eviction notice and a
harvest offer sent by the program's fault injector, as ``launch/train.py``
sends them.

Set-up builds the one trainer the window uses, gives it the benchmark's
weights and token batches (made from the seed), compiles the program the
eviction will need into the persistent cache, and drives the trainer
through its first three steps, whose results the reference follows.  The
window then steps on; the notices arrive at fixed shares of the window and
are applied at the next step boundary.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import tempfile
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import flops, weights

CHECKED_STEPS = 3


class Batches:
    """The token stream: ``batch_at(step)`` is a pure function of the seed
    and the step, uniform over the vocabulary; every row differs."""

    def __init__(self, vocab: int, batch: int, seq: int, seed: int):
        self.vocab, self.batch, self.seq, self.seed = vocab, batch, seq, seed

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        g = np.random.default_rng([self.seed, 7, int(step)])
        return {"tokens": g.integers(0, self.vocab, (self.batch, self.seq + 1),
                                     dtype=np.int32)}


@dataclasses.dataclass
class TrainRecord:
    window: tuple
    steps: List[tuple]            # (t_start, t_end, dp, tokens, loss)
    resizes: List[tuple]          # (t_event, t_done, dp_after, n_devices)
    state_changed: int            # resizes after which the state differed
    trace_window: Optional[tuple] = None
    window_compiles: Dict[str, float] = dataclasses.field(
        default_factory=dict)

    def window_steps(self):
        ws, we = self.window
        return [s for s in self.steps if s[0] >= ws and s[1] <= we]


@jax.jit
def _digest(leaves):
    def one(a):
        bits = jax.lax.bitcast_convert_type(
            a, {2: jnp.uint16, 4: jnp.uint32}[a.dtype.itemsize])
        return jnp.sum(bits.astype(jnp.uint32), dtype=jnp.uint32)
    return jnp.stack([one(a) for a in leaves])


def fingerprint(tree) -> np.ndarray:
    """Order-free exact digest of a state: per leaf, the wrapping sum of
    its bits as unsigned integers (the same for any sharding)."""
    return np.asarray(_digest(jax.tree.leaves(tree)))


class Driver:
    """Runs one elastic-training cell and checks its first steps."""

    def __init__(self, cell, seconds: float, seed: int, trace_dir=None,
                 devices=None, counter=None):
        self.cell, self.seconds, self.seed = cell, float(seconds), int(seed)
        self.devices, self.counter = list(devices), counter
        self.cfg = cell.config.model
        self.tc = cell.config.meta["train"]
        self.mix = cell.traffic
        self.trace_dir = trace_dir

    def setup(self):
        from repro.configs.base import RunConfig
        from repro.core.global_manager import GlobalManager
        from repro.runtime.faults import FaultInjector
        from repro.runtime.trainer import WITrainer
        tc, cfg = self.tc, self.cfg
        self.rcfg = RunConfig(model=cfg, seed=self.seed % 2 ** 31,
                              learning_rate=tc["learning_rate"],
                              warmup_steps=tc["warmup_steps"],
                              total_steps=tc["total_steps"])
        gm = GlobalManager(hint_rate_per_s=1e6, hint_burst=1e6)
        self.ckpt_dir = tempfile.mkdtemp(prefix="chipbench-ckpt-")
        model_axis = tc["mesh"]["model"]
        self.tr = WITrainer(self.rcfg, gm, ckpt_dir=self.ckpt_dir,
                            devices=self.devices, model_axis=model_axis,
                            ckpt_every=10 ** 9, batch_override=tc["global_batch"],
                            seq_override=tc["seq_len"])
        self.inj = FaultInjector(gm, self.tr.workload)
        self.data = Batches(cfg.vocab_size, tc["global_batch"], tc["seq_len"],
                            self.seed)
        self.tr.data = self.data
        self.tr.params = weights.make(cfg, self.seed, self.tr.pshard)
        self.p0 = jax.device_get(self.tr.params)
        self._compile_shrunk(model_axis)
        # the first steps, through the window's own call and feed
        self.first = []
        for i in range(CHECKED_STEPS):
            self.first.append(self.tr.step_once()["loss"])
            if i == 0:
                self.m1 = jax.device_get(self.tr.opt_state.m)
        self.p3 = jax.device_get(self.tr.params)

    def _compile_shrunk(self, model_axis: int):
        """The train step on the mesh an eviction leaves (data 1 over the
        first ``model_axis`` chips), compiled into the persistent cache with
        the trainer's public ``build_step``, so that the window's re-jit
        loads it as a warm deployment would."""
        from jax.sharding import Mesh
        from repro.models import sharding as SH
        from repro.runtime.trainer import build_step, parallel_config
        from repro.launch import steps as ST
        tr = self.tr
        mesh = Mesh(np.asarray(self.devices[:model_axis]).reshape(1, -1),
                    ("data", "model"))
        pcfg = parallel_config(1, model_axis)
        like = self.data.batch_at(0)
        step, ps, os_, bs, rules = build_step(self.cfg, self.rcfg, pcfg, mesh,
                                              like)
        # the resize check's digest, for the state on either mesh
        fingerprint((tr.params, tr.opt_state))
        fingerprint(jax.tree.map(
            lambda a, s: jax.device_put(np.zeros((), a.dtype), s)
            if a.ndim == 0 else jax.jit(
                lambda: jnp.zeros(a.shape, a.dtype), out_shardings=s)(),
            (tr.params, tr.opt_state), (ps, os_)))
        SH.set_mesh(mesh, rules)
        try:
            def sds(tree, shard):
                return jax.tree.map(lambda a, s: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=s), tree, shard)
            step.lower(sds(tr.params, ps), sds(tr.opt_state, os_),
                       {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=bs[k])
                        for k, v in like.items()}).compile()
        finally:
            SH.set_mesh(tr.mesh, ST.train_shardings(self.cfg, tr.pcfg,
                                                    tr.mesh)[2])

    def run(self, trace: bool) -> TrainRecord:
        tr, mix, seconds = self.tr, self.mix, self.seconds
        clock = time.perf_counter
        model_axis = self.tc["mesh"]["model"]
        tokens = self.tc["global_batch"] * self.tc["seq_len"]
        ws = clock()
        we = ws + seconds
        snap = self.counter.snapshot()
        events = [(ws + seconds * mix["evict_at"], self.inj.evict),
                  (ws + seconds * mix["offer_at"], self.inj.offer_capacity)]
        tr_start = ws + seconds * mix["evict_at"] - mix["trace_lead_s"]
        steps, resizes = [], []
        changed, tracing, trace_window = 0, False, None
        steps_after = None
        while clock() < we:
            now = clock()
            if trace and not tracing and trace_window is None \
                    and now >= tr_start:
                jax.profiler.start_trace(str(self.trace_dir))
                tracing, t_tr = True, clock()
            if events and now >= events[0][0]:
                if tracing and steps_after is not None:
                    # the traced part holds one resize only
                    jax.profiler.stop_trace()
                    tracing, trace_window = False, (t_tr, clock())
                _, send = events.pop(0)
                before = fingerprint((tr.params, tr.opt_state))
                t_ev = clock()
                send(n_devices=model_axis)
                with jax.profiler.TraceAnnotation("bench.trainer_poll"):
                    tr.poll_events()
                t_done = clock()
                resizes.append((t_ev, t_done, tr.dp, len(tr.active_devices)))
                changed += int(not np.array_equal(
                    before, fingerprint((tr.params, tr.opt_state))))
                if tracing and steps_after is None:
                    steps_after = mix["trace_steps_after"]
                continue
            with jax.profiler.TraceAnnotation("bench.trainer_poll"):
                tr.poll_events()
            ts = clock()
            with jax.profiler.TraceAnnotation("bench.trainer_step"):
                rec = tr.step_once()
            te = clock()
            steps.append((ts, te, tr.dp, tokens, rec["loss"]))
            if tracing and steps_after is not None:
                steps_after -= 1
                if steps_after <= 0:
                    jax.profiler.stop_trace()
                    tracing, trace_window = False, (t_tr, clock())
        win = self.counter.since(snap)
        if tracing:
            jax.profiler.stop_trace()
            trace_window = (t_tr, clock())
        return TrainRecord((ws, we), steps, resizes, changed, trace_window,
                           win)

    def check(self, rec: TrainRecord, control: Optional[str] = None) -> Dict:
        """Free the trainer, then follow its first three steps with the
        reference and compare (``check`` below)."""
        self.tr.ckpt.wait()
        self.tr = self.inj = None
        gc.collect()
        shutil.rmtree(self.ckpt_dir, ignore_errors=True)
        return check(self, rec, control)


def _norms(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {weights.leaf_name(p): float(np.linalg.norm(
        np.asarray(a, np.float32).ravel())) for p, a in flat}


def _delta_norms(a, b) -> Dict[str, float]:
    fa = jax.tree_util.tree_flatten_with_path(a)[0]
    fb = jax.tree.leaves(b)
    return {weights.leaf_name(p): float(np.linalg.norm(
        (np.asarray(y, np.float32) - np.asarray(x, np.float32)).ravel()))
        for (p, x), y in zip(fa, fb)}


def worst_gap(prog: Dict[str, float], ref: Dict[str, float],
              keep=None) -> float:
    """Largest |prog - ref| over leaves, each against the larger of the
    reference's norm of that leaf and of the median leaf."""
    names = [k for k in ref if keep is None or k in keep]
    med = float(np.median([ref[k] for k in names]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in names)


def opt_settings(driver: Driver) -> Dict:
    tc, r = driver.tc, driver.rcfg
    return {"learning_rate": tc["learning_rate"],
            "warmup_steps": tc["warmup_steps"],
            "total_steps": tc["total_steps"], "beta1": r.beta1,
            "beta2": r.beta2, "weight_decay": r.weight_decay,
            "grad_clip": r.grad_clip}


def compare(p0, got, ref) -> Dict:
    """Three steps ``got`` against the reference's ``ref``, each a tuple
    (losses, first clipped gradient, parameters after the last step).
    Leaves whose reference gradient is under a thousandth of the median
    leaf's move by round-off alone and are left out of the change."""
    g_ref = _norms(ref[1])
    med = float(np.median(list(g_ref.values())))
    moving = {k for k, v in g_ref.items() if v >= 1e-3 * med}
    return {"loss_gap": max(abs(a - b) / abs(b)
                            for a, b in zip(got[0], ref[0])),
            "grad_norm_gap": worst_gap(_norms(got[1]), g_ref),
            "update_norm_gap": worst_gap(_delta_norms(p0, got[2]),
                                         _delta_norms(p0, ref[2]), moving),
            "leaves_left_out": sorted(set(g_ref) - moving)}


def reference_steps(driver: Driver, weights_mode: str = "float32",
                    rows=None, grad_rows=None):
    """The reference's three steps from the benchmark's weights on the same
    batches (``rows`` keeps only those rows of each batch; ``grad_rows``
    takes the gradient from those rows alone)."""
    from reference import mamba2
    batches = [driver.data.batch_at(i)["tokens"] for i in range(CHECKED_STEPS)]
    if rows is not None:
        batches = [b[rows] for b in batches]
    p0 = jax.device_put(driver.p0, driver.devices[0])
    return mamba2.follow(driver.cfg, opt_settings(driver), p0, batches,
                         weights=weights_mode, grad_rows=grad_rows)


def program_steps(driver: Driver):
    """The trainer's three steps: its losses, its first gradient as the
    optimizer got it (Adam's first moment after one step over 1 - beta1),
    its parameters after the third step."""
    g1 = jax.tree.map(lambda m: np.asarray(m, np.float32)
                      / (1.0 - driver.rcfg.beta1), driver.m1)
    return driver.first, g1, driver.p3


def check(driver: Driver, rec: TrainRecord, control: Optional[str]) -> Dict:
    ref = reference_steps(driver)
    got = program_steps(driver) if control is None \
        else reference_steps(driver, control)
    out = compare(driver.p0, got, ref)
    out.update(resize_state_changed=rec.state_changed,
               nonfinite_losses=int(sum(not np.isfinite(s[4])
                                        for s in rec.steps)),
               program_losses=list(got[0]), reference_losses=list(ref[0]))
    return out


def control_readings(driver: Driver) -> Dict[str, Dict]:
    """For setting limits: the program's readings, the control's (the
    reference in float8) and those of planted faults, each against the
    float32 reference.  A step that returns its state unchanged reads 1 on
    ``update_norm_gap`` by construction and needs no run."""
    driver.tr.ckpt.wait()
    driver.tr = driver.inj = None
    gc.collect()
    shutil.rmtree(driver.ckpt_dir, ignore_errors=True)
    ref = reference_steps(driver)
    half = slice(0, driver.tc["global_batch"] // 2)
    ranks = driver.tc["global_batch"] // driver.tc["mesh"]["data"]
    out = {"program": compare(driver.p0, program_steps(driver), ref),
           "control_fp8": compare(driver.p0,
                                  reference_steps(driver, "fp8"), ref),
           "half_batch": compare(driver.p0,
                                 reference_steps(driver, rows=half), ref),
           "no_gradient_exchange": compare(
               driver.p0, reference_steps(driver,
                                          grad_rows=slice(0, ranks)), ref)}
    for v in out.values():
        v.pop("leaves_left_out")
    return out


def end_to_end(rec: TrainRecord, seconds: float) -> Dict[str, float]:
    done = rec.window_steps()
    return {"train_tokens_per_s": sum(s[3] for s in done) / seconds}


def counts(rec: TrainRecord) -> Dict[str, int]:
    """Steps attempted in the window and those whose loss was not finite."""
    return {"attempted": len(rec.steps),
            "failed": sum(1 for s in rec.steps if not np.isfinite(s[4]))}


def summary(rec: TrainRecord) -> Dict:
    by_dp: Dict[int, List[float]] = {}
    for s in rec.window_steps():
        by_dp.setdefault(s[2], []).append((s[1] - s[0]) * 1e3)
    return {"steps": len(rec.steps),
            "median_step_ms_by_dp": {str(k): float(np.median(v))
                                     for k, v in by_dp.items()},
            "resize_s": [r[1] - r[0] for r in rec.resizes],
            "dp_after_resizes": [[r[2], r[3]] for r in rec.resizes]}


def flops_per_step(cfg, tc) -> float:
    return flops.ssd_train_flops_per_token(cfg, tc["seq_len"]) \
        * tc["global_batch"] * tc["seq_len"]
