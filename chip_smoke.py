#!/usr/bin/env python3
"""Bring-up check on a TPU host: the system's JAX paths at published widths,
driven through their normal entry points, in this one process.

    python chip_smoke.py              # one chip: Pallas kernels + serving
    python chip_smoke.py --chips 4    # four chips: the elastic trainer

One chip: the flash-attention, SSD and RG-LRU kernels at real widths against
their references, then minitron-8b (every width as published, 8 of its 32
layers) serving 16 requests through ``launch/serve.serve``, and its
token-by-token decode checked against a prefill of the same prompt.
Four chips: mamba2-370m trained by the standalone elastic trainer on a 2x2
data x model mesh, shrunk to dp 1 by an injected eviction and regrown to
dp 2 by a harvest offer; its first loss is checked against one chip, and
each resize must copy the training state exactly.

Weights and tokens are random, drawn from ``--seed``.  Each phase prints
``<phase>: {json}`` lines; these are bring-up readings, not benchmark
numbers.  The last line, ``{"ok": true, "device": {...}}``, is printed only
when every phase passed.  Without a TPU, or with fewer chips than asked
for, the script exits 2 before any work and prints no such line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import statistics
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.archs import ARCHS  # noqa: E402
from repro.configs.base import AttnConfig, mconfig_replace  # noqa: E402
from repro.data.pipeline import DataConfig, make_dataset  # noqa: E402
from repro.kernels.flash_attention import ops as fa_ops  # noqa: E402
from repro.kernels.flash_attention import ref as fa_ref  # noqa: E402
from repro.kernels.rglru import ops as lru_ops  # noqa: E402
from repro.kernels.rglru import ref as lru_ref  # noqa: E402
from repro.kernels.ssd import ops as ssd_ops  # noqa: E402
from repro.kernels.ssd import ref as ssd_ref  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch import serve as S  # noqa: E402
from repro.launch.train import elastic_trainer  # noqa: E402
from repro.models import model as M  # noqa: E402
from repro.models import sharding as SH  # noqa: E402
from repro.runtime.trainer import parallel_config  # noqa: E402
from repro.serve.engine import decode_fn  # noqa: E402

# Serving: minitron-8b at every published width.  reduced: depth 32 -> 8
# layers (all 32 are 19.8 GB of bf16 weights, over one v5e chip's 16 GB);
# the uniform ("attn", "mlp") pattern keeps whole periods at any depth.
SERVE_LAYERS = 8

# engine steps left out of the median decode-step time (the first one
# includes compiling the decode step)
WARMUP_STEPS = 2

# trainer steps taken at each width (dp 2, dp 1, dp 2 again)
STEPS_PER_WIDTH = 2

# Decode-vs-prefill: max |logit difference| at the prompt's last position,
# relative to the largest |logit|.  bf16 keeps 8 significant bits (2^-9
# relative rounding per op) and the two paths round at different points:
# 8 bf16 layers (d_model 256 and 512, 160-token prompts, CPU) measured
# 1.2e-2 to 1.5e-2.  A KV cache written one slot late, or attended one slot
# short, measured 0.24 to 0.43 on the same models.
LOGIT_RTOL = 4e-2

# Trainer: first-step loss on the mesh vs one chip, relative.  Sharding over
# the model axis reorders and re-rounds the bf16 reductions of 48 layers;
# 1e-2 leaves headroom over that while a mis-sharded weight or batch moves
# the loss far more.
LOSS_RTOL = 1e-2

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def report(phase: str, **fields):
    print(f"{phase}: {json.dumps(fields)}", flush=True)


class CompileClock:
    """Seconds JAX spends compiling inside the ``with`` block (a persistent
    cache hit counts its retrieval time)."""

    def __enter__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def _on(self, event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)


def peak_bytes():
    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelShapes:
    flash: tuple    # B, S, H, K, hd, q_chunk, kv_chunk
    ssd: tuple      # B, S, H, P, N, chunk
    rglru: tuple    # B, S, W, chunk


# minitron-8b attention, mamba2-370m SSD, recurrentgemma-9b RG-LRU
REAL_KERNELS = KernelShapes(flash=(1, 4096, 32, 8, 128, 512, 512),
                            ssd=(1, 4096, 32, 64, 128, 256),
                            rglru=(1, 4096, 4096, 256))

# (atol, rtol) per kernel: the bounds tests/test_kernels.py holds the
# interpret-mode kernels to (bf16 inputs for flash and SSD, f32 for RG-LRU)
KERNEL_TOL = {"flash": (2e-2, 2e-1), "ssd": (2e-2, 2e-1),
              "rglru": (1e-4, 1e-3)}


def _check(name, got, want):
    atol, rtol = KERNEL_TOL[name]
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = np.abs(got - want)
    worst = float((err / (atol + rtol * np.abs(want))).max())
    report(f"kernel.{name}", shape=list(got.shape),
           max_abs_err=float(err.max()), atol=atol, rtol=rtol,
           err_over_tol=worst)
    if not (np.isfinite(got).all() and worst <= 1.0):
        raise AssertionError(f"{name} kernel off its reference: "
                             f"{worst:.3g} x tolerance")


def kernel_phase(shapes: KernelShapes, *, interpret: bool = False,
                 seed: int = 0):
    """Run each Pallas kernel once and compare with its ``ref.py`` (float32,
    full-precision matmuls)."""
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def normal(shape, dtype=jnp.float32, scale=1.0):
        return (jax.random.normal(next(ks), shape) * scale).astype(dtype)

    def f32(*xs):
        return [x.astype(jnp.float32) for x in xs]

    exact = jax.default_matmul_precision("highest")

    B, T, H, K, hd, cq, ck = shapes.flash
    acfg = AttnConfig(causal=True)
    q = normal((B, T, H, hd), jnp.bfloat16)
    k = normal((B, T, K, hd), jnp.bfloat16)
    v = normal((B, T, K, hd), jnp.bfloat16)
    got = jax.jit(lambda *a: fa_ops.attention(*a, acfg, cq, ck,
                                              interpret))(q, k, v)
    with exact:
        want = jax.jit(lambda *a: fa_ref.reference(*a, acfg))(*f32(q, k, v))
    _check("flash", got, want)
    del q, k, v, got, want

    B, T, H, P, N, chunk = shapes.ssd
    x = normal((B, T, H, P), jnp.bfloat16)
    dt = jax.nn.softplus(normal((B, T, H)))
    a_log = normal((H,), scale=0.5)
    Bm = normal((B, T, 1, N), jnp.bfloat16, 0.3)
    Cm = normal((B, T, 1, N), jnp.bfloat16, 0.3)
    got = jax.jit(lambda *a: ssd_ops.ssd_mixer(
        *a, chunk=chunk, interpret=interpret))(x, dt, a_log, Bm, Cm)
    with exact:
        want = jax.jit(lambda x, dt, a, b, c: ssd_ref.reference(
            x, dt, a, b, c, chunk=chunk))(*f32(x, dt, a_log, Bm, Cm))
    _check("ssd", got, want)
    del x, dt, Bm, Cm, got, want

    B, T, W, chunk = shapes.rglru
    x = normal((B, T, W))
    log_a = -jax.nn.softplus(normal((B, T, W)))
    got = jax.jit(lambda *a: lru_ops.rglru_mixer(
        *a, chunk=chunk, interpret=interpret))(x, log_a)
    with exact:
        want = jax.jit(lru_ref.reference)(x, log_a)
    _check("rglru", got, want)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def decode_vs_prefill(cfg, params, prompt):
    """Feed ``prompt`` token by token through the engine's jitted decode
    step and compare its last logits with one prefill over the prompt."""
    n = len(prompt)
    step = decode_fn(cfg, S.SERVE_PCFG)
    cache = M.init_cache(cfg, 1, n)
    for tok in prompt:
        logits, cache = step(params, cache, jnp.asarray([[tok]], jnp.int32))
    prefill = jax.jit(lambda p, b, c: M.prefill(cfg, S.SERVE_PCFG, p, b, c))
    want, _ = prefill(params, {"tokens": jnp.asarray(prompt[None])},
                      M.init_cache(cfg, 1, n))
    got = np.asarray(logits[0, -1, :cfg.vocab_size], np.float32)
    want = np.asarray(want[0, -1, :cfg.vocab_size], np.float32)
    return (float(np.abs(got - want).max()), float(np.abs(want).max()),
            bool(got.argmax() == want.argmax()))


def serving_phase(cfg, *, n_requests=16, prompt_len=(128, 512),
                  max_new=(32, 64), slots=16, max_len=2048, seed=0):
    """Serve seeded requests through ``launch/serve.serve`` and check the
    decode path against prefill."""
    with CompileClock() as cc:
        t0 = time.perf_counter()
        params = S.random_params(cfg, seed)
        reqs = S.make_requests(cfg, n_requests, prompt_len, max_new, seed)
        run = S.serve(cfg, params, reqs, slots=slots, max_len=max_len,
                      seed=seed)
        wall = time.perf_counter() - t0
    if run.served != n_requests or any(
            len(r.out_tokens) != r.max_new for r in reqs):
        raise AssertionError(f"served {run.served}/{n_requests} requests")
    if not all(0 <= t < cfg.vocab_size for r in reqs for t in r.out_tokens):
        raise AssertionError("token id outside the vocabulary")
    report("serve", requests=n_requests, served=run.served,
           tokens_out=run.tokens_out,
           prompt_tokens=int(sum(len(r.prompt) for r in reqs)),
           engine_steps=len(run.step_s),
           median_step_s=statistics.median(run.step_s[WARMUP_STEPS:]),
           first_step_s=run.step_s[0], compile_s=cc.seconds, wall_s=wall,
           peak_bytes_in_use=peak_bytes())

    with CompileClock() as cc:
        diff, scale, top1 = decode_vs_prefill(cfg, params, reqs[0].prompt)
    rel = diff / scale
    report("serve.decode_vs_prefill", prompt_len=len(reqs[0].prompt),
           max_abs_logit_diff=diff, max_abs_logit=scale, rel_diff=rel,
           rel_tol=LOGIT_RTOL, argmax_match=top1, compile_s=cc.seconds)
    if not rel <= LOGIT_RTOL:
        raise AssertionError(f"decode disagrees with prefill: {rel:.3g} > "
                             f"{LOGIT_RTOL}")


# ---------------------------------------------------------------------------
# elastic trainer (four chips)
# ---------------------------------------------------------------------------

def one_chip_loss(cfg, batch, seed):
    """The first step's loss computed on one device without a mesh: the
    trainer's initial weights (same seed) on the same batch."""
    SH.set_mesh(None)
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    pcfg = parallel_config(1, 1)
    fn = jax.jit(lambda p, b: M.loss_and_aux(cfg, pcfg, p, b)[0])
    return float(fn(params, {k: jnp.asarray(v) for k, v in batch.items()}))


def _state(tr):
    return jax.tree.map(np.asarray, {"params": tr.params,
                                     "opt": tr.opt_state})


def _identical(a, b):
    # bit for bit: arrays read back from the device need not be contiguous
    return all(x.dtype == y.dtype and x.shape == y.shape
               and x.tobytes() == y.tobytes()
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _check_placement(tr, devices):
    """Mesh, training state and batch all span exactly ``devices``."""
    want = set(devices)
    if set(tr.mesh.devices.flat) != want:
        raise AssertionError(f"mesh holds {tr.mesh.devices.size} devices")
    for x in jax.tree.leaves((tr.params, tr.opt_state)):
        if x.sharding.device_set != want:
            raise AssertionError(f"a state leaf sits on "
                                 f"{len(x.sharding.device_set)} devices")
    if any(s.device_set != want for s in tr.bshard.values()):
        raise AssertionError("the batch does not span the mesh")


def train_phase(cfg, *, batch, seq, model_axis=2, seed=0):
    """dp 2 -> eviction -> dp 1 -> harvest offer -> dp 2, ``STEPS_PER_WIDTH``
    steps at each width, on every visible device."""
    steps = STEPS_PER_WIDTH
    devices = jax.devices()
    tokens = make_dataset(cfg, batch, seq, DataConfig(seed=seed)).batch_at(0)
    with CompileClock() as cc:
        ref_loss = one_chip_loss(cfg, tokens, seed)
    report("train.one_chip_reference", loss=ref_loss, compile_s=cc.seconds)

    with tempfile.TemporaryDirectory() as ckpt, CompileClock() as cc:
        t0 = time.perf_counter()
        tr, inj = elastic_trainer(cfg, ckpt_dir=ckpt, steps=3 * steps,
                                  model_axis=model_axis, batch=batch,
                                  seq=seq, ckpt_every=10 ** 9, seed=seed)
        _check_placement(tr, devices)
        widths = [(tr.dp, len(tr.active_devices))]
        tr.run(steps)
        rel = abs(tr.metrics_log[0]["loss"] - ref_loss) / abs(ref_loss)
        report("train.first_loss", mesh=list(tr.mesh.devices.shape),
               loss=tr.metrics_log[0]["loss"], one_chip=ref_loss,
               rel_diff=rel, rel_tol=LOSS_RTOL)
        if not rel <= LOSS_RTOL:
            raise AssertionError(f"mesh loss off one chip by {rel:.3g}")

        resizes = []
        for event, n_steps in ((inj.evict, 2 * steps),
                               (inj.offer_capacity, 3 * steps)):
            before = _state(tr)
            t1 = time.perf_counter()
            event(n_devices=model_axis)
            tr.poll_events()
            resizes.append(time.perf_counter() - t1)
            if not _identical(before, _state(tr)):
                raise AssertionError("a resize changed the training state")
            del before
            _check_placement(tr, devices[:len(tr.active_devices)])
            widths.append((tr.dp, len(tr.active_devices)))
            tr.run(n_steps)
        tr.ckpt.wait()
        wall = time.perf_counter() - t0

    losses = [m["loss"] for m in tr.metrics_log]
    want = [(len(devices) // model_axis, len(devices)),
            (len(devices) // model_axis - 1, len(devices) - model_axis),
            (len(devices) // model_axis, len(devices))]
    report("train.elastic", dp_devices=widths, losses=losses,
           dp_per_step=[m["dp"] for m in tr.metrics_log],
           step_ms=[m["ms"] for m in tr.metrics_log], resize_s=resizes,
           compile_s=cc.seconds, wall_s=wall, peak_bytes_in_use=peak_bytes())
    if widths != want:
        raise AssertionError(f"(dp, devices) went {widths}, not {want}")
    if not np.isfinite(losses).all():
        raise AssertionError("non-finite loss")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the elastic-trainer phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s); JAX reports "
              f"{len(devices)} {dev.platform} device(s)", file=sys.stderr)
        return 2
    report("device", platform=dev.platform, kind=dev.device_kind,
           count=len(devices), compile_cache=compile_cache.enable())

    t0 = time.perf_counter()
    with CompileClock() as cc:
        if args.chips == 4:
            train_phase(ARCHS["mamba2-370m"], batch=8, seq=1024,
                        model_axis=2, seed=args.seed)
        else:
            kernel_phase(REAL_KERNELS, seed=args.seed)
            cfg = mconfig_replace(ARCHS["minitron-8b"], n_layers=SERVE_LAYERS)
            report("serve.config", arch=cfg.name, d_model=cfg.d_model,
                   heads=[cfg.n_heads, cfg.n_kv_heads, cfg.head_dim],
                   d_ff=cfg.d_ff, vocab=cfg.vocab_size,
                   layers=f"{cfg.n_layers} of "
                          f"{ARCHS['minitron-8b'].n_layers}",
                   params=cfg.n_params)
            serving_phase(cfg, seed=args.seed)
    report("total", compile_s=cc.seconds, wall_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
