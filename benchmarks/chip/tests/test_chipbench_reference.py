"""Each plain reference against the program's own model code
(``src/repro/models/model.py``) at toy widths, in float32."""
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parents[1] / "src")]

from chipbench import spec, train, weights  # noqa: E402
from reference import dense, mamba2  # noqa: E402

DATA = BENCH / "tests" / "data"


def _f32(name):
    cell = spec.find_cell(name, root=DATA, bench_dir=DATA)
    return dataclasses.replace(cell.config.model, act_dtype="float32"), cell


def test_dense_reference_matches_the_program_prefill():
    from repro.launch.serve import SERVE_PCFG
    from repro.models import model as M
    cfg, _ = _f32("tiny-dense.chat")
    params = weights.make(cfg, 2 ** 33 + 7)
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 16),
                                            dtype=np.int32)
    best, tl, arg = dense.logits_at(cfg, params, toks,
                                    np.stack([toks, toks], -1))
    with jax.default_matmul_precision("highest"):
        for n in (5, 11, 16):
            logits, _ = M.prefill(cfg, SERVE_PCFG, params,
                                  {"tokens": jnp.asarray(toks[:, :n])},
                                  M.init_cache(cfg, 1, n,
                                               cache_dtype=jnp.float32))
            lg = np.asarray(logits[0, -1, :cfg.vocab_size])
            assert best[0, n - 1] == pytest.approx(lg.max(), abs=1e-4)
            assert arg[0, n - 1] == lg.argmax()
            assert tl[0, n - 1, 0] == pytest.approx(lg[toks[0, n - 1]],
                                                    abs=1e-4)


def test_fp8_control_departs_from_the_reference():
    cfg, _ = _f32("tiny-dense.chat")
    params = weights.make(cfg, 3)
    toks = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 32),
                                            dtype=np.int32)
    b32, _, _ = dense.logits_at(cfg, params, toks, toks[..., None])
    b8, _, _ = dense.logits_at(cfg, params, toks, toks[..., None], "fp8")
    assert np.abs(b8 - b32).max() > 1e-3


def test_mamba2_reference_matches_the_program_loss_and_gradients():
    from repro.models import model as M
    from repro.models import sharding as SH
    from repro.runtime.trainer import parallel_config
    cfg, cell = _f32("tiny-ssm.elastic")
    params = weights.make(cfg, 11)
    toks = train.Batches(cfg.vocab_size, 2, 32, 5).batch_at(0)["tokens"]
    SH.set_mesh(None)
    pcfg = parallel_config(1, 1)
    with jax.default_matmul_precision("highest"):
        (loss, _), g = jax.value_and_grad(
            lambda p: M.loss_and_aux(cfg, pcfg, p, {"tokens": toks}),
            has_aux=True)(params)
        rloss, rg = mamba2.loss_and_grads(cfg, params, jnp.asarray(toks))
    assert float(rloss) == pytest.approx(float(loss), rel=1e-5)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(g)[0],
                            jax.tree.leaves(rg)):
        a, b = np.asarray(a), np.asarray(b)
        scale = max(np.abs(a).max(), 1e-6)
        assert np.abs(a - b).max() <= 1e-3 * scale, weights.leaf_name(path)


def test_mamba2_follow_matches_the_program_optimizer():
    from repro.configs.base import RunConfig
    from repro.models import sharding as SH
    from repro.runtime.trainer import parallel_config
    from repro.train.train_step import make_train_step
    from repro.train import optimizer as opt
    cfg, cell = _f32("tiny-ssm.elastic")
    tc = cell.config.meta["train"]
    rcfg = RunConfig(model=cfg, learning_rate=tc["learning_rate"],
                     warmup_steps=tc["warmup_steps"],
                     total_steps=tc["total_steps"])
    params = weights.make(cfg, 12)
    data = train.Batches(cfg.vocab_size, 2, 32, 6)
    SH.set_mesh(None)
    pcfg = parallel_config(1, 1)
    step = jax.jit(make_train_step(cfg, pcfg, rcfg))
    p, o = params, opt.init_opt_state(rcfg, params, pcfg)
    losses = []
    with jax.default_matmul_precision("highest"):
        for i in range(2):
            p, o, m = step(p, o, {"tokens": jnp.asarray(
                data.batch_at(i)["tokens"])})
            losses.append(float(m["loss"]))
        settings = {"learning_rate": rcfg.learning_rate,
                    "warmup_steps": rcfg.warmup_steps,
                    "total_steps": rcfg.total_steps, "beta1": rcfg.beta1,
                    "beta2": rcfg.beta2, "weight_decay": rcfg.weight_decay,
                    "grad_clip": rcfg.grad_clip}
        rl, _, rp = mamba2.follow(cfg, settings, params,
                                  [data.batch_at(i)["tokens"]
                                   for i in range(2)])
    assert rl == pytest.approx(losses, rel=1e-5)
    for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(rp)):
        assert np.abs(np.asarray(a) - b).max() <= 1e-5
