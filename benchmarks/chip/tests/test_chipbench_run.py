"""The harness as a whole on the CPU at toy sizes: no result without a
chip, cells found by name in files of their own, and ``correct`` false when
the timed path is broken underneath (the control, and each fault a cell
can have), true when it is sound."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import run  # noqa: E402
from chipbench import device, serve, spec  # noqa: E402

DATA = BENCH / "tests" / "data"
SEED = 2 ** 40 + 11


@pytest.fixture(autouse=True)
def _no_cache_in_the_checkout(monkeypatch, tmp_path):
    # the program's compile_cache.enable() then leaves JAX's persistent
    # cache off in this process instead of writing into the checkout
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(ROOT / "src"))
    env.update(extra)
    return env


def _cell(name):
    cell = spec.find_cell(name, root=DATA, bench_dir=DATA)
    cell.bench_dir = BENCH
    return cell


def _run(cell, seconds=1.5):
    return run.run_cell(cell, jax.devices()[:cell.chips], SEED, seconds,
                        False, t_start=time.perf_counter())


def test_off_a_tpu_the_run_prints_no_result(tmp_path):
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "minitron-8b.chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_env(), capture_output=True, text=True, timeout=300)
    assert out.returncode == 2
    assert "TPU" in out.stderr
    for line in out.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_fewer_chips_than_the_cell_asks_for(monkeypatch):
    one = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(jax, "devices", lambda: [one])
    with pytest.raises(device.NoChip):
        device.require_chips(4)
    assert device.require_chips(1) == [one]


def test_no_peak_for_an_unknown_device_kind():
    with pytest.raises(KeyError):
        device.peaks("cpu")
    assert device.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def _digest(root):
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes())
            .hexdigest() for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    bench = root / "benchmarks" / "chip"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "tests", "__pycache__", ".out"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = _digest(bench)
    # a later change adds three files and the entries that name them
    cfg = json.loads((DATA / "configs" / "tiny-dense.json").read_text())
    cfg["name"] = "other-model"
    (bench / "configs" / "other-model.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "chat.json").read_text())
    mix["arrivals"]["rate_per_s"] = 9.0
    (bench / "traffic" / "bursty.json").write_text(json.dumps(mix))
    (bench / "metrics" / "engine.queue.bursty.py").write_text(
        "def read(run):\n    return float(len(run.record))\n")
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "other-model", "source": "test",
                         "file": "benchmarks/chip/configs/other-model.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "other-model.bursty",
                           "config": "other-model", "traffic": "bursty",
                           "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "engine.queue.bursty", "unit": "1",
                           "better": "lower", "source": "program_counter",
                           "layer": "engine", "moves": "ttft_p90_s",
                           "workloads": ["other-model.bursty"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    after = _digest(bench)
    assert all(after[k] == v for k, v in before.items())
    cell = spec.find_cell("other-model.bursty", root=root, bench_dir=bench)
    assert cell.config.name == "other-model"
    assert cell.traffic["arrivals"]["rate_per_s"] == 9.0
    assert [m["name"] for m in cell.per_layer] == ["engine.queue.bursty"]
    reader = spec.metric_reader("engine.queue.bursty", bench)
    assert reader(SimpleNamespace(record=[1, 2])) == 2.0
    # the cells that were there are found as before
    old = spec.find_cell("minitron-8b.chat", root=root, bench_dir=bench)
    assert old.traffic == spec.load_traffic("chat")


@pytest.mark.parametrize("name", ["tiny-dense.chat", "tiny-dense.batch"])
def test_sound_serving_run_is_correct(name):
    out = _run(_cell(name))
    assert out["correct"], out["check"]
    assert list(out)[-1] == "check"
    assert set(out["metrics"]) == {m["name"] for m in _cell(name).end_to_end}
    assert out["device"]["count"] == 1


def test_an_altered_token_is_not_correct(monkeypatch):
    from repro.serve.engine import ServingEngine
    real = ServingEngine.step_once

    def altered(self):
        n = real(self)
        for r in self._active:
            if r is not None and r.out_tokens:
                r.out_tokens[-1] = (r.out_tokens[-1] + 1) % self.cfg.vocab_size
        return n
    monkeypatch.setattr(ServingEngine, "step_once", altered)
    out = _run(_cell("tiny-dense.chat"))
    assert not out["correct"]
    assert out["check"]["served_gap_max"]["value"] > \
        out["check"]["served_gap_max"]["limit"]


def test_the_control_fails_the_serving_limit():
    cell = _cell("tiny-dense.chat")
    drv = serve.Driver(cell, 1.5, SEED, devices=jax.devices()[:1],
                       counter=device.CompileCounter())
    drv.setup()
    r = drv.check(drv.run(trace=False), control="fp8")
    limit = cell.config.meta["check"]["served_gap_max"]
    assert r["served_gap_max"] <= limit < r["control_gap_max"]


TRAIN_FAULTS = r"""
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[2]]
from pathlib import Path
import jax, jax.numpy as jnp
import run
from chipbench import device, spec, train
from repro.launch import steps as ST
from repro.train import train_step as TS
data = Path(sys.argv[1]) / "tests" / "data"
cell = spec.find_cell("tiny-ssm.elastic", root=data, bench_dir=data)
cell.bench_dir = Path(sys.argv[1])
real_fn, real_grads = ST.build_train_fn, TS.grads_fn

def unchanged(*a):
    f = real_fn(*a)
    def step(p, o, b):
        _, _, m = f(p, o, b)
        return p, o, m
    return step

def half_batch(*a):
    f = real_fn(*a)
    def step(p, o, b):
        t = b["tokens"]
        return f(p, o, {"tokens": jnp.concatenate([t[:t.shape[0] // 2]] * 2)})
    return step

def no_exchange(cfg, pcfg, params, batch):
    l, parts, _ = real_grads(cfg, pcfg, params, batch)
    t = batch["tokens"]
    _, _, g = real_grads(cfg, pcfg, params,
                         {"tokens": t[:t.shape[0] // pcfg.data]})
    return l, parts, g

out = {}
for name in ("sound", "unchanged", "half_batch", "no_exchange"):
    ST.build_train_fn = {"unchanged": unchanged,
                         "half_batch": half_batch}.get(name, real_fn)
    TS.grads_fn = no_exchange if name == "no_exchange" else real_grads
    r = run.run_cell(cell, jax.devices()[:4], 7, 2.0, False,
                     t_start=time.perf_counter())
    out[name] = {"correct": r["correct"], "check": r["check"],
                 "train_tokens_per_s": r["metrics"]["train_tokens_per_s"]}
ST.build_train_fn, TS.grads_fn = real_fn, real_grads
drv = train.Driver(cell, 1.0, 8, devices=jax.devices()[:4],
                   counter=device.CompileCounter())
drv.setup()
out["control"] = train.control_readings(drv)["control_fp8"]
print(json.dumps(out))
"""


def test_training_faults_and_control_are_not_correct(tmp_path):
    out = subprocess.run(
        [sys.executable, "-c", TRAIN_FAULTS, str(BENCH), str(ROOT / "src")],
        cwd=tmp_path, capture_output=True, text=True, timeout=900,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4",
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")))
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["sound"]["correct"], res["sound"]["check"]
    assert res["sound"]["train_tokens_per_s"]["value"] > 0
    for fault in ("unchanged", "half_batch", "no_exchange"):
        assert not res[fault]["correct"], (fault, res[fault]["check"])
    assert res["unchanged"]["check"]["update_norm_gap"]["value"] == \
        pytest.approx(1.0)
    limits = _cell("tiny-ssm.elastic").config.meta["check"]
    assert any(res["control"][k] > limits[k] for k in
               ("loss_gap", "grad_norm_gap", "update_norm_gap"))
