"""CPU rehearsal of chip_smoke.py: its phases at smoke size (kernels in
interpret mode, the trainer phase on 4 virtual devices), and its refusal to
run, or to print an ok line, without a TPU."""
import importlib.util
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.configs.archs import smoke_config

ROOT = Path(__file__).resolve().parents[1]


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cs():
    return _load()


def _lines(out, prefix):
    return [json.loads(l.split(": ", 1)[1]) for l in out.splitlines()
            if l.startswith(prefix + ": ")]


def test_kernel_phase_interpret(cs, capsys):
    cs.kernel_phase(cs.KernelShapes(flash=(1, 128, 4, 2, 32, 64, 64),
                                    ssd=(1, 64, 2, 8, 16, 32),
                                    rglru=(1, 64, 256, 32)),
                    interpret=True)
    out = capsys.readouterr().out
    for name in ("flash", "ssd", "rglru"):
        (r,) = _lines(out, f"kernel.{name}")
        assert r["err_over_tol"] <= 1.0


def test_kernel_phase_fails_loudly(cs, monkeypatch):
    monkeypatch.setitem(cs.KERNEL_TOL, "rglru", (1e-12, 0.0))
    with pytest.raises(AssertionError, match="rglru"):
        cs.kernel_phase(cs.KernelShapes(flash=(1, 64, 2, 1, 16, 32, 32),
                                        ssd=(1, 32, 2, 8, 16, 16),
                                        rglru=(1, 32, 128, 16)),
                        interpret=True)


def test_serving_phase_smoke(cs, capsys):
    cfg = smoke_config("minitron-8b")
    cs.serving_phase(cfg, n_requests=3, prompt_len=(4, 9), max_new=(2, 5),
                     slots=2, max_len=32)
    out = capsys.readouterr().out
    (serve,) = _lines(out, "serve")
    assert serve["served"] == serve["requests"] == 3
    assert serve["tokens_out"] >= 6 and serve["median_step_s"] > 0
    (cmp,) = _lines(out, "serve.decode_vs_prefill")
    assert cmp["rel_diff"] <= cmp["rel_tol"]


def test_train_phase_on_four_virtual_devices():
    code = textwrap.dedent("""
        import importlib.util, sys
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", sys.argv[1])
        cs = sys.modules["chip_smoke"] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cs)
        from repro.configs.archs import smoke_config
        import jax
        assert len(jax.devices()) == 4
        cs.train_phase(smoke_config("mamba2-370m"), batch=4, seq=32,
                       model_axis=2)
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", code,
                          str(ROOT / "chip_smoke.py")], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    (el,) = _lines(out.stdout, "train.elastic")
    assert el["dp_devices"] == [[2, 4], [1, 2], [2, 4]]
    assert el["dp_per_step"] == [2, 2, 1, 1, 2, 2]
    (first,) = _lines(out.stdout, "train.first_loss")
    assert first["rel_diff"] <= first["rel_tol"]


def test_main_refuses_without_tpu(cs, capsys):
    assert cs.main([]) != 0
    assert cs.main(["--chips", "4"]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_compile_cache_placement(tmp_path):
    """Unset, the cache goes to <repo>/.jax_cache; set, JAX's own reading
    of JAX_COMPILATION_CACHE_DIR stands."""
    code = ("import jax; from repro.launch import compile_cache as c; "
            "print(c.enable()); print(jax.config.jax_compilation_cache_dir)")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    unset = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=120)
    assert unset.stdout.split() == [str(ROOT / ".jax_cache")] * 2
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    given = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=120)
    assert given.stdout.split() == [str(tmp_path)] * 2
