"""Compiles for a described TPU v5e (2x2 topology) with no chip attached: the
TPU compiler refuses here what the chip would refuse (untileable blocks,
unlowerable ops, too much VMEM, programs over HBM), at no chip time.

This is the only file that describes the chip.  The topology is described
inside a module fixture (never at import), and the persistent compilation
cache is off around the compiles: entries written for a described chip
cannot be read back without one.  Shapes are those ``chip_smoke.py`` runs.
"""
import importlib.util
import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs.archs import ARCHS
from repro.configs.base import AttnConfig, RunConfig, mconfig_replace
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.rglru import ops as lru_ops
from repro.kernels.ssd import ops as ssd_ops
from repro.launch.serve import SERVE_PCFG
from repro.models import model as M
from repro.models import sharding as SH
from repro.runtime.trainer import build_step, parallel_config
from repro.serve.engine import decode_fn
from repro.train import optimizer as opt

HBM_BYTES = 16 * 10 ** 9        # one v5e chip: 16 GB of HBM

SMOKE = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, no_cache):
    return SingleDeviceSharding(topo.devices[0])


def _on(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _device_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def _all_reduce_groups(hlo):
    """Replica groups of every all-reduce in an HLO text, as sorted tuples
    of device-id tuples (explicit ``{{0,2},{1,3}}`` or iota
    ``[2,2]<=[2,2]T(1,0)`` spelling)."""
    out = set()
    for line in hlo.splitlines():
        if not re.search(r"all-reduce(-start)?\(", line):
            continue
        m = re.search(r"replica_groups=(\{[\d,{}]*\}|\[[\d,]+\]<=\[[\d,]+\]"
                      r"(?:T\([\d,]+\))?)", line)
        if m is None:
            continue
        spec = m.group(1)
        if spec.startswith("{"):
            groups = [tuple(int(i) for i in g.split(",") if i)
                      for g in re.findall(r"\{([\d,]*)\}", spec[1:-1])]
        else:
            shape, dims, perm = re.fullmatch(
                r"\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?",
                spec).groups()
            ids = np.arange(np.prod([int(d) for d in dims.split(",")]))
            ids = ids.reshape([int(d) for d in dims.split(",")])
            if perm:
                ids = ids.transpose([int(d) for d in perm.split(",")])
            groups = [tuple(g) for g in ids.reshape(
                [int(d) for d in shape.split(",")]).tolist()]
        out.add(tuple(sorted(groups)))
    return out


def _kernel_program(name, shapes):
    """(fn, argument shapes) of one kernel as chip_smoke.py calls it."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    if name == "flash":
        B, S, H, K, hd, cq, ck = shapes.flash
        acfg = AttnConfig(causal=True)
        return (lambda q, k, v: fa_ops.attention(q, k, v, acfg, cq, ck),
                [((B, S, H, hd), bf16), ((B, S, K, hd), bf16),
                 ((B, S, K, hd), bf16)])
    if name == "ssd":
        B, S, H, P, N, chunk = shapes.ssd
        return (lambda *a: ssd_ops.ssd_mixer(*a, chunk=chunk),
                [((B, S, H, P), bf16), ((B, S, H), f32), ((H,), f32),
                 ((B, S, 1, N), bf16), ((B, S, 1, N), bf16)])
    B, S, W, chunk = shapes.rglru
    return (lambda *a: lru_ops.rglru_mixer(*a, chunk=chunk),
            [((B, S, W), f32), ((B, S, W), f32)])


@pytest.mark.parametrize("name", ["flash", "ssd", "rglru"])
def test_kernel_compiles_at_real_width(name, smoke, one_chip):
    fn, args = _kernel_program(name, smoke.REAL_KERNELS)
    compiled = jax.jit(fn).lower(*[
        jax.ShapeDtypeStruct(s, d, sharding=one_chip)
        for s, d in args]).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _moves(hlo, shapes):
    """Instructions of an HLO text, fused ones included, that copy or
    (dynamic-)slice-and-write an array of one of ``shapes``."""
    out = []
    for m in re.finditer(r"%(\S+) = \w+\[([\d,]*)\]\S* "
                         r"(copy|dynamic-slice|dynamic-update-slice)\(",
                         hlo):
        if tuple(int(d) for d in m.group(2).split(",") if d) in shapes:
            out.append(m.group(1))
    return out


def test_serving_programs_fit_one_chip(smoke, one_chip):
    """The 8-layer minitron-8b decode step at 16 slots x 2048 positions,
    with its cache donated, and the jitted weight draw.  The decode
    writes its new rows into the donated cache in place: it neither copies
    the stacked cache nor slices a layer's K or V out of it."""
    cfg = mconfig_replace(ARCHS["minitron-8b"], n_layers=smoke.SERVE_LAYERS)
    params = _on(M.abstract_params(cfg), one_chip)
    cache = _on(M.init_cache(cfg, 16, 2048, abstract=True), one_chip)
    toks = jax.ShapeDtypeStruct((16, 1), jnp.int32, sharding=one_chip)
    decode = decode_fn(cfg, SERVE_PCFG).lower(params, cache, toks).compile()
    cache_bytes = sum(a.size * a.dtype.itemsize
                      for a in jax.tree.leaves(cache))
    memory = decode.memory_analysis()
    assert memory.alias_size_in_bytes >= cache_bytes
    assert memory.temp_size_in_bytes < cache_bytes / 8
    stack = cache["groups"][0]["0.attn"]["k"].shape   # [8, 16, 2048, 8, 128]
    assert _moves(decode.as_text(),
                  {stack, (1,) + stack[1:], stack[1:]}) == []
    assert _device_bytes(decode) < HBM_BYTES

    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    init = jax.jit(M.init_params, static_argnums=0).lower(cfg, key).compile()
    assert _device_bytes(init) < HBM_BYTES


@pytest.mark.parametrize("dp", [2, 1])
def test_mamba2_train_step_fits_mesh(dp, topo, no_cache):
    """mamba2-370m at published widths, global batch 8 x 1024, on the
    trainer's dp x 2 mesh (dp 2: all four chips; dp 1: after an eviction)."""
    cfg = ARCHS["mamba2-370m"]
    rcfg = RunConfig(model=cfg)
    mesh = Mesh(np.asarray(topo.devices[:2 * dp]).reshape(dp, 2),
                ("data", "model"))
    pcfg = parallel_config(dp, 2)
    step, _, _, _, rules = build_step(
        cfg, rcfg, pcfg, mesh, {"tokens": np.zeros((8, 1025), np.int32)})
    like_p = M.abstract_params(cfg)
    SH.set_mesh(mesh, rules)
    try:
        compiled = step.lower(
            like_p, opt.init_opt_state(rcfg, like_p, pcfg, abstract=True),
            {"tokens": jax.ShapeDtypeStruct((8, 1025), jnp.int32)}).compile()
    finally:
        SH.set_mesh(None)
    assert _device_bytes(compiled) < HBM_BYTES
    groups = _all_reduce_groups(compiled.as_text())
    # model-axis groups are the mesh's rows ({0,1},{2,3} at dp 2); the
    # gradient reduction over "data" runs down its columns ({0,2},{1,3})
    model_pairs = tuple(sorted(map(tuple, np.arange(2 * dp).reshape(dp, 2))))
    assert model_pairs in groups, groups
    if dp == 2:
        assert ((0, 2), (1, 3)) in groups, groups
