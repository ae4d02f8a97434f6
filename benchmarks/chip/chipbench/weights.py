"""Seeded random weights, made by the benchmark on the device in one jitted
call, in the program's parameter layout and the dtypes it serves them in.

The benchmark makes them so that the reference can use the very same
values without taking anything the program made.  The layout (leaf names,
shapes, dtypes) is read from the program's ``abstract_params``; each leaf
is filled by a rule on its name, and a name without a rule is an error.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> np.ndarray:
    """Threefry key data from any non-negative seed (JAX's ``PRNGKey``
    keeps only its low 32 bits)."""
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


def leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def _fill(name: str, shape, dtype, key):
    last = name.rsplit("/", 1)[-1]
    normal = jax.random.normal(key, shape, jnp.float32)
    uniform = jax.random.uniform(key, shape, jnp.float32)
    if last in ("scale", "norm_scale", "conv_b"):
        v = 0.1 * normal
    elif last == "tok":                      # [vocab, d]: rows of rms 1/sqrt(d)
        v = normal / np.sqrt(shape[-1])
    elif last == "conv_w":                   # [L, width, channels], depthwise
        v = normal / np.sqrt(shape[-2])
    elif last in ("unembed", "wq", "wk", "wv", "wo", "w_gate", "w_up",
                  "w_down", "in_proj", "out_proj"):
        v = normal / np.sqrt(shape[-2])      # N(0, 1/fan_in)
    elif last == "a_log":                    # A = -exp(a_log), |A| in [1, 16]
        v = jnp.log(1.0 + 15.0 * uniform)
    elif last == "dt_bias":                  # softplus(dt_bias) in [1e-3, 0.1]
        dt = jnp.exp(np.log(1e-3) + uniform * np.log(100.0))
        v = dt + jnp.log(-jnp.expm1(-dt))
    elif last == "d_skip":
        v = jnp.ones(shape, jnp.float32)
    else:
        raise KeyError(f"no weight rule for leaf {name!r}")
    return v.astype(dtype)


def make(cfg, seed: int, shardings=None):
    """The weight tree of ``cfg`` from ``seed``, made on the device.  The
    key is an argument, so one compiled program serves every seed."""
    from repro.models import model as M
    abstract = M.abstract_params(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    names = [leaf_name(p) for p, _ in flat]

    def build(key_data):
        key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
        leaves = [_fill(n, a.shape, a.dtype,
                        jax.random.fold_in(key, zlib.crc32(n.encode())))
                  for n, (_, a) in zip(names, flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    fn = jax.jit(build, out_shardings=shardings)
    return jax.block_until_ready(fn(jnp.asarray(seed_key(seed))))
