"""jit'd wrapper for the RG-LRU Pallas kernel (model-layer layout)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.rglru.rglru import rglru_scan as _kernel_scan


def rglru_mixer(x_gated, log_a, *, chunk=256, interpret=False):
    """x_gated [B,S,W] (input-gated), log_a [B,S,W] -> h [B,S,W] f32.

    Matches layers.rglru.rglru_scan (zero initial state).
    """
    return _kernel_scan(x_gated, log_a, chunk=chunk, interpret=interpret)
