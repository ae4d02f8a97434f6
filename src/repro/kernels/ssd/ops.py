"""jit'd wrapper for the SSD Pallas kernel: model-layer layout in/out."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.ssd.ssd import ssd_scan


def ssd_mixer(x, dt, a_log, Bm, Cm, *, chunk=128, interpret=False):
    """x [B,S,H,P]; dt [B,S,H] (post-softplus); a_log [H];
    Bm/Cm [B,S,G,N] -> y [B,S,H,P].  Matches layers.ssd.ssd_chunked."""
    B, S, H, P = x.shape
    G = Bm.shape[2]
    rep = H // G
    xg = x.transpose(0, 2, 1, 3).reshape(B * H, S, P)
    dtg = dt.transpose(0, 2, 1).reshape(B * H, S)
    Bg = jnp.repeat(Bm, rep, axis=2).transpose(0, 2, 1, 3) \
        .reshape(B * H, S, -1)
    Cg = jnp.repeat(Cm, rep, axis=2).transpose(0, 2, 1, 3) \
        .reshape(B * H, S, -1)
    ag = jnp.tile(a_log, B)
    y = ssd_scan(xg, dtg, ag, Bg, Cg, chunk=chunk, interpret=interpret)
    return y.reshape(B, H, S, P).transpose(0, 2, 1, 3)
