"""jit'd wrapper around the Pallas flash-attention kernel.

Public entry matches models/layers/flash.flash_attention: q [B,S,H,hd],
k/v [B,S,K,hd].  Forward = Pallas kernel; backward = the pure-JAX chunked
VJP from models/layers/flash (identical math, recomputation-based).
The kernel compiles for the TPU by default; ``interpret=True`` executes its
body in Python on the CPU (how the tests validate it without a chip).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AttnConfig
from repro.kernels.flash_attention.flash_attention import flash_attention_fwd
from repro.models.layers import flash as jflash


def _fold(q, k, v):
    B, S, H, hd = q.shape
    K = k.shape[2]
    R = H // K
    qf = q.reshape(B, S, K, R, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B * K, S, R, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * K, k.shape[1], hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * K, v.shape[1], hd)
    return qf, kf, vf, (B, S, H, K, R, hd)


def _unfold(out, dims):
    B, S, H, K, R, hd = dims
    return out.reshape(B, K, S, R, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, S, H, hd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_kernel(q, k, v, cfg: AttnConfig, q_chunk=512,
                           kv_chunk=512, interpret=False):
    qf, kf, vf, dims = _fold(q, k, v)
    scale = (cfg.query_scale if cfg.query_scale is not None
             else 1.0 / np.sqrt(q.shape[-1]))
    out = flash_attention_fwd(qf, kf, vf, scale=scale, causal=cfg.causal,
                              window=cfg.window, softcap=cfg.logit_softcap,
                              q_chunk=q_chunk, kv_chunk=kv_chunk,
                              interpret=interpret)
    return _unfold(out, dims)


def _fwd(q, k, v, cfg, q_chunk, kv_chunk, interpret):
    out = flash_attention_kernel(q, k, v, cfg, q_chunk, kv_chunk, interpret)
    # lse recomputed in bwd by the pure-JAX path; save primals only
    return out, (q, k, v)


def _bwd(cfg, q_chunk, kv_chunk, interpret, res, dout):
    q, k, v = res
    # reuse the chunked pure-JAX VJP: re-run its forward for (out, lse)
    # residuals, then its backward — recomputation, no big saves.
    _, vjp = jax.vjp(
        lambda q_, k_, v_: jflash.flash_attention(q_, k_, v_, cfg, q_chunk,
                                                  kv_chunk, False), q, k, v)
    return vjp(dout)


flash_attention_kernel.defvjp(_fwd, _bwd)


def attention(q, k, v, cfg: AttnConfig, q_chunk=512, kv_chunk=512,
              interpret=False):
    """Drop-in attention entry point selecting the Pallas kernel."""
    return flash_attention_kernel(q, k, v, cfg, q_chunk, kv_chunk, interpret)
