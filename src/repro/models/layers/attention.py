"""Attention: projections, dense reference attention, and decode attention.

Dense attention is the oracle used by smoke tests and by tiny configs; the
chunked flash implementation (layers/flash.py) and the Pallas kernel
(repro.kernels.flash_attention) must match it.

Decode attention supports a *sequence-sharded* KV cache: on the production
mesh the cache sequence dimension lives on the "model" axis; each device
computes partial attention over its sequence shard and shards are combined
with a numerically-stable log-sum-exp ``psum`` inside ``shard_map`` (a
flash-decode pattern — the TPU-native answer to GQA head counts that do not
divide the TP width).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AttnConfig
from repro.kernels.decode_attention.decode_attention import (
    stacked_decode_attention)
from repro.models.layers.basic import _leaf, apply_rope
from repro.models.sharding import get_mesh

A = jax.ShapeDtypeStruct
NEG_INF = -0.7 * float(np.finfo(np.float32).max)


def attn_params(d, n_heads, n_kv, head_dim, dtype, key=None):
    ks = jax.random.split(key, 4) if key is not None else (None,) * 4
    return {
        "wq": _leaf((d, n_heads * head_dim), dtype, ks[0], "normal"),
        "wk": _leaf((d, n_kv * head_dim), dtype, ks[1], "normal"),
        "wv": _leaf((d, n_kv * head_dim), dtype, ks[2], "normal"),
        "wo": _leaf((n_heads * head_dim, d), dtype, ks[3], "normal"),
    }


def attn_axes():
    return {"wq": ("embed", "heads"), "wk": ("embed", "kv_heads"),
            "wv": ("embed", "kv_heads"), "wo": ("heads", "embed")}


def qkv(p, x, n_heads, n_kv, head_dim, positions, rope_theta):
    """Project and rope. Returns q [B,S,H,hd], k/v [B,S,Khv,hd]."""
    B, S, _ = x.shape
    q = (x @ p["wq"]).reshape(B, S, n_heads, head_dim)
    k = (x @ p["wk"]).reshape(B, S, n_kv, head_dim)
    v = (x @ p["wv"]).reshape(B, S, n_kv, head_dim)
    q = apply_rope(q, positions, rope_theta)
    k = apply_rope(k, positions, rope_theta)
    return q, k, v


def _mask(q_pos, k_pos, causal, window):
    """[Sq, Sk] bool mask: True = attend."""
    m = jnp.ones((q_pos.shape[-1], k_pos.shape[-1]), bool)
    if causal:
        m &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        m &= q_pos[:, None] - k_pos[None, :] < window
    return m


def dense_attention(q, k, v, cfg: AttnConfig, q_offset=0):
    """Reference attention. q [B,Sq,H,hd], k/v [B,Sk,K,hd]."""
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    rep = H // K
    scale = cfg.query_scale if cfg.query_scale is not None else 1.0 / np.sqrt(hd)
    qh = q.reshape(B, Sq, K, rep, hd)
    s = jnp.einsum("bqkrd,bskd->bkrqs", qh, k).astype(jnp.float32) * scale
    if cfg.logit_softcap:
        s = cfg.logit_softcap * jnp.tanh(s / cfg.logit_softcap)
    q_pos = jnp.arange(Sq) + q_offset
    k_pos = jnp.arange(k.shape[1])
    m = _mask(q_pos, k_pos, cfg.causal, cfg.window)
    s = jnp.where(m[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkrqs,bskd->bqkrd", p, v)
    return o.reshape(B, Sq, H, hd)


# ---------------------------------------------------------------------------
# Decode attention over a (possibly sequence-sharded) KV cache
# ---------------------------------------------------------------------------

def decode_attention_local(q, k_cache, v_cache, valid_len, cfg: AttnConfig,
                           kv_offset=0):
    """Partial decode attention over a local KV-cache shard.

    q        [B, 1, H, hd]
    k/v      [B, Sc, K, hd]   (this device's shard of the cache)
    valid_len scalar or [B]   (valid cache positions, per sequence)
    kv_offset scalar          (global position of this shard's first slot)

    Returns (numerator [B,1,H,hd] f32, denominator [B,1,H] f32, max [B,1,H]).
    Combine shards with combine_decode_partials (LSE merge).
    """
    B, _, H, hd = q.shape
    K = k_cache.shape[2]
    rep = H // K
    scale = cfg.query_scale if cfg.query_scale is not None else 1.0 / np.sqrt(hd)
    qh = q.reshape(B, K, rep, hd)
    s = jnp.einsum("bkrd,bskd->bkrs", qh, k_cache).astype(jnp.float32) * scale
    if cfg.logit_softcap:
        s = cfg.logit_softcap * jnp.tanh(s / cfg.logit_softcap)
    pos = kv_offset + jnp.arange(k_cache.shape[1])
    vl = jnp.asarray(valid_len)
    if vl.ndim == 0:
        vl = jnp.broadcast_to(vl, (B,))
    ok = pos[None, :] < vl[:, None]                            # [B, Sc]
    if cfg.window is not None:
        ok &= pos[None, :] >= (vl[:, None] - cfg.window)
    okb = ok[:, None, None, :]
    s = jnp.where(okb, s, NEG_INF)
    m = jnp.max(s, axis=-1)                                    # [B,K,rep]
    p = jnp.exp(s - m[..., None])
    p = jnp.where(okb, p, 0.0)
    den = jnp.sum(p, axis=-1)
    num = jnp.einsum("bkrs,bskd->bkrd", p.astype(v_cache.dtype), v_cache)
    num = num.astype(jnp.float32)
    return (num.reshape(B, 1, H, hd), den.reshape(B, 1, H), m.reshape(B, 1, H))


def decode_attention_stacked(q, k_stack, v_stack, layer, valid_len,
                             cfg: AttnConfig):
    """Decode attention over layer ``layer`` of stacked caches
    k/v_stack [L, B, S, K, hd] -> [B, 1, H, hd] f32.

    Compiled for a TPU, a Pallas kernel reads the layer's blocks straight
    from the stack.  Elsewhere, and on a mesh (the kernel would be handed
    the whole sharded stack), XLA slices the layer out: on a TPU that is a
    copy of the layer's whole K and V."""
    def sliced(q, k_stack, v_stack, layer, valid_len):
        return finalize_decode(*decode_attention_local(
            q, k_stack[layer], v_stack[layer], valid_len, cfg))

    def in_place(q, k_stack, v_stack, layer, valid_len):
        scale = (cfg.query_scale if cfg.query_scale is not None
                 else 1.0 / np.sqrt(q.shape[-1]))
        return stacked_decode_attention(
            q, k_stack, v_stack, layer, valid_len, scale=scale,
            window=cfg.window, softcap=cfg.logit_softcap)

    args = (q, k_stack, v_stack, layer, valid_len)
    if get_mesh() is not None:
        return sliced(*args)
    return jax.lax.platform_dependent(*args, tpu=in_place, default=sliced)


def combine_decode_partials(num, den, m, axis_name):
    """LSE-combine decode partials across a mesh axis (inside shard_map)."""
    g_m = jax.lax.pmax(m, axis_name)
    corr = jnp.exp(m - g_m)
    num = jax.lax.psum(num * corr[..., None], axis_name)
    den = jax.lax.psum(den * corr, axis_name)
    return num / jnp.maximum(den, 1e-30)[..., None]


def finalize_decode(num, den, m):
    """Single-shard finalization (no mesh axis)."""
    return num / jnp.maximum(den, 1e-30)[..., None]
