"""Sub-layer dispatch: params / logical axes / apply for each block kind.

Block kinds: 'attn', 'attn_local', 'mlp', 'moe', 'ssd', 'rglru', 'cross_attn'.
Every sublayer is pre-norm (optionally sandwich post-norm, gemma-2 style) and
residual.  Apply functions return (x, aux, cache_update) so MoE aux losses and
decode-cache updates flow through a uniform interface.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ParallelConfig
from repro.models.layers import basic
from repro.models.layers.attention import (attn_axes, attn_params,
                                           decode_attention_stacked,
                                           dense_attention, qkv)
from repro.models.layers.flash import flash_attention
from repro.models.layers.moe import moe, moe_axes, moe_params
from repro.models.layers.rglru import (rglru_axes, rglru_block, rglru_params,
                                       rglru_init_state)
from repro.models.layers.ssd import (ssd_axes, ssd_block, ssd_params,
                                     ssd_init_state)

A = jax.ShapeDtypeStruct


def _acfg(cfg: ModelConfig, kind: str):
    if kind == "attn_local":
        assert cfg.attn_local is not None
        return cfg.attn_local
    return cfg.attn


def sublayer_params(cfg: ModelConfig, kind: str, dtype, key=None):
    d = cfg.d_model
    norm = {"norm_in": basic.rmsnorm_params(d, dtype, key)}
    if cfg.post_block_norm:
        norm["norm_out"] = basic.rmsnorm_params(d, dtype, key)
    k2 = jax.random.split(key)[1] if key is not None else None
    if kind in ("attn", "attn_local", "cross_attn"):
        core = attn_params(d, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, dtype, k2)
    elif kind == "mlp":
        core = basic.mlp_params(d, cfg.d_ff, dtype, k2)
    elif kind == "moe":
        core = moe_params(d, cfg.moe, dtype, k2)
    elif kind == "ssd":
        core = ssd_params(d, cfg.ssd, dtype, k2)
    elif kind == "rglru":
        core = rglru_params(d, cfg.rglru, dtype, k2)
    else:
        raise ValueError(kind)
    return {**norm, "core": core}


def sublayer_axes(cfg: ModelConfig, kind: str):
    norm = {"norm_in": basic.rmsnorm_axes()}
    if cfg.post_block_norm:
        norm["norm_out"] = basic.rmsnorm_axes()
    if kind in ("attn", "attn_local", "cross_attn"):
        core = attn_axes()
    elif kind == "mlp":
        core = basic.mlp_axes()
    elif kind == "moe":
        core = moe_axes()
    elif kind == "ssd":
        core = ssd_axes()
    elif kind == "rglru":
        core = rglru_axes()
    else:
        raise ValueError(kind)
    return {**norm, "core": core}


# ---------------------------------------------------------------------------
# apply — full-sequence (train / prefill)
# ---------------------------------------------------------------------------

def apply_sublayer(cfg: ModelConfig, pcfg: ParallelConfig, kind: str, p, x,
                   positions, enc_out=None, cache=None, decode_index=None,
                   layer=None):
    """Returns (x_new, aux_loss, new_cache_entry).

    In decode (``decode_index`` given) ``cache`` is the group's stacked
    entry, layers first, and ``layer`` this sublayer's index in it; the
    returned entry is that stacked cache with this layer's new state
    written in."""
    acfg = _acfg(cfg, kind)
    h = basic.rmsnorm(p["norm_in"], x, cfg.rms_eps)
    aux = jnp.zeros((), jnp.float32)
    new_cache = cache

    if kind in ("attn", "attn_local"):
        if decode_index is None:
            q, k, v = qkv(p["core"], h, cfg.n_heads, cfg.n_kv_heads,
                          cfg.head_dim, positions, cfg.rope_theta)
            if cache is not None:   # prefill: also populate the cache
                new_cache = dict(cache)
                new_cache["k"] = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"].astype(k.dtype), k, 0, axis=1)
                new_cache["v"] = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"].astype(v.dtype), v, 0, axis=1)
            if pcfg.attn_impl == "dense":
                o = dense_attention(q, k, v, acfg)
            else:
                o = flash_attention(q, k, v, acfg, pcfg.flash_q_chunk,
                                    pcfg.flash_kv_chunk, pcfg.flash_causal_skip)
            o = o.reshape(*h.shape[:2], cfg.n_heads * cfg.head_dim)
            h = o @ p["core"]["wo"]
        else:                       # single-token decode against the cache
            q, k, v = qkv(p["core"], h, cfg.n_heads, cfg.n_kv_heads,
                          cfg.head_dim, positions, cfg.rope_theta)
            B = h.shape[0]
            idx = jnp.broadcast_to(jnp.asarray(decode_index), (B,))
            # one row per slot at [layer, slot, index]; an index past the
            # end overwrites the last row, as dynamic_update_slice clamps
            row = jnp.clip(idx, 0, cache["k"].shape[2] - 1)
            slot = jnp.arange(B)
            new_cache = {n: cache[n].at[layer, slot, row].set(
                t[:, 0].astype(cache[n].dtype))
                for n, t in (("k", k), ("v", v))}
            o = decode_attention_stacked(
                q, new_cache["k"], new_cache["v"], layer, idx + 1,
                acfg).astype(h.dtype)
            o = o.reshape(h.shape[0], 1, cfg.n_heads * cfg.head_dim)
            h = o @ p["core"]["wo"]

    elif kind == "cross_attn":
        if decode_index is None:
            # training / prefill: compute cross K/V from encoder output
            B, Se, _ = enc_out.shape
            k = (enc_out @ p["core"]["wk"]).reshape(B, Se, cfg.n_kv_heads,
                                                    cfg.head_dim)
            v = (enc_out @ p["core"]["wv"]).reshape(B, Se, cfg.n_kv_heads,
                                                    cfg.head_dim)
            if cache is not None:
                new_cache = {"k": k.astype(cache["k"].dtype),
                             "v": v.astype(cache["v"].dtype)}
        else:                       # read-only: passed through unwritten
            k, v = cache["k"][layer], cache["v"][layer]
        B, Sd, _ = h.shape
        q = (h @ p["core"]["wq"]).reshape(B, Sd, cfg.n_heads, cfg.head_dim)
        from repro.configs.base import AttnConfig
        xacfg = AttnConfig(causal=False)
        if decode_index is None and pcfg.attn_impl != "dense" and Sd > 1:
            o = flash_attention(q, k.astype(h.dtype), v.astype(h.dtype), xacfg,
                                pcfg.flash_q_chunk, pcfg.flash_kv_chunk, False)
        else:
            o = dense_attention(q, k.astype(h.dtype), v.astype(h.dtype), xacfg)
        h = o.reshape(B, Sd, cfg.n_heads * cfg.head_dim) @ p["core"]["wo"]

    elif kind == "mlp":
        h = basic.mlp(p["core"], h)

    elif kind == "moe":
        h, aux = moe(p["core"], h, cfg.moe, cap_shard=pcfg.moe_cap_shard)

    elif kind in ("ssd", "rglru"):
        decoding = cache is not None and decode_index is not None
        st = cache["state"][layer] if decoding else None
        cv = cache["conv"][layer] if decoding else None
        if kind == "ssd":
            h, (new_st, new_cv) = ssd_block(p["core"], h, cfg.ssd, cfg.d_model,
                                            state=st, conv_state=cv,
                                            rms_eps=cfg.rms_eps)
        else:
            h, (new_st, new_cv) = rglru_block(p["core"], h, cfg.rglru,
                                              state=st, conv_state=cv)
        if decoding:                # the layer's whole (small) state
            new_cache = {n: cache[n].at[layer].set(t.astype(cache[n].dtype))
                         for n, t in (("state", new_st), ("conv", new_cv))}
        elif cache is not None:
            new_cache = {"state": new_st, "conv": new_cv}
    else:
        raise ValueError(kind)

    if cfg.post_block_norm:
        h = basic.rmsnorm(p["norm_out"], h, cfg.rms_eps)
    return (x + h).astype(x.dtype), aux, new_cache


def sublayer_cache(cfg: ModelConfig, kind: str, batch, max_len, cache_dtype,
                   abstract=False, enc_len=0):
    """Abstract/zero cache entry for one sublayer (None if stateless)."""
    if kind in ("attn", "attn_local"):
        shp = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
        if abstract:
            return {"k": A(shp, cache_dtype), "v": A(shp, cache_dtype)}
        return {"k": jnp.zeros(shp, cache_dtype), "v": jnp.zeros(shp, cache_dtype)}
    if kind == "cross_attn":
        shp = (batch, enc_len, cfg.n_kv_heads, cfg.head_dim)
        if abstract:
            return {"k": A(shp, cache_dtype), "v": A(shp, cache_dtype)}
        return {"k": jnp.zeros(shp, cache_dtype), "v": jnp.zeros(shp, cache_dtype)}
    if kind == "ssd":
        return ssd_init_state(batch, cfg.d_model, cfg.ssd, cache_dtype, abstract)
    if kind == "rglru":
        return rglru_init_state(batch, cfg.d_model, cfg.rglru, cache_dtype,
                                abstract)
    return None


def cache_axes(kind: str):
    """Logical axes for a sublayer cache entry (leading scan dim added later)."""
    if kind in ("attn", "attn_local"):
        return {"k": ("batch", "kv_seq", None, None),
                "v": ("batch", "kv_seq", None, None)}
    if kind == "cross_attn":
        return {"k": ("batch", "kv_seq", None, None),
                "v": ("batch", "kv_seq", None, None)}
    if kind == "ssd":
        return {"state": ("batch", "ssm_heads", None, None),
                "conv": ("batch", None, "inner")}
    if kind == "rglru":
        return {"state": ("batch", "inner"), "conv": ("batch", None, "inner")}
    return None
