"""Unified model assembly for all assigned architectures.

A model is: embedding (+ optional modality frontend) -> a stack of scanned
*groups* -> final norm -> (un)embedding.  Each group repeats a block
``pattern`` R times via ``lax.scan`` over stacked parameters, with
``jax.remat`` inside the body (compile-time and memory control: the 126-layer
llama3-405b train step lowers+compiles in seconds).

Entry points:
  abstract_params / init_params / param_axes
  loss_and_aux (train), prefill, decode_step, init_cache
  count_params (analytic, cross-checked against the tree in tests)
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ParallelConfig
from repro.models import blocks
from repro.models.layers import basic
from repro.models.sharding import constrain

A = jax.ShapeDtypeStruct


# ---------------------------------------------------------------------------
# Stack structure
# ---------------------------------------------------------------------------

def stack_groups(cfg: ModelConfig, n_layers=None) -> List[Tuple[Tuple, int]]:
    """[(pattern, repeats), ...] covering n_layers total layers."""
    n = cfg.n_layers if n_layers is None else n_layers
    u = len(cfg.pattern)
    groups = []
    if n // u:
        groups.append((cfg.pattern, n // u))
    if n % u:
        groups.append((cfg.pattern[: n % u], 1))
    return groups


def _unit_params(cfg, pattern, dtype, key=None):
    import zlib
    out = {}
    for i, layer_kinds in enumerate(pattern):
        for kind in layer_kinds:
            k = (jax.random.fold_in(key, zlib.crc32(f"{i}.{kind}".encode()))
                 if key is not None else None)
            out[f"{i}.{kind}"] = blocks.sublayer_params(cfg, kind, dtype, k)
    return out


def _stack(tree, r):
    return jax.tree.map(
        lambda l: A((r,) + l.shape, l.dtype) if isinstance(l, A)
        else jnp.broadcast_to(l, (r,) + l.shape), tree)


def _params(cfg: ModelConfig, key=None) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.act_dtype)
    ks = jax.random.split(key, 8) if key is not None else [None] * 8
    p: Dict[str, Any] = {
        "embed": basic.embed_params(cfg.padded_vocab, cfg.d_model, dtype, ks[0],
                                    tie=cfg.tie_embeddings),
        "final_norm": basic.rmsnorm_params(cfg.d_model, dtype, ks[1]),
    }
    groups = []
    for gi, (pattern, r) in enumerate(stack_groups(cfg)):
        if key is None:
            unit = _unit_params(cfg, pattern, dtype, None)
            groups.append(_stack(unit, r))
        else:
            kr = jax.random.split(jax.random.fold_in(ks[2], gi), r)
            groups.append(jax.vmap(
                lambda k: _unit_params(cfg, pattern, dtype, k))(kr))
    p["groups"] = groups
    if cfg.family == "encdec":
        enc_groups = []
        enc_cfg = _encoder_cfg(cfg)
        for gi, (pattern, r) in enumerate(stack_groups(enc_cfg)):
            if key is None:
                enc_groups.append(_stack(_unit_params(enc_cfg, pattern, dtype,
                                                      None), r))
            else:
                kr = jax.random.split(jax.random.fold_in(ks[3], gi), r)
                enc_groups.append(jax.vmap(
                    lambda k: _unit_params(enc_cfg, pattern, dtype, k))(kr))
        p["enc_groups"] = enc_groups
        p["enc_norm"] = basic.rmsnorm_params(cfg.d_model, dtype, ks[4])
    if cfg.family == "vlm":
        p["vis_proj"] = basic._leaf((VIS_EMBED_DIM, cfg.d_model), dtype, ks[5],
                                    "normal")
    return p


VIS_EMBED_DIM = 3200  # InternViT-6B hidden size (frontend stub output)


def abstract_params(cfg):
    return _params(cfg, None)


def init_params(cfg, key):
    return _params(cfg, key)


def _encoder_cfg(cfg: ModelConfig) -> ModelConfig:
    from repro.configs.base import AttnConfig, mconfig_replace
    return mconfig_replace(cfg, n_layers=cfg.enc_layers,
                           pattern=(("attn", "mlp"),),
                           attn=AttnConfig(causal=False))


def param_axes(cfg: ModelConfig):
    """Tree of logical-axis tuples matching abstract_params (scan dim first)."""
    def unit_axes(c, pattern):
        out = {}
        for i, layer_kinds in enumerate(pattern):
            for kind in layer_kinds:
                sub = blocks.sublayer_axes(c, kind)
                out[f"{i}.{kind}"] = jax.tree.map(
                    lambda ax: ("layers",) + ax, sub,
                    is_leaf=lambda v: isinstance(v, tuple))
        return out

    axes: Dict[str, Any] = {
        "embed": basic.embed_axes(tie=cfg.tie_embeddings),
        "final_norm": basic.rmsnorm_axes(),
        "groups": [unit_axes(cfg, pat) for pat, _ in stack_groups(cfg)],
    }
    if cfg.family == "encdec":
        ec = _encoder_cfg(cfg)
        axes["enc_groups"] = [unit_axes(ec, pat) for pat, _ in stack_groups(ec)]
        axes["enc_norm"] = basic.rmsnorm_axes()
    if cfg.family == "vlm":
        axes["vis_proj"] = (None, "embed")
    return axes


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------

def _fsdp_gather_axes(cfg, pattern):
    """Per-unit logical axes with the FSDP-mapped axes dropped (scan-slice
    view, no leading 'layers').  Constraining the sliced weights to these
    axes *inside* the scan body makes GSPMD all-gather one layer per
    iteration instead of resharding the whole stacked array before the loop
    (measured: 18.5 -> ~2 GiB/device fwd temp on llama3-405b)."""
    out = {}
    for i, layer_kinds in enumerate(pattern):
        for kind in layer_kinds:
            sub = blocks.sublayer_axes(cfg, kind)
            out[f"{i}.{kind}"] = jax.tree.map(
                lambda ax: tuple(None if a in ("embed", "inner_in") else a
                                 for a in ax),
                sub, is_leaf=lambda v: isinstance(v, tuple))
    return out


def _run_groups(cfg, pcfg, groups_p, patterns, x, positions, enc_out=None,
                caches=None, decode_index=None, remat=True):
    """Scan every group.  Returns (x, aux_sum, new_caches).

    Prefill scans each group's cache as ``xs`` and stacks what its layers
    write as ``ys``: it writes whole prompts.  Decode writes one row a
    layer, so there the group's stacked cache is part of the scan carry and
    each layer writes its rows into it in place.  As ``xs``/``ys`` every
    layer's cache would be sliced out, written back into a fresh stacked
    buffer, and that buffer copied once more onto the donated input."""
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = [] if caches is not None else None
    in_place = caches is not None and decode_index is not None

    for gi, (pattern, r) in enumerate(patterns):
        unit_p = groups_p[gi]
        cache_g = caches[gi] if caches is not None else None
        gather_axes = _fsdp_gather_axes(cfg, pattern) if pcfg.fsdp else None

        def body(carry, xs, _pattern=pattern, _gather=gather_axes):
            if in_place:
                (xx, aux, uc), (up, li) = carry, xs
            else:
                (xx, aux), (up, uc) = carry, xs
                li = None
            if _gather is not None:
                up = jax.tree.map(lambda w, ax: constrain(w, ax), up, _gather)
                if pcfg.gather_barrier:
                    # pin the gathered weights here: without the barrier XLA
                    # sinks the all-gathers into the flash-attention inner
                    # loops and re-gathers per chunk (measured 20x wire
                    # bytes on llama3-405b/train_4k — §Perf iteration 1)
                    up = jax.lax.optimization_barrier(up)
            ncache = {} if uc is not None else None
            for i, layer_kinds in enumerate(_pattern):
                for kind in layer_kinds:
                    key = f"{i}.{kind}"
                    c_in = uc.get(key) if uc is not None else None
                    c_in = c_in if c_in else None  # {} placeholder -> None
                    xx, a, c_out = blocks.apply_sublayer(
                        cfg, pcfg, kind, up[key], xx, positions,
                        enc_out=enc_out, cache=c_in, decode_index=decode_index,
                        layer=li)
                    if pcfg.seq_shard_acts and decode_index is None:
                        xx = constrain(xx, ("batch", "seq", None))
                    aux = aux + a
                    if ncache is not None:
                        ncache[key] = c_out if c_out is not None else {}
            if in_place:
                return (xx, aux, ncache), None
            return (xx, aux), ncache

        if remat and decode_index is None and pcfg.remat != "none":
            policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                      if pcfg.remat == "dots" else None)
            fn = jax.remat(body, policy=policy)
        else:
            fn = body
        if in_place:
            (x, aux_total, ys), _ = jax.lax.scan(
                fn, (x, aux_total, cache_g), (unit_p, jnp.arange(r)))
        else:
            xs = (unit_p, cache_g if cache_g is not None
                  else jax.tree.map(lambda v: v, {k: {} for k in unit_p}))
            (x, aux_total), ys = jax.lax.scan(fn, (x, aux_total), xs)
        if new_caches is not None:
            new_caches.append(ys)
    return x, aux_total, new_caches


def _embed_inputs(cfg, params, batch, for_decode=False):
    """Returns (x, positions, labels, loss_mask, enc_in)."""
    tokens = batch["tokens"]
    if not for_decode:
        inputs, labels = tokens[:, :-1], tokens[:, 1:]
    else:
        inputs, labels = tokens, None
    x = basic.embed(params["embed"], inputs,
                    scale_by_sqrt_dim=cfg.emb_scale_by_sqrt_dim)
    mask = jnp.ones(x.shape[:2], jnp.float32) if labels is not None else None
    if cfg.family == "vlm" and "patches" in batch:
        vis = (batch["patches"] @ params["vis_proj"]).astype(x.dtype)
        x = jnp.concatenate([vis, x], axis=1)
        if mask is not None:
            mask = jnp.concatenate(
                [jnp.zeros(vis.shape[:2], jnp.float32), mask], axis=1)
            labels = jnp.concatenate(
                [jnp.zeros(vis.shape[:2], jnp.int32), labels], axis=1)
    positions = jnp.arange(x.shape[1])[None, :] + jnp.zeros(
        (x.shape[0], 1), jnp.int32)
    return x, positions, labels, mask


def encode(cfg, pcfg, params, frames):
    """Whisper encoder over (stubbed) frame embeddings [B, Se, D]."""
    ec = _encoder_cfg(cfg)
    pos = jnp.arange(frames.shape[1])[None, :] + jnp.zeros(
        (frames.shape[0], 1), jnp.int32)
    x = frames.astype(jnp.dtype(cfg.act_dtype))
    x, _, _ = _run_groups(ec, pcfg, params["enc_groups"], stack_groups(ec),
                          x, pos)
    return basic.rmsnorm(params["enc_norm"], x, cfg.rms_eps)


def loss_and_aux(cfg: ModelConfig, pcfg: ParallelConfig, params, batch):
    """Scalar LM loss (+MoE aux).  batch['tokens'] is [B, S+1]."""
    enc_out = None
    if cfg.family == "encdec":
        enc_out = encode(cfg, pcfg, params, batch["frames"])
    x, positions, labels, mask = _embed_inputs(cfg, params, batch)
    x = constrain(x, ("batch", "seq", None))
    x, aux, _ = _run_groups(cfg, pcfg, params["groups"], stack_groups(cfg), x,
                            positions, enc_out=enc_out)
    x = basic.rmsnorm(params["final_norm"], x, cfg.rms_eps)
    loss = _xent(cfg, pcfg, params, x, labels, mask)
    return loss + aux, {"xent": loss, "aux": aux}


def _xent(cfg, pcfg, params, x, labels, mask):
    """Chunked cross-entropy (avoids materializing [B,S,V] f32)."""
    B, S, D = x.shape
    chunk = pcfg.loss_chunk or S
    chunk = min(chunk, S)
    while S % chunk:
        chunk -= 1
    nc = S // chunk

    @jax.remat   # recompute per-chunk logits in backward (memory control)
    def chunk_loss(carry, idx):
        xs = jax.lax.dynamic_slice_in_dim(x, idx * chunk, chunk, 1)
        ls = jax.lax.dynamic_slice_in_dim(labels, idx * chunk, chunk, 1)
        ms = jax.lax.dynamic_slice_in_dim(mask, idx * chunk, chunk, 1)
        logits = basic.unembed_logits(params["embed"], xs,
                                      cfg.final_logit_softcap,
                                      n_valid=cfg.vocab_size)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ls[..., None], axis=-1)[..., 0]
        return carry + jnp.sum((lse - gold) * ms), None

    total, _ = jax.lax.scan(chunk_loss, jnp.zeros((), jnp.float32),
                            jnp.arange(nc))
    return total / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------------------------
# Serving: cache init / prefill / decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch_size, max_len, abstract=False,
               cache_dtype=jnp.bfloat16, enc_len=0):
    def unit_cache(c, pattern, r):
        out = {}
        for i, layer_kinds in enumerate(pattern):
            for kind in layer_kinds:
                e = blocks.sublayer_cache(c, kind, batch_size, max_len,
                                          cache_dtype, abstract=False,
                                          enc_len=enc_len)
                out[f"{i}.{kind}"] = (jax.tree.map(
                    lambda l: jnp.zeros((r,) + l.shape, l.dtype), e)
                    if e is not None else {})
        return out

    def a_unit_cache(c, pattern, r):
        out = {}
        for i, layer_kinds in enumerate(pattern):
            for kind in layer_kinds:
                e = blocks.sublayer_cache(c, kind, batch_size, max_len,
                                          cache_dtype, abstract=True,
                                          enc_len=enc_len)
                out[f"{i}.{kind}"] = (jax.tree.map(
                    lambda l: A((r,) + l.shape, l.dtype), e)
                    if e is not None else {})
        return out

    mk = a_unit_cache if abstract else unit_cache
    cache = {"groups": [mk(cfg, pat, r) for pat, r in stack_groups(cfg)],
             "index": (A((batch_size,), jnp.int32) if abstract
                       else jnp.zeros((batch_size,), jnp.int32))}
    if cfg.family == "encdec":
        # encoder output replayed through cross-attn caches (per group entry)
        pass  # cross entries already sized via enc_len above
    return cache


def cache_logical_axes(cfg: ModelConfig):
    def unit(c, pattern):
        out = {}
        for i, layer_kinds in enumerate(pattern):
            for kind in layer_kinds:
                ax = blocks.cache_axes(kind)
                out[f"{i}.{kind}"] = (jax.tree.map(
                    lambda t: ("layers",) + t, ax,
                    is_leaf=lambda v: isinstance(v, tuple))
                    if ax is not None else {})
        return out
    return {"groups": [unit(cfg, pat) for pat, _ in stack_groups(cfg)],
            "index": ("batch",)}


def prefill(cfg, pcfg, params, batch, cache):
    """Populate cache from a prompt; returns (last-position logits, cache)."""
    enc_out = None
    if cfg.family == "encdec":
        enc_out = encode(cfg, pcfg, params, batch["frames"])
    x, positions, _, _ = _embed_inputs(cfg, params, batch, for_decode=True)
    x = constrain(x, ("batch", "seq", None))
    x, _, new_caches = _run_groups(cfg, pcfg, params["groups"],
                                   stack_groups(cfg), x, positions,
                                   enc_out=enc_out, caches=cache["groups"],
                                   remat=False)
    x = basic.rmsnorm(params["final_norm"], x, cfg.rms_eps)
    logits = basic.unembed_logits(params["embed"], x[:, -1:],
                                  cfg.final_logit_softcap,
                                  n_valid=cfg.vocab_size)
    return logits, {"groups": new_caches,
                    "index": jnp.full((x.shape[0],), x.shape[1], jnp.int32)}


def decode_step(cfg, pcfg, params, cache, tokens):
    """One token for every sequence.  tokens [B, 1] -> (logits [B,1,V], cache).

    cache['index'] is per-sequence [B] — slots may be at different positions
    (continuous batching in serve/engine.py)."""
    idx = cache["index"]
    x = basic.embed(params["embed"], tokens,
                    scale_by_sqrt_dim=cfg.emb_scale_by_sqrt_dim)
    positions = idx[:, None]
    x, _, new_caches = _run_groups(cfg, pcfg, params["groups"],
                                   stack_groups(cfg), x, positions,
                                   caches=cache["groups"], decode_index=idx,
                                   remat=False)
    x = basic.rmsnorm(params["final_norm"], x, cfg.rms_eps)
    logits = basic.unembed_logits(params["embed"], x, cfg.final_logit_softcap,
                                  n_valid=cfg.vocab_size)
    return logits, {"groups": new_caches, "index": idx + 1}


# ---------------------------------------------------------------------------
# Param counting (analytic)
# ---------------------------------------------------------------------------

def count_params(cfg: ModelConfig, active_only=False) -> int:
    tree = abstract_params(cfg)
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        n = int(np.prod(leaf.shape))
        keys = [getattr(k, "key", getattr(k, "idx", "")) for k in path]
        if active_only and any(str(k).endswith(".moe") for k in keys) \
                and str(keys[-1]) in ("w_gate", "w_up", "w_down"):
            n = n * cfg.moe.top_k // cfg.moe.n_experts
        total += n
    return total
