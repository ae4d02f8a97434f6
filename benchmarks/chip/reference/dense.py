"""Plain float32 reference of the dense decoder (``configs/minitron-8b.json``).

Straight ``jax.numpy`` with no cache, kernels or batching tricks, written
from the model's description and independent of ``src/repro/models``:
token embedding; per layer a pre-norm block of grouped-query causal
self-attention with rotary positions (rotate-half form), then a pre-norm
gated SiLU MLP, each added to the residual stream; a final norm and the
output projection.  The norm is RMSNorm with a ``(1 + scale)`` gain, as the
configuration's family states.

It reads the weights by their names in the benchmark's weight tree and
upcasts one layer at a time, so the float32 copy of the whole model (16 GB
for 8 minitron-8b layers) never exists.  The logits are reduced over
vocabulary chunks to what the comparison needs at each position: the best
logit, the logit of a given token, and the arg-max.

``weights="fp8"`` is the control, the step below the bfloat16 that the
configuration states: every matrix product of a layer and of the output
projection takes float8 e4m3 operands (one scale per weight output
channel, one per activation row) and accumulates in float32; attention's
own products stay in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

VOCAB_CHUNK = 32_000


def quantize_fp8(w, axis=-2):
    """Round ``w`` to float8 e4m3 with one scale per slice along ``axis``
    (the default: per output channel of an [..., in, out] matrix), and
    return it dequantized in float32."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(a, w, weights):
    """``a @ w`` in float32, or for the control with float8 operands."""
    if weights == "fp8":
        return quantize_fp8(a, axis=-1) @ quantize_fp8(w)
    return a @ w.astype(jnp.float32)


def rmsnorm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale)


def rope(x, theta):
    """x [T, heads, hd]: rotate-half rotary embedding at positions 0..T-1."""
    T, _, hd = x.shape
    inv = theta ** (-np.arange(0, hd // 2, dtype=np.float32) * 2.0 / hd)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(cfg, w, x, weights="float32"):
    """One decoder layer on one sequence x [T, D] (float32)."""
    T = x.shape[0]
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = rmsnorm(x, w["attn_norm"], cfg.rms_eps)
    q = rope(mm(h, w["wq"], weights).reshape(T, H, hd), cfg.rope_theta)
    k = rope(mm(h, w["wk"], weights).reshape(T, K, hd), cfg.rope_theta)
    v = mm(h, w["wv"], weights).reshape(T, K, hd)
    q = q.reshape(T, K, H // K, hd)
    s = jnp.einsum("tkrd,skd->krts", q, k) / np.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("krts,skd->tkrd", p, v).reshape(T, H * hd)
    x = x + mm(o, w["wo"], weights)
    h = rmsnorm(x, w["mlp_norm"], cfg.rms_eps)
    g = jax.nn.silu(mm(h, w["w_gate"], weights)) * mm(h, w["w_up"], weights)
    return x + mm(g, w["w_down"], weights)


def layer_weights(params, i):
    """Layer ``i``'s weights, as stored, by their names in the tree."""
    g = params["groups"][0]
    a, m = g["0.attn"], g["0.mlp"]
    return {"attn_norm": a["norm_in"]["scale"][i],
            "wq": a["core"]["wq"][i], "wk": a["core"]["wk"][i],
            "wv": a["core"]["wv"][i], "wo": a["core"]["wo"][i],
            "mlp_norm": m["norm_in"]["scale"][i],
            "w_gate": m["core"]["w_gate"][i], "w_up": m["core"]["w_up"][i],
            "w_down": m["core"]["w_down"][i]}


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(cfg, w, x, weights):
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    return jax.lax.map(lambda xs: block(cfg, w, xs, weights), x)


@functools.partial(jax.jit, static_argnums=(0, 5))
def _head(cfg, norm, unembed, x, targets, weights):
    """Per position: best logit, the logits of ``targets`` [n, T, m], the
    arg-max."""
    V = cfg.vocab_size
    n_chunks = -(-unembed.shape[1] // VOCAB_CHUNK)
    pad = n_chunks * VOCAB_CHUNK - unembed.shape[1]
    u = jnp.pad(unembed, ((0, 0), (0, pad))).reshape(
        unembed.shape[0], n_chunks, VOCAB_CHUNK).transpose(1, 0, 2)

    def one(args):
        xs, tg = args
        h = rmsnorm(xs, norm.astype(jnp.float32), cfg.rms_eps)

        def chunk(carry, c):
            best, arg, tl = carry
            ids = c * VOCAB_CHUNK + jnp.arange(VOCAB_CHUNK)
            lg = mm(h, u[c], weights)
            lg = jnp.where(ids[None, :] < V, lg, -jnp.inf)
            cb, ca = lg.max(-1), c * VOCAB_CHUNK + lg.argmax(-1)
            arg = jnp.where(cb > best, ca, arg)
            best = jnp.maximum(best, cb)
            inside = (tg >= c * VOCAB_CHUNK) & (tg < (c + 1) * VOCAB_CHUNK)
            got = jnp.take_along_axis(
                lg, jnp.clip(tg - c * VOCAB_CHUNK, 0, VOCAB_CHUNK - 1), -1)
            return (best, arg, jnp.where(inside, got, tl)), None

        T = xs.shape[0]
        init = (jnp.full((T,), -jnp.inf), jnp.zeros((T,), jnp.int32),
                jnp.full(tg.shape, -jnp.inf))
        (best, arg, tl), _ = jax.lax.scan(chunk, init, jnp.arange(n_chunks))
        return best, tl, arg

    return jax.lax.map(one, (x, targets))


def logits_at(cfg, params, tokens, targets, weights="float32"):
    """Run the reference over ``tokens`` [n, T] (causal, positions 0..T-1)
    and return, per position, the best logit [n, T], the logits of
    ``targets`` [n, T, m] and the arg-max [n, T], on the host.
    ``weights="fp8"`` gives the control."""
    with jax.default_matmul_precision("highest"):
        emb = params["embed"]["tok"]
        x = emb[jnp.asarray(tokens)].astype(jnp.float32)
        for i in range(cfg.n_layers):
            x = _layer(cfg, layer_weights(params, i), x, weights)
        unembed = params["embed"].get("unembed")
        if unembed is None:
            unembed = emb.T
        out = _head(cfg, params["final_norm"]["scale"], unembed, x,
                    jnp.asarray(targets), weights)
    return tuple(np.asarray(a) for a in out)
