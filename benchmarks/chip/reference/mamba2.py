"""Plain float32 reference of Mamba-2 training (``configs/mamba2-370m.json``):
the loss, its gradients and AdamW, independent of ``src/repro``.

Forward, per layer (arXiv:2405.21060, as the configuration's family
states it): RMSNorm with a ``(1 + scale)`` gain; one input projection to
(z, x, B, C, dt); dt = softplus(dt + dt_bias); a causal depthwise
convolution of width W with bias over (x, B, C), then SiLU; the selective
state space y_t = C_t . h_t with h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t,
A = -exp(a_log), computed here in its quadratic "masked attention" form
(no chunks, no scan); plus D x_t; a gated RMSNorm of y times SiLU(z); the
output projection; the residual.  Then a final RMSNorm and the output
projection through the tied embedding; the loss is the mean next-token
cross-entropy over the logical vocabulary.

Gradients are taken one sequence at a time and summed, so that a batch at
the timed size fits one chip; each layer is recomputed in the backward
pass.  The optimizer is AdamW as the run configuration states it:
gradient clipping to a global norm, bias correction, decoupled weight
decay on every stored leaf of rank 2 or more, and parameters stored in the
dtype the configuration serves them in (bfloat16) after each update.

``weights="fp8"`` is the control, the step below the bfloat16 that the
configuration states: the input and output projections and the output
head take float8 e4m3 operands (one scale per weight output channel, one
per activation row) and accumulate in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from reference.dense import mm, rmsnorm


def _layers(params):
    return params["groups"][0]["0.ssd"]


def ssd(x, dt, A, B, C):
    """x [S, H, P], dt [S, H], A [H], B/C [S, N] (one group) -> y [S, H, P]."""
    S = x.shape[0]
    seg = jnp.cumsum(dt * A, axis=0)                           # [S, H]
    diff = seg[:, None, :] - seg[None, :, :]                   # [t, s, H]
    causal = jnp.tril(jnp.ones((S, S), bool))[:, :, None]
    decay = jnp.exp(jnp.where(causal, diff, -jnp.inf))
    cb = C @ B.T                                               # [t, s]
    w = cb[:, :, None] * decay * dt[None, :, :]                # [t, s, H]
    return jnp.einsum("tsh,shp->thp", w, x)


def block(cfg, p, x, weights="float32"):
    """One Mamba-2 layer on one sequence x [S, D] (float32)."""
    s = cfg.ssd
    D = cfg.d_model
    di = s.expand * D
    H, P, N, W = di // s.head_dim, s.head_dim, s.d_state, s.conv_width
    S = x.shape[0]
    c = p["core"]
    h = rmsnorm(x, p["norm_in"]["scale"], cfg.rms_eps)
    proj = mm(h, c["in_proj"], weights)
    z, xr = proj[:, :di], proj[:, di:2 * di]
    bc = proj[:, 2 * di:2 * di + 2 * N]
    dt = jax.nn.softplus(proj[:, 2 * di + 2 * N:] + c["dt_bias"])
    u = jnp.concatenate([xr, bc], axis=-1)                     # [S, di + 2N]
    up = jnp.concatenate([jnp.zeros((W - 1, u.shape[1])), u], axis=0)
    conv = sum(up[i:i + S] * c["conv_w"][i] for i in range(W)) + c["conv_b"]
    conv = jax.nn.silu(conv)
    xs = conv[:, :di].reshape(S, H, P)
    B, C = conv[:, di:di + N], conv[:, di + N:]
    y = ssd(xs, dt, -jnp.exp(c["a_log"]), B, C)
    y = y + xs * c["d_skip"][None, :, None]
    y = rmsnorm(y.reshape(S, di), c["norm_scale"], cfg.rms_eps) * jax.nn.silu(z)
    return x + mm(y, c["out_proj"], weights)


def seq_loss_sum(cfg, p32, tokens, weights="float32"):
    """Summed next-token cross-entropy of one sequence [S + 1]."""
    inp, lab = tokens[:-1], tokens[1:]
    x = p32["embed"]["tok"][inp]

    @jax.checkpoint
    def body(x, lp):
        return block(cfg, lp, x, weights), None

    x, _ = jax.lax.scan(body, x, _layers(p32))
    x = rmsnorm(x, p32["final_norm"]["scale"], cfg.rms_eps)
    logits = mm(x, p32["embed"]["tok"][:cfg.vocab_size].T, weights)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, lab[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - gold)


@functools.partial(jax.jit, static_argnums=(0, 3))
def loss_and_grads(cfg, params, tokens, weights="float32"):
    """Mean loss over a batch [B, S + 1] and its gradients with respect to
    the stored parameters, one sequence at a time."""
    def lossfn(p32, row):
        return seq_loss_sum(cfg, p32, row, weights)

    vg = jax.value_and_grad(lossfn)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    zero = jax.tree.map(jnp.zeros_like, p32)

    def acc(carry, row):
        tot, g = carry
        l, gr = vg(p32, row)
        return (tot + l, jax.tree.map(jnp.add, g, gr)), None

    (tot, g), _ = jax.lax.scan(acc, (jnp.zeros(()), zero), tokens)
    n = tokens.shape[0] * (tokens.shape[1] - 1)
    return tot / n, jax.tree.map(lambda a: a / n, g)


def lr_at(opt, step: int) -> float:
    """Linear warm-up over ``warmup_steps`` then cosine decay to
    ``total_steps``, as the run configuration states."""
    warm = min(1.0, (step + 1) / max(opt["warmup_steps"], 1))
    prog = np.clip((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0, 1)
    return opt["learning_rate"] * warm * 0.5 * (1 + np.cos(np.pi * prog))


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def adamw(b1, b2, weight_decay, grad_clip, params, grads, m, v, count, lr):
    """One AdamW update; returns (params in their stored dtype, m, v,
    the gradient as the update used it, after clipping)."""
    eps = 1e-8
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, grad_clip / jnp.maximum(gn, 1e-9))
    g = jax.tree.map(lambda a: a * scale, grads)
    t = count + 1.0
    m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)

    def upd(p, mm, vv):
        step = (mm / (1 - b1 ** t)) / (jnp.sqrt(vv / (1 - b2 ** t)) + eps)
        if p.ndim >= 2:
            step = step + weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * step).astype(p.dtype)

    return jax.tree.map(upd, params, m, v), m, v, g


def follow(cfg, opt, params, batches, weights="float32", grad_rows=None):
    """Three (or ``len(batches)``) training steps from ``params``: returns
    the losses, the first clipped gradient, and the parameters after the
    last step (all on the host).  ``grad_rows`` takes each step's gradient
    from those rows alone while the loss stays the whole batch's: one data
    rank updating without the gradient exchange (a planted fault)."""
    hyper = (opt["beta1"], opt["beta2"], opt["weight_decay"], opt["grad_clip"])
    m = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    v = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    losses, g_first = [], None
    with jax.default_matmul_precision("highest"):
        for i, b in enumerate(batches):
            loss, g = loss_and_grads(cfg, params, jnp.asarray(b), weights)
            if grad_rows is not None:
                _, g = loss_and_grads(cfg, params, jnp.asarray(b[grad_rows]),
                                      weights)
            params, m, v, gc = adamw(*hyper, params, g, m, v, float(i),
                                     lr_at(opt, i))
            losses.append(float(loss))
            if g_first is None:
                g_first = jax.tree.map(np.asarray, gc)
    return losses, g_first, jax.tree.map(np.asarray, params)

