"""Mamba-2 SSD (state-space duality) block  [arXiv:2405.21060].

Block layout follows the Mamba-2 paper: one input projection produces
(z, x, B, C, dt); a short depthwise conv over (x, B, C); the SSD mixer; a
gated RMSNorm; and an output projection.

The SSD mixer itself is the chunked algorithm (Listing 1 of the paper):
  * intra-chunk: quadratic attention-like term with decay L-matrix,
  * inter-chunk: a sequential ``lax.scan`` over per-chunk states
    [B, H, P, N] (nheads × headdim × dstate).
Training/prefill use the chunked path; decode uses the recurrent step.
``repro.kernels.ssd`` holds the Pallas TPU version of the chunked kernel and
must match ``ssd_chunked`` (its ref.py re-exports the functions here).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import SSDConfig
from repro.models.layers.basic import _leaf, rmsnorm

A = jax.ShapeDtypeStruct


def ssd_dims(d_model, scfg: SSDConfig):
    d_inner = scfg.expand * d_model
    n_heads = d_inner // scfg.head_dim
    return d_inner, n_heads


def ssd_params(d_model, scfg: SSDConfig, dtype, key=None):
    d_inner, H = ssd_dims(d_model, scfg)
    G, N, W = scfg.n_groups, scfg.d_state, scfg.conv_width
    conv_dim = d_inner + 2 * G * N
    ks = jax.random.split(key, 7) if key is not None else (None,) * 7
    return {
        # in_proj -> [z (d_inner), x (d_inner), B (G*N), C (G*N), dt (H)]
        "in_proj": _leaf((d_model, 2 * d_inner + 2 * G * N + H), dtype, ks[0], "normal"),
        "conv_w": _leaf((W, conv_dim), dtype, ks[1], "normal"),
        "conv_b": _leaf((conv_dim,), dtype, ks[2], "zeros"),
        "a_log": _leaf((H,), jnp.float32, ks[3], "ones"),
        "dt_bias": _leaf((H,), jnp.float32, ks[4], "zeros"),
        "d_skip": _leaf((H,), jnp.float32, ks[5], "ones"),
        "norm_scale": _leaf((d_inner,), dtype, ks[6], "zeros"),
        "out_proj": _leaf((d_inner, d_model), dtype,
                          jax.random.split(ks[0])[0] if key is not None else None,
                          "normal"),
    }


def ssd_axes():
    return {"in_proj": ("embed", "inner"), "conv_w": (None, "inner"),
            "conv_b": ("inner",), "a_log": ("ssm_heads",),
            "dt_bias": ("ssm_heads",), "d_skip": ("ssm_heads",),
            "norm_scale": ("inner",), "out_proj": ("inner", "embed")}


def _split_proj(proj, d_inner, G, N, H):
    z = proj[..., :d_inner]
    x = proj[..., d_inner:2 * d_inner]
    Bm = proj[..., 2 * d_inner:2 * d_inner + G * N]
    Cm = proj[..., 2 * d_inner + G * N:2 * d_inner + 2 * G * N]
    dt = proj[..., 2 * d_inner + 2 * G * N:]
    return z, x, Bm, Cm, dt


def _causal_conv(x, w, b, state=None):
    """Depthwise causal conv. x [B,S,C], w [W,C]. state [B,W-1,C] for decode."""
    W = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    else:
        pad = state
    xp = jnp.concatenate([pad, x], axis=1)
    out = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(W))
    new_state = xp[:, -(W - 1):, :] if W > 1 else jnp.zeros_like(pad)
    return jax.nn.silu(out + b), new_state


def ssd_chunked(x, dt, a_log, Bm, Cm, chunk, init_state=None):
    """Chunked SSD.  x [B,S,H,P], dt [B,S,H] (post-softplus), a_log [H],
    Bm/Cm [B,S,G,N].  Returns (y [B,S,H,P], final_state [B,H,P,N])."""
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    nc = S // chunk
    a = -jnp.exp(a_log)                                     # [H] negative
    dA = dt * a                                             # [B,S,H] log-decay
    xc = x.reshape(Bsz, nc, chunk, H, P)
    dtc = dt.reshape(Bsz, nc, chunk, H)
    dAc = dA.reshape(Bsz, nc, chunk, H)
    Bc = Bm.reshape(Bsz, nc, chunk, G, N)
    Cc = Cm.reshape(Bsz, nc, chunk, G, N)

    seg = jnp.cumsum(dAc, axis=2)                           # [B,nc,L,H]
    total = seg[:, :, -1, :]                                # [B,nc,H]

    # intra-chunk (diagonal blocks): y_intra[t] = sum_{s<=t} C_t·B_s exp(seg_t-seg_s) dt_s x_s
    Cg = Cc.reshape(Bsz, nc, chunk, G, 1, N)
    Bg = Bc.reshape(Bsz, nc, chunk, G, 1, N)
    scores = jnp.einsum("bclgrn,bcsgrn->bcglrs",
                        jnp.broadcast_to(Cg, (Bsz, nc, chunk, G, rep, N)),
                        jnp.broadcast_to(Bg, (Bsz, nc, chunk, G, rep, N)),
                        preferred_element_type=jnp.float32)  # [B,nc,G,l,rep,s]
    # decay L matrix per head: L[l,s] = exp(seg[l] - seg[s]), causal-masked
    segh = seg.reshape(Bsz, nc, chunk, G, rep)
    segl = segh.transpose(0, 1, 3, 4, 2)                    # [B,nc,G,rep,L]
    dmat = segl[..., :, None] - segl[..., None, :]          # [B,nc,G,rep,l,s]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    # mask before the exp: above the diagonal dmat is positive and can
    # overflow, and exp's gradient there would be 0 * inf = NaN
    lmat = jnp.exp(jnp.where(causal, dmat, -jnp.inf))
    dtl = dtc.reshape(Bsz, nc, chunk, G, rep).transpose(0, 1, 3, 4, 2)
    sc = scores.transpose(0, 1, 2, 4, 3, 5)                 # [B,nc,G,rep,l,s]
    w = sc * lmat * dtl[..., None, :]
    xh = xc.reshape(Bsz, nc, chunk, G, rep, P)
    y_intra = jnp.einsum("bcgrls,bcsgrp->bclgrp", w.astype(x.dtype), xh)

    # per-chunk input state: state_c = sum_s exp(total - seg_s) dt_s B_s x_s
    decay_in = jnp.exp(total[:, :, None, :] - seg)          # [B,nc,L,H]
    contrib = (dtc * decay_in).reshape(Bsz, nc, chunk, G, rep)
    states = jnp.einsum("bcsgr,bcsgn,bcsgrp->bcgrpn", contrib,
                        Bc, xh, preferred_element_type=jnp.float32)

    # inter-chunk recurrence over chunk states
    def step(carry, inp):
        st_in, tot = inp                                    # [B,G,rep,P,N], [B,H]
        toth = jnp.exp(tot).reshape(Bsz, G, rep)[..., None, None]
        new = carry * toth + st_in
        return new, carry                                   # emit state *before* chunk

    init = (jnp.zeros((Bsz, G, rep, P, N), jnp.float32) if init_state is None
            else init_state.reshape(Bsz, G, rep, P, N).astype(jnp.float32))
    final, prev_states = jax.lax.scan(
        step, init, (states.transpose(1, 0, 2, 3, 4, 5), total.transpose(1, 0, 2)))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4, 5)   # [B,nc,G,rep,P,N]

    # inter-chunk output: y_inter[t] = C_t · (exp(seg_t) * state_prev)
    outdec = jnp.exp(seg).reshape(Bsz, nc, chunk, G, rep)
    y_inter = jnp.einsum("bclgn,bcgrpn,bclgr->bclgrp", Cc,
                         prev_states.astype(jnp.float32), outdec)
    y = (y_intra.astype(jnp.float32) + y_inter).reshape(Bsz, S, H, P)
    return y.astype(x.dtype), final.reshape(Bsz, H, P, N)


def ssd_recurrent_step(state, xt, dtt, a_log, Bt, Ct):
    """One decode step. state [B,H,P,N]; xt [B,H,P]; dtt [B,H];
    Bt/Ct [B,G,N] -> (y [B,H,P], new_state)."""
    Bsz, H, P, N = state.shape
    G = Bt.shape[1]
    rep = H // G
    a = -jnp.exp(a_log)
    dA = jnp.exp(dtt * a)                                    # [B,H]
    Bh = jnp.repeat(Bt, rep, axis=1)                         # [B,H,N]
    Ch = jnp.repeat(Ct, rep, axis=1)
    upd = jnp.einsum("bh,bhp,bhn->bhpn", dtt, xt.astype(jnp.float32),
                     Bh.astype(jnp.float32))
    new = state * dA[..., None, None] + upd
    y = jnp.einsum("bhpn,bhn->bhp", new, Ch.astype(jnp.float32))
    return y.astype(xt.dtype), new


def ssd_block(p, x, scfg: SSDConfig, d_model, state=None, conv_state=None,
              rms_eps=1e-6):
    """Full Mamba-2 block.  x [B,S,D].

    Train/prefill: state/conv_state None -> chunked path, returns (y, None).
    Decode: S==1 with states -> recurrent path, returns (y, (state, conv)).
    """
    d_inner, H = ssd_dims(d_model, scfg)
    G, N, P = scfg.n_groups, scfg.d_state, scfg.head_dim
    proj = x @ p["in_proj"]
    z, xr, Bm, Cm, dt = _split_proj(proj, d_inner, G, N, H)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    conv_in = jnp.concatenate([xr, Bm, Cm], axis=-1)
    conv_out, new_conv = _causal_conv(conv_in, p["conv_w"], p["conv_b"], conv_state)
    xr, Bm, Cm = (conv_out[..., :d_inner],
                  conv_out[..., d_inner:d_inner + G * N],
                  conv_out[..., d_inner + G * N:])
    Bsz, S = x.shape[0], x.shape[1]
    xh = xr.reshape(Bsz, S, H, P)
    Bm = Bm.reshape(Bsz, S, G, N)
    Cm = Cm.reshape(Bsz, S, G, N)
    if state is None:
        chunk = min(scfg.chunk_size, S)
        y, fin = ssd_chunked(xh, dt, p["a_log"], Bm, Cm, chunk)
        new_state = fin
    else:
        y, new_state = ssd_recurrent_step(
            state, xh[:, 0], dt[:, 0], p["a_log"], Bm[:, 0], Cm[:, 0])
        y = y[:, None]
    y = y + (xh.astype(jnp.float32)
             * p["d_skip"][None, None, :, None]).astype(y.dtype)
    y = y.reshape(Bsz, S, d_inner)
    y = rmsnorm({"scale": p["norm_scale"]}, y, rms_eps) * jax.nn.silu(z)
    out = y @ p["out_proj"]
    return out.astype(x.dtype), (new_state, new_conv)


def ssd_init_state(batch, d_model, scfg: SSDConfig, dtype=jnp.float32,
                   abstract=False):
    d_inner, H = ssd_dims(d_model, scfg)
    conv_dim = d_inner + 2 * scfg.n_groups * scfg.d_state
    shapes = {
        "state": (batch, H, scfg.head_dim, scfg.d_state),
        "conv": (batch, scfg.conv_width - 1, conv_dim),
    }
    if abstract:
        return {"state": A(shapes["state"], jnp.float32),
                "conv": A(shapes["conv"], dtype)}
    return {"state": jnp.zeros(shapes["state"], jnp.float32),
            "conv": jnp.zeros(shapes["conv"], dtype)}
