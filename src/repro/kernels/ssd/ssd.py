"""Pallas TPU kernel for the Mamba-2 SSD chunked scan  [arXiv:2405.21060].

TPU adaptation: the SSD algorithm decomposes into (a) an intra-chunk
quadratic term — two MXU matmuls per chunk tile — and (b) a sequential
inter-chunk state recurrence.  The kernel grid is (B*H, n_chunks); the
chunk axis is the innermost (sequential on TPU), so the running state
[N, P] lives in VMEM scratch across chunk iterations, exactly like the
flash-attention accumulator.  CUDA implementations spread the recurrence
over thread blocks with global-memory handoffs; on TPU the sequential grid
+ persistent VMEM scratch is the natural (and faster) shape.

Per (b, h) the kernel consumes blocks x [L, P], dt [1, L] (lane-dense row),
B/C [L, N] and emits y [L, P]; heads are independent (n_groups=1 is
broadcast by ops.py).  The per-head decay rate ``a = -exp(a_log)`` is a
scalar read from SMEM.  The in-chunk prefix sum of ``dt * a`` is a masked
lane/sublane reduction over an [L, L] tile (Mosaic has no cumsum), which
yields it both as a column (rows of C and x) and as a row (columns of the
score tile) without a transpose.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, y_ref, st_scr):
    g, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        st_scr[...] = jnp.zeros_like(st_scr)

    x = x_ref[...].astype(jnp.float32)        # [L, P]
    dt = dt_ref[...].astype(jnp.float32)      # [1, L]
    Bm = b_ref[...].astype(jnp.float32)       # [L, N]
    Cm = c_ref[...].astype(jnp.float32)       # [L, N]
    dA = dt * a_ref[g]                        # [1, L] log-decay per step

    L = x.shape[0]
    li = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    si = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = li >= si
    # seg[l] = sum_{t<=l} dA[t], as a column (lane reduce over the lower
    # triangle) and as a row (sublane reduce over the upper triangle)
    dA_col = jnp.sum(jnp.where(li == si, dA, 0.0), axis=1, keepdims=True)
    seg_col = jnp.sum(jnp.where(causal, dA, 0.0), axis=1, keepdims=True)
    seg_row = jnp.sum(jnp.where(li <= si, dA_col, 0.0), axis=0,
                      keepdims=True)
    total = jnp.sum(dA, axis=1, keepdims=True)          # [1, 1]

    # intra-chunk: w[l, s] = (C_l . B_s) * exp(seg_l - seg_s) * dt_s, s <= l
    scores = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    dec = jnp.exp(jnp.where(causal, seg_col - seg_row, -jnp.inf))
    w = scores * dec * dt
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y += (C exp(seg)) @ state_in ;  state [N, P]
    y += jax.lax.dot_general(Cm * jnp.exp(seg_col), st_scr[...],
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    # state' = exp(total) * state + sum_s dt_s exp(total - seg_s) B_s^T x_s
    dt_col = jnp.sum(jnp.where(li == si, dt, 0.0), axis=1, keepdims=True)
    xw = x * (dt_col * jnp.exp(total - seg_col))        # [L, P]
    new_state = jax.lax.dot_general(Bm, xw, (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    st_scr[...] = st_scr[...] * jnp.exp(total) + new_state
    y_ref[...] = y.astype(y_ref.dtype)


def ssd_scan(x, dt, a_log, Bm, Cm, *, chunk=128, interpret=False):
    """x [G, S, P]; dt [G, S]; a_log [G]; Bm/Cm [G, S, N] -> y [G, S, P].

    G = batch*heads (ops.py folds + broadcasts groups).
    """
    G, S, P = x.shape
    N = Bm.shape[-1]
    L = min(chunk, S)
    assert S % L == 0
    nc = S // L
    a = -jnp.exp(a_log.astype(jnp.float32))             # [G] decay rates
    y = pl.pallas_call(
        _kernel,
        grid=(G, nc),
        in_specs=[
            pl.BlockSpec((None, L, P), lambda g, j: (g, j, 0)),
            pl.BlockSpec((None, 1, L), lambda g, j: (g, 0, j)),
            pl.BlockSpec((None, L, N), lambda g, j: (g, j, 0)),
            pl.BlockSpec((None, L, N), lambda g, j: (g, j, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((None, L, P), lambda g, j: (g, j, 0)),
        out_shape=jax.ShapeDtypeStruct((G, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(x, dt[:, None, :], Bm, Cm, a)
    return y
