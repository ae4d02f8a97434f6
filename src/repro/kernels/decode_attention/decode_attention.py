"""Pallas TPU decode attention over one layer of a stacked KV cache, read
in place.

The decode scan carries each group's cache stacked [L, B, S, K, hd]
(models/model.py).  Sliced with XLA, layer ``l`` is first copied out of
the stack: a read and a write of a whole layer's K and V in every layer of
every step.  Here the layer index is a scalar-prefetch operand of the
BlockSpecs, so each block is read straight from the stack into VMEM.

Grid: (B, S / cs); one program instance takes one slot's H query heads
against cs positions; the online-softmax state (m, l, acc) persists
across the sequential position axis.  A position's K kv-heads are
contiguous, so a block is [cs*K, hd] (row = s*K + kv) and all H heads
multiply it at once; products across heads (kv != h // R) are masked out.
That is K times the MXU work of the grouped product for the same bytes;
decode attention is bound by the bytes.

Numerics follow models/layers/attention.decode_attention_local: scores
rounded to the cache dtype, then scaled in f32; probabilities cast to the
cache dtype for the value product; the numerator rounded to the cache
dtype once, at the end.  With one position block (cs == S) the arithmetic
is the same; with more, the online softmax rescales in between.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
import numpy as np

NEG_INF = -0.7 * float(np.finfo(np.float32).max)
BLOCK_BYTES = 4 << 20           # one K or V block in VMEM
VMEM_LIMIT = 64 << 20           # two buffers each of K and V, and scores


def _kernel(layer_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
            acc_scr, *, scale, window, softcap, cs, ns, kv, rep):
    del layer_ref                 # used by the index maps only
    b, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...]                # [H, hd]
    k = k_ref[...]                # [cs*K, hd]
    v = v_ref[...]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s.astype(k.dtype).astype(jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    # column c holds kv-head c % K of position c // K; row h is query
    # head h, of kv-head h // R (built small, then broadcast)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, s.shape[1]), 1)
    group = jax.lax.broadcasted_iota(jnp.int32, (s.shape[0], 1), 0) // rep
    pos = j * cs + col // kv
    n = len_ref[b]
    ok = (col % kv == group) & (pos < n)
    if window is not None:
        ok &= pos >= n - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]           # [H, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == ns - 1)
    def _finalize():
        num = acc_scr[...].astype(v.dtype).astype(jnp.float32)
        o_ref[...] = num / jnp.maximum(l_scr[...], 1e-30)


def position_block(S, row_bytes):
    """Positions per block: the largest divisor of S whose K (or V) block
    stays within BLOCK_BYTES."""
    cap = max(1, BLOCK_BYTES // row_bytes)
    if S <= cap:
        return S
    return max(d for d in range(1, cap + 1) if S % d == 0)


def stacked_decode_attention(q, k_stack, v_stack, layer, valid_len, *, scale,
                             window=None, softcap=None, interpret=False):
    """q [B, 1, H, hd]; k/v_stack [L, B, S, K, hd]; layer scalar;
    valid_len [B] (positions < valid_len[b] are attended, the last
    ``window`` of them if given) -> [B, 1, H, hd] f32."""
    L, B, S, K, hd = k_stack.shape
    H = q.shape[2]
    cs = position_block(S, K * hd * k_stack.dtype.itemsize)
    ns = S // cs
    # free reshapes: a position's K rows of hd are contiguous
    kf = k_stack.reshape(L, B, S * K, hd)
    vf = v_stack.reshape(L, B, S * K, hd)
    kernel = functools.partial(_kernel, scale=scale, window=window,
                               softcap=softcap, cs=cs, ns=ns, kv=K,
                               rep=H // K)
    kv_spec = pl.BlockSpec((None, None, cs * K, hd),
                           lambda b, j, l, n: (l[0], b, j, 0))
    head_spec = pl.BlockSpec((None, H, hd), lambda b, j, l, n: (b, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, ns),
            in_specs=[head_spec, kv_spec, kv_spec],
            out_specs=head_spec,
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, hd), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      valid_len.astype(jnp.int32), q.reshape(B, H, hd), kf, vf)
    return out.reshape(B, 1, H, hd)
