"""Pallas TPU kernel for the RG-LRU linear recurrence  [arXiv:2402.19427].

h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * x_t   (per channel, diagonal)

TPU adaptation: the recurrence is *diagonal*, so there is no MXU work — this
is a VPU (vector-unit) kernel and it is memory-bound.  The Griffin paper
makes the same observation and implements the scan *sequentially* on TPU
(Appendix: "linear scan"), which beats associative-scan lowering because
the bottleneck is HBM traffic, not the O(S) dependency chain.  We follow
that design: channels map to lanes in blocks of ``BLOCK_W`` channels (a
grid axis, so a block's buffers fit the scoped VMEM at any width), sequence
blocks map to the sequential innermost grid dim with the carry h in VMEM
scratch.  Inside a block the gate terms are computed once for the whole
tile into VMEM scratch, then a ``fori_loop`` walks time steps reading one
row at a time from the refs (``pl.ds``) with pure VPU multiply-adds.
A log-space closed form (two cumsums) was rejected: cumulative decays reach
exp(+-8*L) inside a block and overflow f32 (documented trade-off).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, loga_ref, y_ref, h_scr, a_scr, b_scr):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    a = jnp.exp(loga_ref[...].astype(jnp.float32))          # [L, BW]
    a_scr[...] = a
    b_scr[...] = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) \
        * x_ref[...].astype(jnp.float32)

    def step(t, h):
        h = a_scr[pl.ds(t, 1), :] * h + b_scr[pl.ds(t, 1), :]
        y_ref[pl.ds(t, 1), :] = h.astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, a_scr.shape[0], step, h_scr[...])


# channels per grid block: at chunk 256 a block's double-buffered in/out
# tiles and its scratch take about 4 MiB of the 16 MiB scoped VMEM
BLOCK_W = 512


def rglru_scan(x, log_a, *, chunk=256, interpret=False):
    """x [G, S, W]; log_a same shape -> h [G, S, W] (f32).

    G folds batch.  W is split into blocks of ``BLOCK_W`` channels (a
    multiple of 128 for TPU lanes); a narrower W is one block.
    """
    G, S, W = x.shape
    L = min(chunk, S)
    bw = min(BLOCK_W, W)
    assert S % L == 0 and W % bw == 0
    nc, nw = S // L, W // bw
    spec = pl.BlockSpec((None, L, bw), lambda g, w, j: (g, j, w))
    y = pl.pallas_call(
        _kernel,
        grid=(G, nw, nc),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((G, S, W), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, bw), jnp.float32),
                        pltpu.VMEM((L, bw), jnp.float32),
                        pltpu.VMEM((L, bw), jnp.float32)],
        interpret=interpret,
    )(x, log_a)
    return y
