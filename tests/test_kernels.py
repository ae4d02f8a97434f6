"""Pallas kernel validation (interpret=True on CPU) against ref.py oracles.

Shape/dtype sweeps via hypothesis; gradients of the flash kernel wrapper
checked against the dense oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")   # property tests need it; skip cleanly if absent
from hypothesis import given, settings, strategies as st

from repro.configs.base import AttnConfig
from repro.kernels.decode_attention import decode_attention as da
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.rglru import ops as lru_ops
from repro.kernels.rglru import ref as lru_ref
from repro.kernels.ssd import ops as ssd_ops
from repro.kernels.ssd import ref as ssd_ref

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@settings(max_examples=12, deadline=None)
@given(
    b=st.sampled_from([1, 2]),
    s=st.sampled_from([64, 128, 256]),
    kh=st.sampled_from([1, 2, 4]),
    rep=st.sampled_from([1, 2, 4]),
    hd=st.sampled_from([16, 32]),
    causal=st.booleans(),
    window=st.sampled_from([None, 32, 64]),
    softcap=st.sampled_from([None, 30.0]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_flash_kernel_sweep(b, s, kh, rep, hd, causal, window, softcap,
                            dtype):
    cfg = AttnConfig(causal=causal, window=window, logit_softcap=softcap)
    H = kh * rep
    ks = jax.random.split(jax.random.PRNGKey(b * s + H), 3)
    q = rand(ks[0], (b, s, H, hd), dtype)
    k = rand(ks[1], (b, s, kh, hd), dtype)
    v = rand(ks[2], (b, s, kh, hd), dtype)
    ref = fa_ref.reference(q.astype(jnp.float32), k.astype(jnp.float32),
                           v.astype(jnp.float32), cfg)
    out = fa_ops.attention(q, k, v, cfg, q_chunk=32, kv_chunk=32,
                           interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype] * 10)


def test_flash_kernel_grad_matches_dense():
    cfg = AttnConfig(causal=True, window=64, logit_softcap=50.0)
    B, S, H, K, hd = 2, 128, 4, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = rand(ks[0], (B, S, H, hd), jnp.float32)
    k = rand(ks[1], (B, S, K, hd), jnp.float32)
    v = rand(ks[2], (B, S, K, hd), jnp.float32)
    f_k = lambda *a: (fa_ops.attention(*a, cfg, 32, 32, True) ** 2).sum()
    f_r = lambda *a: (fa_ref.reference(*a, cfg) ** 2).sum()
    gk = jax.grad(f_k, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_r, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   rtol=1e-3)


# ---------------------------------------------------------------------------
# SSD
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(
    b=st.sampled_from([1, 2]),
    s=st.sampled_from([32, 64, 128]),
    h=st.sampled_from([2, 4]),
    p=st.sampled_from([8, 16]),
    g=st.sampled_from([1, 2]),
    n=st.sampled_from([8, 16]),
    chunk=st.sampled_from([16, 32]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_ssd_kernel_sweep(b, s, h, p, g, n, chunk, dtype):
    if h % g:
        g = 1
    ks = jax.random.split(jax.random.PRNGKey(s + h + p), 5)
    x = rand(ks[0], (b, s, h, p), dtype)
    dt = jax.nn.softplus(rand(ks[1], (b, s, h), jnp.float32))
    a_log = rand(ks[2], (h,), jnp.float32) * 0.5
    Bm = rand(ks[3], (b, s, g, n), dtype) * 0.3
    Cm = rand(ks[4], (b, s, g, n), dtype) * 0.3
    ref = ssd_ref.reference(x.astype(jnp.float32), dt, a_log,
                            Bm.astype(jnp.float32),
                            Cm.astype(jnp.float32), chunk=chunk)
    out = ssd_ops.ssd_mixer(x, dt, a_log, Bm, Cm, chunk=chunk,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=max(TOL[dtype], 1e-4),
                               rtol=TOL[dtype] * 10)


def test_ssd_kernel_state_continuity_across_chunks():
    """Different chunk sizes must give identical results (state handoff)."""
    ks = jax.random.split(jax.random.PRNGKey(11), 5)
    b, s, h, p, g, n = 1, 128, 2, 8, 1, 16
    x = rand(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(rand(ks[1], (b, s, h), jnp.float32))
    a_log = rand(ks[2], (h,), jnp.float32) * 0.5
    Bm = rand(ks[3], (b, s, g, n), jnp.float32) * 0.3
    Cm = rand(ks[4], (b, s, g, n), jnp.float32) * 0.3
    o16 = ssd_ops.ssd_mixer(x, dt, a_log, Bm, Cm, chunk=16, interpret=True)
    o64 = ssd_ops.ssd_mixer(x, dt, a_log, Bm, Cm, chunk=64, interpret=True)
    np.testing.assert_allclose(np.asarray(o16), np.asarray(o64), atol=2e-5,
                               rtol=1e-4)


# ---------------------------------------------------------------------------
# RG-LRU
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(
    b=st.sampled_from([1, 2]),
    s=st.sampled_from([32, 96, 256]),
    w=st.sampled_from([8, 16, 64]),
    chunk=st.sampled_from([16, 32]),
    steep=st.floats(0.5, 8.0),
)
def test_rglru_kernel_sweep(b, s, w, chunk, steep):
    if s % chunk:
        chunk = 16
    ks = jax.random.split(jax.random.PRNGKey(s + w), 2)
    x = rand(ks[0], (b, s, w), jnp.float32)
    log_a = -jax.nn.softplus(rand(ks[1], (b, s, w), jnp.float32) * steep)
    ref = lru_ref.reference(x, log_a)
    out = lru_ops.rglru_mixer(x, log_a, chunk=chunk, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4,
                               rtol=1e-3)


def test_rglru_kernel_steep_decay_no_overflow():
    """Steep decays overflowed the rejected closed-form variant; the
    sequential kernel must stay finite and exact."""
    b, s, w = 1, 512, 8
    x = jnp.ones((b, s, w))
    log_a = jnp.full((b, s, w), -8.0)       # decay ~ e^-8 per step
    out = lru_ops.rglru_mixer(x, log_a, chunk=256, interpret=True)
    assert np.isfinite(np.asarray(out)).all()
    ref = lru_ref.reference(x, log_a)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_rglru_kernel_channel_blocks():
    """Splitting W over the grid's channel-block axis changes nothing: each
    block carries its own slice of the state."""
    from repro.kernels.rglru.rglru import BLOCK_W, rglru_scan
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    x = rand(ks[0], (2, 64, 2 * BLOCK_W), jnp.float32)
    log_a = -jax.nn.softplus(rand(ks[1], (2, 64, 2 * BLOCK_W), jnp.float32))
    out = rglru_scan(x, log_a, chunk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(lru_ref.reference(x, log_a)),
                               atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------------------
# decode attention over one layer of a stacked cache
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_blocks", [1, 4])
@pytest.mark.parametrize("window,softcap", [(None, None), (16, 30.0)])
def test_stacked_decode_kernel_matches_sliced_layer(n_blocks, window,
                                                    softcap, monkeypatch):
    """Each layer of the stack, read in place, against XLA's decode
    attention over that layer sliced out; slots at different lengths, one
    past the cache's end (attends every position)."""
    from repro.models.layers.attention import (decode_attention_local,
                                               finalize_decode)
    L, B, S, K, rep, hd = 3, 4, 64, 2, 3, 128
    monkeypatch.setattr(da, "BLOCK_BYTES", S // n_blocks * K * hd * 2)
    cfg = AttnConfig(window=window, logit_softcap=softcap)
    ks = jax.random.split(jax.random.PRNGKey(n_blocks), 3)
    q = rand(ks[0], (B, 1, K * rep, hd), jnp.bfloat16)
    kc = rand(ks[1], (L, B, S, K, hd), jnp.bfloat16)
    vc = rand(ks[2], (L, B, S, K, hd), jnp.bfloat16)
    valid = jnp.array([1, 23, S, S + 1])
    for layer in range(L):
        ref = finalize_decode(*decode_attention_local(
            q, kc[layer], vc[layer], valid, cfg))
        out = da.stacked_decode_attention(
            q, kc, vc, jnp.int32(layer), valid, scale=1 / np.sqrt(hd),
            window=window, softcap=softcap, interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=TOL[jnp.bfloat16],
                                   rtol=TOL[jnp.bfloat16])
