"""Per-arch smoke tests: reduced configs, one forward/train step on CPU,
asserting output shapes + no NaNs; prefill+decode vs full-forward consistency.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.archs import ARCHS, smoke_config
from repro.configs.base import ParallelConfig
from repro.models import model as M

PCFG = ParallelConfig(data=1, model=1, attn_impl="dense",
                      seq_shard_acts=False, fsdp=False)
KEY = jax.random.PRNGKey(0)
B, S = 2, 32


def make_batch(cfg, key, batch=B, seq=S, train=True):
    kt, kf = jax.random.split(key)
    extra = 1 if train else 0
    if cfg.family == "encdec":
        return {"frames": jax.random.normal(kf, (batch, seq, cfg.d_model),
                                            jnp.bfloat16),
                "tokens": jax.random.randint(kt, (batch, seq // 4 + extra), 0,
                                             cfg.vocab_size)}
    if cfg.family == "vlm":
        nv = cfg.n_vision_tokens
        return {"patches": jax.random.normal(kf, (batch, nv, M.VIS_EMBED_DIM),
                                             jnp.bfloat16),
                "tokens": jax.random.randint(kt, (batch, seq - nv + extra), 0,
                                             cfg.vocab_size)}
    return {"tokens": jax.random.randint(kt, (batch, seq + extra), 0,
                                         cfg.vocab_size)}


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_forward_and_grad_step(name):
    cfg = smoke_config(name)
    params = M.init_params(cfg, KEY)
    batch = make_batch(cfg, KEY)

    def loss(p):
        l, _ = M.loss_and_aux(cfg, PCFG, p, batch)
        return l

    l0, grads = jax.jit(jax.value_and_grad(loss))(params)
    assert np.isfinite(float(l0)), name
    gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                         for g in jax.tree.leaves(grads)))
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0, name
    # one SGD step lowers the loss on the same batch
    lr = 2e-2
    p2 = jax.tree.map(lambda p, g: (p.astype(jnp.float32)
                                    - lr * g.astype(jnp.float32)).astype(p.dtype),
                      params, grads)
    l1 = jax.jit(loss)(p2)
    assert float(l1) < float(l0), (name, float(l0), float(l1))


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_prefill_decode_matches_forward(name):
    """Greedy decode continuation must match teacher-forced full forward."""
    cfg = smoke_config(name)
    params = M.init_params(cfg, KEY)
    batch = make_batch(cfg, KEY, train=False)
    n_prompt = 8 if cfg.family not in ("vlm",) else 4
    toks = batch["tokens"]

    # full forward logits at each position (teacher forcing)
    full_batch = dict(batch, tokens=toks)
    enc_out = None
    if cfg.family == "encdec":
        enc_out = M.encode(cfg, PCFG, params, batch["frames"])
    x, positions, _, _ = M._embed_inputs(cfg, params, full_batch,
                                         for_decode=True)
    x, _, _ = M._run_groups(cfg, PCFG, params["groups"], M.stack_groups(cfg),
                            x, positions, enc_out=enc_out)
    from repro.models.layers import basic
    x = basic.rmsnorm(params["final_norm"], x, cfg.rms_eps)
    ref_logits = basic.unembed_logits(params["embed"], x,
                                      cfg.final_logit_softcap)

    # prefill on the prompt prefix, then decode token by token
    max_len = toks.shape[1] + (cfg.n_vision_tokens
                               if cfg.family == "vlm" else 0)
    enc_len = batch["frames"].shape[1] if cfg.family == "encdec" else 0
    cache = M.init_cache(cfg, B, max_len, enc_len=enc_len)
    pre_batch = dict(batch, tokens=toks[:, :n_prompt])
    logits, cache = M.prefill(cfg, PCFG, params, pre_batch, cache)
    off = cfg.n_vision_tokens if cfg.family == "vlm" else 0
    np.testing.assert_allclose(
        np.asarray(logits[:, 0]),
        np.asarray(ref_logits[:, off + n_prompt - 1]), rtol=0.15, atol=0.15)

    for t in range(n_prompt, min(toks.shape[1], n_prompt + 4)):
        logits, cache = M.decode_step(cfg, PCFG, params, cache, toks[:, t:t + 1])
        np.testing.assert_allclose(
            np.asarray(logits[:, 0]), np.asarray(ref_logits[:, off + t]),
            rtol=0.15, atol=0.15)


def _prefilled(cfg, params, key, n_pos, max_len):
    """A batch-of-one cache holding ``n_pos`` prefilled positions."""
    nv = cfg.n_vision_tokens if cfg.family == "vlm" else 0
    if cfg.family == "encdec":   # one encoder length for every slot
        batch = make_batch(cfg, key, batch=1, seq=4 * max_len, train=False)
        enc_len = batch["frames"].shape[1]
    else:
        batch = make_batch(cfg, key, batch=1, seq=n_pos, train=False)
        enc_len = 0
    batch["tokens"] = batch["tokens"][:, :n_pos - nv]
    cache = M.init_cache(cfg, 1, max_len, enc_len=enc_len)
    _, cache = M.prefill(cfg, PCFG, params, batch, cache)
    assert int(cache["index"][0]) == n_pos
    return cache


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_decode_at_mixed_positions_matches_each_alone(name):
    """Continuous batching: slots at different positions (the last one the
    cache holds among them) decode together as each would alone, and the
    step writes nothing but the slots' new rows."""
    cfg = smoke_config(name)
    params = M.init_params(cfg, KEY)
    max_len = 33
    positions = [12, max_len - 1, 16]   # prefill lengths the SSD chunks fit
    keys = jax.random.split(jax.random.PRNGKey(1), len(positions) + 1)
    solo = [_prefilled(cfg, params, k, n, max_len)
            for k, n in zip(keys, positions)]
    # cache leaves are [layers, slots, ...], the index [slots]
    cache = jax.tree.map(
        lambda *c: jnp.concatenate(c, axis=1 if c[0].ndim > 1 else 0), *solo)
    toks = jax.random.randint(keys[-1], (len(positions), 1), 0,
                              cfg.vocab_size)
    step = jax.jit(lambda c, t: M.decode_step(cfg, PCFG, params, c, t))
    logits, new = step(cache, toks)
    np.testing.assert_array_equal(np.asarray(new["index"]),
                                  np.asarray(positions) + 1)

    for s, c1 in enumerate(solo):
        l1, n1 = step(c1, toks[s:s + 1])
        np.testing.assert_allclose(np.asarray(logits[s]), np.asarray(l1[0]),
                                   rtol=1e-4, atol=1e-4)
        for got, want in zip(jax.tree.leaves(new["groups"]),
                             jax.tree.leaves(n1["groups"])):
            np.testing.assert_allclose(
                np.asarray(got[:, s], np.float32),
                np.asarray(want[:, 0], np.float32), rtol=1e-2, atol=1e-2)

    written = np.zeros((len(positions), max_len), bool)
    written[np.arange(len(positions)), positions] = True
    for gi, group in enumerate(new["groups"]):
        for key, entry in group.items():
            kind = key.split(".", 1)[1]
            old = cache["groups"][gi][key]
            if kind in ("attn", "attn_local"):
                for leaf in ("k", "v"):
                    a, b = np.asarray(entry[leaf]), np.asarray(old[leaf])
                    np.testing.assert_array_equal(a[:, ~written],
                                                  b[:, ~written])
                    assert not np.array_equal(a[:, written], b[:, written])
            elif kind == "cross_attn":
                for leaf in ("k", "v"):
                    np.testing.assert_array_equal(np.asarray(entry[leaf]),
                                                  np.asarray(old[leaf]))


def test_count_params_matches_tree():
    for name in ARCHS:
        cfg = smoke_config(name)
        tree = M.abstract_params(cfg)
        n_tree = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))
        assert M.count_params(cfg) == n_tree
        if cfg.moe:
            assert M.count_params(cfg, active_only=True) < n_tree


def test_sub_quadratic_flags():
    assert ARCHS["mamba2-370m"].sub_quadratic
    assert ARCHS["recurrentgemma-9b"].sub_quadratic
    for n in ("gemma2-27b", "gemma2-9b", "llama3-405b", "minitron-8b",
              "granite-moe-1b-a400m", "whisper-tiny", "internvl2-26b"):
        assert not ARCHS[n].sub_quadratic, n


@pytest.mark.parametrize("impl", ["pallas", "splash", ""])
def test_unimplemented_attn_impl_raises(impl):
    """Only the dense and pure-JAX flash paths exist; any other name must
    fail at configuration time instead of silently running one of them."""
    with pytest.raises(NotImplementedError, match="attn_impl"):
        ParallelConfig(data=1, model=1, attn_impl=impl)


def test_ssd_chunked_grads_finite_under_steep_decay():
    """Across a 256-step chunk the summed log-decay reaches -700: the
    masked (upper) half of the decay matrix then holds exp(+700).  It must
    be masked before the exp, or its zero cotangent times inf is a NaN
    gradient (mamba2-370m's chunk and initial decay do this)."""
    from repro.models.layers.ssd import ssd_chunked
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (1, 512, 2, 8))
    Bm = jax.random.normal(ks[1], (1, 512, 1, 16))
    Cm = jax.random.normal(ks[2], (1, 512, 1, 16))
    dt = jnp.ones((1, 512, 2))
    a_log = jnp.ones((2,))                  # a = -e per step

    def loss(x, dt, a_log):
        return ssd_chunked(x, dt, a_log, Bm, Cm, 256)[0].sum()

    grads = jax.grad(loss, argnums=(0, 1, 2))(x, dt, a_log)
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()
