"""int8 gradient-compression tests: quantization round-trip and the ring
all-reduce vs exact psum (4 virtual devices in a subprocess)."""
import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np

from repro.train.compression import dequantize_int8, quantize_int8

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(1000).astype(np.float32)) * 3.0
    q, sc = quantize_int8(x, block=128)
    y = dequantize_int8(q, sc, x.shape)
    # blockwise symmetric int8: |err| <= scale/2 = max|block|/254
    err = np.abs(np.asarray(y - x))
    bound = np.asarray(sc).max() * 0.5 + 1e-7
    assert err.max() <= bound


def test_ring_allreduce_matches_psum():
    code = textwrap.dedent("""
        import os, json
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.train.compression import ring_allreduce_q
        mesh = jax.make_mesh((4,), ("pod",))
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((4, 317)).astype(np.float32))

        def body(xs):
            s, err = ring_allreduce_q(xs[0], "pod", 4, block=64)
            return s[None], err[None]

        f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("pod"),
                                  out_specs=P("pod"), check_vma=False))
        s, err = f(x)
        exact = np.asarray(x).sum(0)
        got = np.asarray(s)
        # every shard within a few quantization steps of the exact sum;
        # shards may differ slightly from each other (each rank keeps its
        # own unquantized accumulation of its segment — same contract as
        # prod int8 rings; periodic param sync handles the drift)
        abs_err = np.abs(got - exact[None]).max()
        cross = max(np.abs(got[i] - got[0]).max() for i in range(1, 4))
        print("RESULT " + json.dumps({
            "abs_err": float(abs_err), "cross": float(cross),
            "err_norm": float(np.abs(np.asarray(err)).max())}))
    """)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT ")][-1]
    res = json.loads(line[len("RESULT "):])
    # int8 blockwise quantization across 2(n-1) hops of ~N(0,1) segments:
    # scale ~ 3/127 per hop, ~6 quantizations -> abs error << 0.3
    assert res["abs_err"] < 0.3, res
    assert res["cross"] < 0.2, res
    # error-feedback residual is bounded by the quantization step
    assert res["err_norm"] < 0.2, res
