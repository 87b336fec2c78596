"""Fault-tolerance runtime: compression, straggler, elastic, pipeline.

Multi-device behaviours (pipeline, compressed mean, sharded solve) run in a
subprocess with XLA_FLAGS=--xla_force_host_platform_device_count=8 so the
main test process keeps its single-device view.
"""

import os
import pathlib
import subprocess
import sys
import textwrap

import numpy as np
import pytest
import jax.numpy as jnp

from repro.runtime import (
    StragglerMonitor,
    dequantize_int8,
    ef_compress,
    quantize_int8,
    remesh_plan,
    with_retries,
    bubble_fraction,
)

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


def _run_subprocess(code: str):
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": "src", "PATH": "/usr/bin:/bin:/usr/local/bin",
           "HOME": os.environ.get("HOME", ""), "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=600, env=env,
                       cwd=REPO_ROOT)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_quantize_roundtrip_error_bounded():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(128,)).astype(np.float32)) * 3.0
    q, s = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, s)) - np.asarray(x))
    assert err.max() <= float(s) * 0.5 + 1e-6


def test_error_feedback_reduces_bias():
    """With EF, the *accumulated* quantized signal tracks the true signal."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(64,)).astype(np.float32))
    e = jnp.zeros((64,), jnp.float32)
    acc_q = np.zeros(64)
    for _ in range(50):
        q, s, e = ef_compress(x, e)
        acc_q += np.asarray(dequantize_int8(q, s))
    np.testing.assert_allclose(acc_q / 50, np.asarray(x), atol=1e-3)


def test_straggler_monitor_flags_persistent_slow_host():
    mon = StragglerMonitor(threshold=1.4, patience=3)
    flagged = []
    for step in range(10):
        times = {0: 1.0, 1: 1.02, 2: 0.98, 3: 2.5}   # host 3 is slow
        flagged = mon.update(times)
    assert flagged == [3]
    # a transient blip never gets flagged
    mon2 = StragglerMonitor(threshold=1.4, patience=3)
    for step in range(10):
        times = {0: 1.0, 1: 1.0, 2: 3.0 if step == 4 else 1.0}
        out = mon2.update(times)
    assert out == []


def test_with_retries():
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise RuntimeError("transient")
        return "ok"

    assert with_retries(flaky, max_retries=5, backoff_s=0.0)() == "ok"
    assert calls["n"] == 3

    def always_fails():
        raise RuntimeError("permanent")
    with pytest.raises(RuntimeError):
        with_retries(always_fails, max_retries=2, backoff_s=0.0)()


def test_remesh_plan():
    p = remesh_plan(512, model=16)
    assert p.shape == (32, 16) and p.n_used == 512
    p = remesh_plan(500, model=16)         # lost 12 devices
    assert p.shape == (31, 16) and p.n_used == 496
    assert p.utilization > 0.99
    p = remesh_plan(7, model=16)           # catastrophic loss
    assert p.n_used == 4


def test_bubble_fraction():
    assert bubble_fraction(4, 12) == pytest.approx(3 / 15)


def test_compressed_mean_multidevice():
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.runtime import make_compressed_mean, init_error_state
        mesh = jax.make_mesh((8,), ("pod",))
        rng = np.random.default_rng(0)
        g = jnp.asarray(rng.normal(size=(8, 32)).astype(np.float32))
        err = init_error_state(g)
        mean_c = make_compressed_mean(mesh, "pod")
        out, err2 = jax.jit(mean_c)(g, err)
        want = np.mean(np.asarray(g), axis=0)
        got = np.asarray(out)[0]
        np.testing.assert_allclose(got, want, atol=0.05)
        for r in range(1, 8):
            np.testing.assert_allclose(np.asarray(out)[r], got, atol=1e-6)
        print("OK")
    """)
    assert "OK" in out


def test_pipeline_multidevice_matches_sequential():
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.runtime import pipeline_run
        K, M, mb, d = 8, 16, 4, 16
        mesh = jax.make_mesh((K,), ("pp",))
        rng = np.random.default_rng(0)
        Ws = jnp.asarray(rng.normal(size=(K, d, d)).astype(np.float32) / np.sqrt(d))
        x = jnp.asarray(rng.normal(size=(M, mb, d)).astype(np.float32))
        stage = lambda W, h: jnp.tanh(h @ W)
        got = pipeline_run(mesh, "pp", stage, Ws, x)
        # sequential oracle
        h = x
        for k in range(K):
            h = jnp.tanh(h @ Ws[k])
        np.testing.assert_allclose(np.asarray(got), np.asarray(h),
                                   rtol=1e-5, atol=1e-5)
        print("OK")
    """)
    assert "OK" in out


def test_sharded_solver_multidevice():
    """The paper's batch solve distributed over 8 devices: one LHS copy per
    device, systems sharded, no result drift."""
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core import thomas_factor, thomas_solve
        mesh = jax.make_mesh((8,), ("batch",))
        rng = np.random.default_rng(0)
        n, m = 64, 512
        a = rng.uniform(-1, 1, n).astype(np.float32)
        c = rng.uniform(-1, 1, n).astype(np.float32)
        b = (np.abs(a) + np.abs(c) + 2.5).astype(np.float32)
        d = rng.normal(size=(n, m)).astype(np.float32)
        f = thomas_factor(jnp.asarray(a), jnp.asarray(b), jnp.asarray(c))
        solve = jax.shard_map(lambda fac, dd: thomas_solve(fac, dd),
                              mesh=mesh, in_specs=(P(), P(None, "batch")),
                              out_specs=P(None, "batch"), check_vma=True)
        got = jax.jit(solve)(f, jnp.asarray(d))
        want = thomas_solve(f, jnp.asarray(d))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        print("OK")
    """)
    assert "OK" in out
