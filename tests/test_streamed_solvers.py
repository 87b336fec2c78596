"""HBM-streamed (split-N) Pallas solvers + the PR-3 bug regressions.

Covers:
  * regression — jitted uniform-mode penta solve on the pallas backend
    (``float(f.eps[2])`` on a traced leaf used to raise
    ``ConcretizationTypeError``), including inside ``lax.scan``;
  * regression — dead padded lanes in the batch-mode kernels factor as
    identity rows, so the whole padded kernel output is finite and the
    solves run clean under ``jax_debug_nans``;
  * streamed kernels == resident kernels bit-for-bit at small N (same
    arithmetic, chunked), across ragged N/M and both bandwidths;
  * streamed solve == reference at an N where the resident ``supports()``
    used to return False, for tridiag + penta, Dirichlet + periodic, under
    jit / vmap / grad (the adjoint reuses the same stored factor);
  * the 2-D ``(block_m, block_n)`` auto-tune policy and the honest
    streamed HBM-traffic model.
"""

import contextlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import common as kcommon
from repro.kernels import ops as kops
from repro.solver import BandedSystem, factorize, plan, solve
from repro.solver import pallas as solver_pallas

# the smallest N whose RESIDENT tridiag/penta constant working set exceeds
# the 12 MiB budget even at block_m=128 (and a multiple of the streamed
# chunk candidates, so the parity runs exercise >= 6 chunks)
BIG_N = 12288


def _tridiag_coeffs(rng, n):
    a = rng.uniform(-1, 1, n).astype(np.float32)
    c = rng.uniform(-1, 1, n).astype(np.float32)
    b = (np.abs(a) + np.abs(c) + 2.5).astype(np.float32)
    return a, b, c


def _penta_coeffs(rng, n):
    a = rng.uniform(-1, 1, n).astype(np.float32)
    b = rng.uniform(-1, 1, n).astype(np.float32)
    d = rng.uniform(-1, 1, n).astype(np.float32)
    e = rng.uniform(-1, 1, n).astype(np.float32)
    c = (np.abs(a) + np.abs(b) + np.abs(d) + np.abs(e) + 4.0).astype(np.float32)
    return a, b, c, d, e


def _uniform_penta_coeffs(n, s=0.11):
    one = np.ones(n, np.float32)
    return s * one, -4 * s * one, (1 + 6 * s) * one, -4 * s * one, s * one


@contextlib.contextmanager
def _debug_nans():
    jax.config.update("jax_debug_nans", True)
    try:
        yield
    finally:
        jax.config.update("jax_debug_nans", False)


# ---------------------------------------------------------------------------
# Regression: traced eps must not be concretised (jit-breaking bug)
# ---------------------------------------------------------------------------

def test_jitted_uniform_penta_pallas_solve():
    """jax.jit(solve) on a uniform-mode penta Factorization (pallas) used to
    raise ConcretizationTypeError via float(f.eps[2])."""
    n, m = 64, 96
    system = BandedSystem.penta(*_uniform_penta_coeffs(n), mode="uniform")
    fact = factorize(system, backend="pallas")
    rng = np.random.default_rng(0)
    rhs = jnp.asarray(rng.normal(size=(n, m)).astype(np.float32))

    got = jax.jit(solve)(fact, rhs)        # must trace, not concretise
    want = solve(factorize(system, backend="reference"), rhs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_uniform_penta_pallas_solve_inside_scan():
    """The lax.scan PDE-loop shape over the same path (fact closed over)."""
    n, m = 64, 32
    system = BandedSystem.penta(*_uniform_penta_coeffs(n), mode="uniform")
    fact = factorize(system, backend="pallas")
    rng = np.random.default_rng(1)
    c0 = jnp.asarray(rng.normal(size=(n, m)).astype(np.float32))

    def body(c, _):
        return solve(fact, c), None

    out, _ = jax.lax.scan(body, c0, None, length=3)
    assert np.isfinite(np.asarray(out)).all()


# ---------------------------------------------------------------------------
# Regression: dead padded lanes must not compute 1/0 (NaN hygiene)
# ---------------------------------------------------------------------------

def test_batch_kernel_dead_lanes_are_finite():
    """M=96 at block_m=128 leaves 32 dead lanes; the zero pad used to put a
    0 main diagonal there -> 1/0 -> inf/NaN across every padded sweep row.
    pad_lanes(identity=True) pads the main diagonal with 1 instead."""
    rng = np.random.default_rng(2)
    n, m = 16, 96
    a, b, c = (rng.uniform(-1, 1, (n, m)).astype(np.float32) * 0.1
               for _ in range(3))
    b = (np.abs(a) + np.abs(c) + 2.0).astype(np.float32)
    d = rng.normal(size=(n, m)).astype(np.float32)

    with _debug_nans():
        x = kops.thomas_batch(*map(jnp.asarray, (a, b, c, d)),
                              block_m=128, interpret=True)
    assert x.shape == (n, m) and np.isfinite(np.asarray(x)).all()

    pa, pb, pc, pd, pe = _penta_coeffs(rng, n)
    tile = lambda v: np.broadcast_to(v[:, None], (n, m)).copy()
    with _debug_nans():
        x5 = kops.penta_batch(*map(jnp.asarray, (tile(pa), tile(pb), tile(pc),
                                                 tile(pd), tile(pe), d)),
                              block_m=128, interpret=True)
    assert np.isfinite(np.asarray(x5)).all()


def test_pad_lanes_identity_flag():
    x = jnp.zeros((4, 96))
    padded, m = kcommon.pad_lanes(x, 128, identity=True)
    assert m == 96 and padded.shape == (4, 128)
    assert np.asarray(padded)[:, 96:].min() == 1.0
    padded0, _ = kcommon.pad_lanes(x, 128)
    assert np.asarray(padded0)[:, 96:].max() == 0.0


# ---------------------------------------------------------------------------
# Streamed kernels: chunked == resident, bit-for-bit at small N
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m,block_n,block_m", [
    (64, 128, 16, 128),
    (100, 70, 32, 64),      # ragged N and M -> sweep + lane padding
    (33, 256, 8, 128),      # odd N
])
def test_thomas_streamed_matches_resident(n, m, block_n, block_m):
    from repro.core import thomas_factor
    rng = np.random.default_rng(n * 7 + m)
    a, b, c = _tridiag_coeffs(rng, n)
    d = jnp.asarray(rng.normal(size=(n, m)).astype(np.float32))
    f = thomas_factor(*map(jnp.asarray, (a, b, c)))
    res = kops.thomas_constant(f, d, block_m=block_m, interpret=True)
    got = kops.thomas_constant(f, d, block_m=block_m, block_n=block_n,
                               interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(res))


@pytest.mark.parametrize("uniform", [False, True])
@pytest.mark.parametrize("n,m,block_n", [(96, 200, 32), (50, 64, 16)])
def test_penta_streamed_matches_resident(uniform, n, m, block_n):
    from repro.core import penta_factor
    rng = np.random.default_rng(n + m)
    coeffs = (_uniform_penta_coeffs(n) if uniform
              else _penta_coeffs(rng, n))
    rhs = jnp.asarray(rng.normal(size=(n, m)).astype(np.float32))
    f = penta_factor(*map(jnp.asarray, coeffs))
    res = kops.penta_constant(f, rhs, interpret=True, uniform=uniform)
    got = kops.penta_constant(f, rhs, block_n=block_n, interpret=True,
                              uniform=uniform)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(res))


# ---------------------------------------------------------------------------
# The tentpole acceptance: large N runs pallas (streamed) instead of
# falling back, and matches reference under jit/vmap/grad
# ---------------------------------------------------------------------------

def _big_system(bandwidth, periodic):
    if bandwidth == 3:
        return BandedSystem.tridiag(-0.4, 1.8, -0.4, n=BIG_N,
                                    periodic=periodic)
    return BandedSystem.penta(0.11, -0.44, 1.66, -0.44, 0.11, n=BIG_N,
                              periodic=periodic)


@pytest.mark.parametrize("periodic", [False, True])
@pytest.mark.parametrize("bandwidth", [3, 5])
def test_streamed_large_n_parity_vs_reference(bandwidth, periodic):
    """At BIG_N the resident working set exceeds the budget at every
    block_m: supports() must now say True (streamed), auto must pick
    pallas, and the solve must match reference to <= 1e-5."""
    system = _big_system(bandwidth, periodic)
    assert solver_pallas.auto_block_m(system) is None   # resident: no fit
    ok, why = solver_pallas.supports(system)
    assert ok and "streamed" in why

    fact = factorize(system, backend="auto")
    assert fact.backend == "pallas"
    assert fact.meta.opt("block_n") is not None

    rng = np.random.default_rng(bandwidth + periodic)
    rhs = jnp.asarray(rng.normal(size=(BIG_N, 130)).astype(np.float32))
    got = jax.jit(solve)(fact, rhs)
    want = solve(factorize(system, backend="reference"), rhs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_streamed_solve_under_vmap():
    """Multi-LHS: vmap over stacked streamed factorizations."""
    n, m = 128, 64
    rng = np.random.default_rng(5)
    facts = []
    for seed in (0, 1):
        r = np.random.default_rng(seed)
        a, b, c = _tridiag_coeffs(r, n)
        facts.append(factorize(BandedSystem.tridiag(a, b, c),
                               backend="pallas", block_n=32))
    stacked = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *facts)
    assert stacked.meta.opt("block_n") == 32
    rhss = jnp.asarray(rng.normal(size=(2, n, m)).astype(np.float32))
    got = jax.vmap(solve)(stacked, rhss)
    for i, f in enumerate(facts):
        want = solve(f, rhss[i])
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)


def test_streamed_grad_reuses_forward_factor():
    """grad through a streamed solve: the adjoint must run the transposed
    sweeps on the SAME stored factor (reference transpose path), matching
    the reference backend's gradient."""
    n, m = 256, 32
    rng = np.random.default_rng(6)
    a, b, c = _tridiag_coeffs(rng, n)
    system = BandedSystem.tridiag(a, b, c)
    rhs = jnp.asarray(rng.normal(size=(n, m)).astype(np.float32))

    fact_s = factorize(system, backend="pallas", block_n=64)
    fact_r = factorize(system, backend="reference")
    loss = lambda f, r: jnp.sum(solve(f, r) ** 2)
    g_s = jax.grad(loss, argnums=1)(fact_s, rhs)
    g_r = jax.grad(loss, argnums=1)(fact_r, rhs)
    np.testing.assert_allclose(np.asarray(g_s), np.asarray(g_r),
                               rtol=1e-4, atol=1e-4)
    # diagonal cotangents flow too (the spec leaves carry the gradient)
    gd_s = jax.grad(lambda diags: loss(
        factorize(BandedSystem.tridiag(*diags), backend="reference"), rhs))(
            tuple(map(jnp.asarray, (a, b, c))))
    assert all(np.isfinite(np.asarray(g)).all() for g in gd_s)


def test_streamed_solve_is_nan_clean():
    """Sweep-axis zero padding must stay finite under jax_debug_nans (the
    padded factored rows compute (0 - 0*carry)*0, never 1/0)."""
    n, m = 100, 70          # pads N 100 -> 128 at block_n=32, M 70 -> 128
    rng = np.random.default_rng(7)
    a, b, c = _tridiag_coeffs(rng, n)
    fact = factorize(BandedSystem.tridiag(a, b, c), backend="pallas",
                     block_n=32)
    rhs = jnp.asarray(rng.normal(size=(n, m)).astype(np.float32))
    with _debug_nans():
        x = solve(fact, rhs)
    assert np.isfinite(np.asarray(x)).all()


# ---------------------------------------------------------------------------
# Auto-tune policy + traffic model
# ---------------------------------------------------------------------------

def test_auto_tune_prefers_resident_when_it_fits():
    system = BandedSystem.tridiag(-0.4, 1.8, -0.4, n=256)
    assert solver_pallas.auto_tune(system) == (1024, None)


def test_auto_tune_streams_explicit_oversize_block_m():
    """An (N, block_m) pair whose resident working set exceeds the budget
    resolves to a streamed pair instead of being rejected."""
    system = BandedSystem.tridiag(-0.4, 1.8, -0.4, n=8192)
    ws = kcommon.vmem_working_set(8192, 1024, 2, n_carry=1, itemsize=4)
    assert ws > kcommon.VMEM_BUDGET_BYTES
    bm, bn = solver_pallas.auto_tune(system, block_m=1024)
    assert bm == 1024 and bn is not None
    ok, why = solver_pallas.supports(system, block_m=1024)
    assert ok and "streamed" in why


def test_auto_still_falls_back_when_nothing_fits(monkeypatch):
    """A budget too small even for the smallest streamed chunk must keep
    the graceful reference fallback; batch mode now STREAMS past the wall
    (the engine spills the fused factor scratch to HBM) so only
    periodic x batch still lacks a kernel."""
    system = BandedSystem.tridiag(-0.4, 1.8, -0.4, n=64)
    monkeypatch.setattr(kcommon, "VMEM_BUDGET_BYTES", 1024)
    assert plan(system, backend="auto").backend == "reference"
    monkeypatch.undo()

    big_batch = BandedSystem.tridiag(-0.4, 1.8, -0.4, n=BIG_N * 2,
                                     mode="batch", batch=128)
    assert solver_pallas.auto_block_m(big_batch) is None  # resident: no fit
    ok, why = solver_pallas.supports(big_batch)
    assert ok and "streamed" in why

    periodic_batch = BandedSystem.tridiag(-0.4, 1.8, -0.4, n=64,
                                          mode="batch", batch=128,
                                          periodic=True)
    ok, why = solver_pallas.supports(periodic_batch)
    assert not ok and "periodic" in why


def test_streamed_traffic_model_is_honest():
    """Streamed = 2 passes: exactly one extra RHS-sized HBM round trip and
    a re-streamed LHS; still cheaper than the per-system baseline."""
    from repro.kernels.penta import hbm_traffic_bytes as pen_t
    from repro.kernels.thomas import hbm_traffic_bytes as tri_t
    n, m = 8192, 4096
    t = tri_t(n, m)
    assert t["constant_streamed"] == t["constant"] * 2
    assert t["constant"] < t["constant_streamed"] < t["batch"]
    p = pen_t(n, m)
    assert p["constant"] < p["constant_streamed"] < p["batch"]
    assert p["uniform_streamed"] < p["constant_streamed"]
    # itemsize derives from dtype (the hardcoded-4 regression)
    assert tri_t(n, m, dtype=jnp.float64)["constant"] == 2 * t["constant"]
    assert kops.solver_hbm_traffic_bytes(3, "constant", n, m,
                                         streamed=True) == t["constant_streamed"]
