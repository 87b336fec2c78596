"""The unified repro.solver front-end: backend parity (reference vs
pallas-interpret vs sharded CPU mesh), auto-selection fallback, block_m
auto-tuning, and the registry contract."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (
    penta_factor,
    penta_solve,
    periodic_penta_factor,
    periodic_penta_solve,
    periodic_thomas_factor,
    periodic_thomas_solve,
    thomas_factor,
    thomas_solve,
)
from repro.kernels import common as kcommon
from repro.solver import BandedSystem, Plan, available_backends, plan
from repro.solver import pallas as solver_pallas
from repro.solver import registry as solver_registry

N, M = 64, 96


def _tridiag_coeffs(rng, n, uniform):
    if uniform:
        s = 0.37
        one = np.ones(n, np.float32)
        return -s * one, (1 + 2 * s) * one, -s * one
    a = rng.uniform(-1, 1, n).astype(np.float32)
    c = rng.uniform(-1, 1, n).astype(np.float32)
    b = (np.abs(a) + np.abs(c) + 2.5).astype(np.float32)
    return a, b, c


def _penta_coeffs(rng, n, uniform):
    if uniform:
        s = 0.11
        one = np.ones(n, np.float32)
        return s * one, -4 * s * one, (1 + 6 * s) * one, -4 * s * one, s * one
    a = rng.uniform(-1, 1, n).astype(np.float32)
    b = rng.uniform(-1, 1, n).astype(np.float32)
    d = rng.uniform(-1, 1, n).astype(np.float32)
    e = rng.uniform(-1, 1, n).astype(np.float32)
    c = (np.abs(a) + np.abs(b) + np.abs(d) + np.abs(e) + 4.0).astype(np.float32)
    return a, b, c, d, e


def _core_reference(bandwidth, periodic, coeffs, rhs):
    """The pre-existing repro.core solve the front-end must reproduce."""
    coeffs = tuple(map(jnp.asarray, coeffs))
    if bandwidth == 3:
        if periodic:
            return periodic_thomas_solve(periodic_thomas_factor(*coeffs), rhs)
        return thomas_solve(thomas_factor(*coeffs), rhs)
    if periodic:
        return periodic_penta_solve(periodic_penta_factor(*coeffs), rhs)
    return penta_solve(penta_factor(*coeffs), rhs)


def _system(bandwidth, coeffs, periodic, mode, batch):
    ctor = BandedSystem.tridiag if bandwidth == 3 else BandedSystem.penta
    return ctor(*coeffs, n=N, periodic=periodic, mode=mode,
                batch=batch if mode == "batch" else None)


@pytest.mark.parametrize("backend", ["reference", "pallas", "sharded"])
@pytest.mark.parametrize("mode", ["constant", "uniform", "batch"])
@pytest.mark.parametrize("periodic", [False, True])
@pytest.mark.parametrize("bandwidth", [3, 5])
def test_backend_parity(bandwidth, periodic, mode, backend):
    """Every (bandwidth, periodic, mode, backend) combination matches the
    repro.core thomas_solve / penta_solve references to <= 1e-5."""
    if backend == "pallas" and periodic and mode == "batch":
        pytest.skip("no Pallas kernel for periodic per-system-LHS solves")
    rng = np.random.default_rng(bandwidth * 100 + periodic * 10)
    make = _tridiag_coeffs if bandwidth == 3 else _penta_coeffs
    coeffs = make(rng, N, uniform=(mode == "uniform"))
    rhs = jnp.asarray(rng.normal(size=(N, M)).astype(np.float32))

    p = plan(_system(bandwidth, coeffs, periodic, mode, M), backend=backend)
    assert p.backend == backend
    want = np.asarray(_core_reference(bandwidth, periodic, coeffs, rhs))
    got = np.asarray(p.solve(rhs))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["reference", "pallas", "sharded"])
def test_single_rhs_shape_preserved(backend):
    rng = np.random.default_rng(0)
    coeffs = _tridiag_coeffs(rng, N, uniform=False)
    d = jnp.asarray(rng.normal(size=N).astype(np.float32))
    p = plan(_system(3, coeffs, False, "constant", None), backend=backend)
    x = p.solve(d)
    assert x.shape == (N,)
    want = np.asarray(_core_reference(3, False, coeffs, d))
    np.testing.assert_allclose(np.asarray(x), want, rtol=1e-5, atol=1e-5)


def test_sharded_uses_cpu_mesh_and_pads_ragged_batch():
    assert jax.device_count() >= 2, "conftest should force >=2 host devices"
    rng = np.random.default_rng(1)
    coeffs = _tridiag_coeffs(rng, N, uniform=False)
    p = plan(_system(3, coeffs, True, "constant", None), backend="sharded")
    assert p.impl.n_shards == jax.device_count()
    # M = 97 is not divisible by the mesh -> exercises identity-lane padding
    rhs = jnp.asarray(rng.normal(size=(N, 97)).astype(np.float32))
    want = np.asarray(_core_reference(3, True, coeffs, rhs))
    np.testing.assert_allclose(np.asarray(p.solve(rhs)), want,
                               rtol=1e-5, atol=1e-5)


def test_auto_prefers_pallas_when_it_fits():
    rng = np.random.default_rng(2)
    coeffs = _tridiag_coeffs(rng, N, uniform=False)
    p = plan(_system(3, coeffs, False, "constant", None), backend="auto")
    assert p.backend == "pallas"


def test_auto_falls_back_to_reference_when_vmem_would_trip(monkeypatch):
    """backend='auto' must degrade to reference instead of raising when
    check_fits would reject even the smallest block_m."""
    rng = np.random.default_rng(3)
    coeffs = _tridiag_coeffs(rng, N, uniform=False)
    system = _system(3, coeffs, False, "constant", None)
    monkeypatch.setattr(kcommon, "VMEM_BUDGET_BYTES", 1024)
    p = plan(system, backend="auto")
    assert p.backend == "reference"
    rhs = jnp.asarray(rng.normal(size=(N, M)).astype(np.float32))
    want = np.asarray(_core_reference(3, False, coeffs, rhs))
    np.testing.assert_allclose(np.asarray(p.solve(rhs)), want,
                               rtol=1e-5, atol=1e-5)
    # explicit pallas still raises (the user asked for it, so no fallback)
    with pytest.raises(NotImplementedError):
        plan(system, backend="pallas")


def test_auto_falls_back_for_periodic_batch_mode():
    rng = np.random.default_rng(4)
    coeffs = _tridiag_coeffs(rng, N, uniform=False)
    p = plan(_system(3, coeffs, True, "batch", M), backend="auto")
    assert p.backend == "reference"


def test_block_m_autotunes_against_vmem_budget(monkeypatch):
    rng = np.random.default_rng(5)
    coeffs = _tridiag_coeffs(rng, 256, uniform=False)
    system = BandedSystem.tridiag(*coeffs, n=256)
    # plenty of budget -> largest candidate
    assert solver_pallas.auto_block_m(system) == 1024
    # 2 double-buffered (256, bm) fp32 blocks + one carry row:
    # (2*2*256*bm + bm)*4 bytes — 1.1 MB fits bm=256, not bm=512
    monkeypatch.setattr(kcommon, "VMEM_BUDGET_BYTES", 1_100_000)
    assert solver_pallas.auto_block_m(system) == 256
    p = plan(system, backend="pallas")
    assert p.impl.block_m == 256


def test_registry_contract():
    assert {"reference", "pallas", "sharded"} <= set(available_backends())
    with pytest.raises(KeyError, match="unknown solver backend"):
        plan(BandedSystem.tridiag(1.0, 4.0, 1.0, n=8), backend="nope")

    @solver_registry.register_backend("_test_echo")
    class EchoBackend:
        def __init__(self, system, **opts):
            self.system = system
            self.stored = ()

        def solve(self, rhs, **kw):
            return rhs

    try:
        p = plan(BandedSystem.tridiag(1.0, 4.0, 1.0, n=8),
                 backend="_test_echo")
        assert isinstance(p, Plan)
        rhs = jnp.ones((8, 2))
        assert p.solve(rhs) is rhs
    finally:
        solver_registry._REGISTRY.pop("_test_echo", None)


def test_plan_storage_bytes_matches_paper_accounting():
    n, m = 1024, 4096
    const = plan(BandedSystem.tridiag(1.0, 4.0, 1.0, n=n), backend="reference")
    batch = plan(BandedSystem.tridiag(1.0, 4.0, 1.0, n=n, mode="batch",
                                      batch=m), backend="reference")
    tot_c = const.storage_bytes(rhs_batch=m)["total_bytes"]
    tot_b = batch.storage_bytes(rhs_batch=m)["total_bytes"]
    assert tot_c == (3 * n + n * m) * 4
    assert tot_b == (4 * n * m) * 4
    assert 1 - tot_c / tot_b > 0.74


@pytest.mark.filterwarnings("ignore::DeprecationWarning")
def test_operator_shims_match_frontend():
    """TridiagOperator/PentaOperator keep their call signatures and now run
    through the same engine as the front-end."""
    from repro.core import PentaOperator, TridiagOperator

    rng = np.random.default_rng(6)
    a, b, c = _tridiag_coeffs(rng, N, uniform=False)
    d = jnp.asarray(rng.normal(size=(N, 7)).astype(np.float32))
    op = TridiagOperator.create(a, b, c, mode="constant", periodic=True)
    p = plan(BandedSystem.tridiag(a, b, c, periodic=True), backend="reference")
    np.testing.assert_allclose(np.asarray(op.solve(d, method="scan", unroll=1)),
                               np.asarray(p.solve(d)), rtol=1e-6, atol=1e-6)

    pa, pb, pc_, pd_, pe = _penta_coeffs(rng, N, uniform=True)
    op5 = PentaOperator.create(pa, pb, pc_, pd_, pe, mode="uniform",
                               periodic=True)
    p5 = plan(BandedSystem.penta(pa, pb, pc_, pd_, pe, periodic=True,
                                 mode="uniform"), backend="reference")
    np.testing.assert_allclose(np.asarray(op5.solve(d)),
                               np.asarray(p5.solve(d)), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("backend", ["pallas", "sharded"])
def test_pde_layer_flips_backends(backend):
    """DiffusionCN routed through repro.solver: one argument flips backends."""
    from repro.pde import DiffusionCN

    n, m = 64, 32
    dt, steps = 2e-5, 3
    rng = np.random.default_rng(7)
    f0 = jnp.asarray(rng.normal(size=(n, m)).astype(np.float32))
    ref = DiffusionCN(n=n, dt=dt, backend="reference")
    other = DiffusionCN(n=n, dt=dt, backend=backend)
    a = np.asarray(ref.run(f0, steps, use_scan=False))
    b = np.asarray(other.run(f0, steps, use_scan=False))
    np.testing.assert_allclose(a, b, rtol=3e-5, atol=3e-5)
