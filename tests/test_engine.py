"""The declarative sweep engine: registry sanity, a full interpret-mode
parity matrix over EVERY registered ``SweepSpec``, and the spec-derived
traffic / VMEM accounting (no hand-kept tables to drift).

The matrix is the CI job that guards the engine's contract: each variant
(2 bandwidths x shared/batch x fwd/transposed x resident/streamed x
uniform) is exercised through the ``repro.kernels.ops`` dispatch on ragged
shapes and compared against the ``repro.core`` reference sweeps, and each
streamed variant must be BIT-exact against its resident sibling (same
arithmetic, chunked).
"""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import (penta_factor, penta_factor_solve, penta_solve,
                        penta_solve_t, thomas_factor, thomas_factor_solve,
                        thomas_solve, thomas_solve_t)
from repro.kernels import ops as kops
from repro.kernels.engine import (REGISTRY, RecurrenceSpec, SweepSpec,
                                  find_recurrence_spec, find_spec)

# ragged on both axes: exercises lane padding and sweep padding
N, M = 45, 70
BLOCK_M, BLOCK_N = 64, 16


# the packed specs engage only on whole packed groups (N % 8, M % 1024)
PACKED_N, PACKED_M = 48, 2048


def _shape(spec) -> tuple:
    return (PACKED_N, PACKED_M) if getattr(spec, "packed", False) else (N, M)


def _tridiag_factor(rng, n=N):
    a = rng.uniform(-1, 1, n).astype(np.float32)
    c = rng.uniform(-1, 1, n).astype(np.float32)
    b = (np.abs(a) + np.abs(c) + 2.5).astype(np.float32)
    return thomas_factor(*map(jnp.asarray, (a, b, c)))


def _penta_coeffs(rng, uniform, n=N):
    if uniform:
        one = np.ones(n, np.float32)
        s = 0.11
        return s * one, -4 * s * one, (1 + 6 * s) * one, -4 * s * one, s * one
    a = rng.uniform(-1, 1, n).astype(np.float32)
    b = rng.uniform(-1, 1, n).astype(np.float32)
    d = rng.uniform(-1, 1, n).astype(np.float32)
    e = rng.uniform(-1, 1, n).astype(np.float32)
    c = (np.abs(a) + np.abs(b) + np.abs(d) + np.abs(e) + 4.0).astype(
        np.float32)
    return a, b, c, d, e


def _batch_diags(rng, bandwidth):
    if bandwidth == 3:
        a = rng.uniform(-1, 1, (N, M)).astype(np.float32)
        c = rng.uniform(-1, 1, (N, M)).astype(np.float32)
        b = (np.abs(a) + np.abs(c) + 2.5).astype(np.float32)
        return tuple(map(jnp.asarray, (a, b, c)))
    a, b, d, e = (rng.uniform(-1, 1, (N, M)).astype(np.float32)
                  for _ in range(4))
    c = (np.abs(a) + np.abs(b) + np.abs(d) + np.abs(e) + 4.0).astype(
        np.float32)
    return tuple(map(jnp.asarray, (a, b, c, d, e)))


def _recurrence_gates(rng, order):
    """Stable per-token gate operands (|s| + |t| < 1 bounds the carries)."""
    scales = (0.9,) if order == 1 else (0.6, 0.3)
    return tuple(jnp.asarray(rng.uniform(-s, s, (N, M)).astype(np.float32))
                 for s in scales)


def _recurrence_reference(gates, q, reverse):
    """Token-by-token numpy scan: h_i = q_i + sum_k gate_k[i] * h_{i-k}."""
    gates = [np.asarray(g) for g in gates]
    q = np.asarray(q)
    h = np.zeros_like(q)
    carries = [np.zeros(q.shape[1], q.dtype) for _ in gates]
    for i in (range(N - 1, -1, -1) if reverse else range(N)):
        v = q[i].copy()
        for g, c in zip(gates, carries):
            v += g[i] * c
        h[i] = v
        carries = [v] + carries[:-1]
    return h


def _run_spec(spec, rhs):
    """Dispatch ``rhs`` through the ops layer exactly as the solver backend
    would, returning (got, want) for the parity check."""
    # seed on the streaming-invariant fields so a streamed spec and its
    # resident sibling solve the SAME system (the bit-exactness pairing)
    if isinstance(spec, RecurrenceSpec):
        rng = np.random.default_rng(spec.order * 8 + spec.reverse * 2)
        gates = _recurrence_gates(rng, spec.order)
        got = kops.recurrence(*gates, rhs, reverse=spec.reverse,
                              block_m=BLOCK_M,
                              block_n=BLOCK_N if spec.streamed else None,
                              interpret=True)
        return got, _recurrence_reference(gates, rhs, spec.reverse)
    seed = (spec.bandwidth * 8 + (spec.layout == "batch") * 4
            + spec.transposed * 2 + spec.uniform)
    rng = np.random.default_rng(seed)
    block_n = BLOCK_N if spec.streamed else None
    if spec.layout == "batch":
        diags = _batch_diags(rng, spec.bandwidth)
        fn = kops.thomas_batch if spec.bandwidth == 3 else kops.penta_batch
        got = fn(*diags, rhs, block_m=BLOCK_M, block_n=block_n,
                 fused=getattr(spec, "fused", False), interpret=True)
        oracle = (thomas_factor_solve if spec.bandwidth == 3
                  else penta_factor_solve)
        return got, oracle(*diags, rhs)
    if spec.bandwidth == 3:
        f = _tridiag_factor(rng, rhs.shape[0])
        got = kops.thomas_constant(f, rhs, block_m=BLOCK_M, block_n=block_n,
                                   fused=getattr(spec, "fused", False),
                                   interpret=True, transposed=spec.transposed)
        want = (thomas_solve_t if spec.transposed else thomas_solve)(f, rhs)
        return got, want
    f = penta_factor(*map(jnp.asarray, _penta_coeffs(rng, spec.uniform,
                                                     rhs.shape[0])))
    got = kops.penta_constant(f, rhs, block_m=BLOCK_M, block_n=block_n,
                              fused=getattr(spec, "fused", False),
                              interpret=True, uniform=spec.uniform,
                              transposed=spec.transposed)
    want = (penta_solve_t if spec.transposed else penta_solve)(f, rhs)
    return got, want


# ---------------------------------------------------------------------------
# Registry shape
# ---------------------------------------------------------------------------

def test_registry_covers_the_variant_matrix():
    """2 bandwidths x (shared: fwd/transposed x resident/packed/streamed/
    fused [x uniform for penta]) + (batch: resident/streamed/fused) = 30
    sweep specs, plus the gated recurrence family (2 orders x fwd/rev x
    resident/streamed) = 38 specs total."""
    assert len(REGISTRY) == 38
    for order in (1, 2):
        for reverse in (False, True):
            for streamed in (False, True):
                assert RecurrenceSpec(order, reverse=reverse,
                                      streamed=streamed).name in REGISTRY
    for bw in (3, 5):
        for transposed in (False, True):
            for streamed, fused in ((False, False), (True, False),
                                    (True, True)):
                assert SweepSpec(bw, "shared", transposed=transposed,
                                 streamed=streamed,
                                 fused=fused).name in REGISTRY
                if bw == 5:
                    assert SweepSpec(bw, "shared", transposed=transposed,
                                     streamed=streamed, fused=fused,
                                     uniform=True).name in REGISTRY
            for uniform in ((False, True) if bw == 5 else (False,)):
                assert SweepSpec(bw, "shared", transposed=transposed,
                                 uniform=uniform,
                                 packed=True).name in REGISTRY
        for streamed, fused in ((False, False), (True, False), (True, True)):
            assert SweepSpec(bw, "batch", streamed=streamed,
                             fused=fused).name in REGISTRY


def test_no_transposed_batch_spec():
    """Transposed batch solves roll the diagonals and reuse the forward
    batch kernels — the engine refuses to mint a redundant variant."""
    with pytest.raises(ValueError):
        SweepSpec(3, "batch", transposed=True)
    with pytest.raises(ValueError):
        SweepSpec(3, "shared", uniform=True)  # uniform is penta-only


def test_find_spec_maps_tridiag_uniform_to_constant():
    assert find_spec(3, "uniform").name == "thomas_constant"
    assert find_spec(5, "uniform", streamed=True,
                     transposed=True).name == "penta_uniform_streamed_t"


# ---------------------------------------------------------------------------
# The parity matrix: every registered spec vs the reference sweeps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(REGISTRY))
def test_spec_parity_matrix(name):
    spec = REGISTRY[name]
    rng = np.random.default_rng(7)
    rhs = jnp.asarray(rng.normal(size=_shape(spec)).astype(np.float32))
    got, want = _run_spec(spec, rhs)
    assert got.shape == _shape(spec)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-5, atol=5e-5)


@pytest.mark.parametrize("name", sorted(n for n, s in REGISTRY.items()
                                        if s.streamed))
def test_streamed_specs_bit_exact_vs_resident(name):
    """Chunking changes where the carries live, not the arithmetic."""
    spec = REGISTRY[name]
    resident = REGISTRY[spec.resident_name]
    rng = np.random.default_rng(11)
    rhs = jnp.asarray(rng.normal(size=(N, M)).astype(np.float32))
    got, _ = _run_spec(spec, rhs)
    res, _ = _run_spec(resident, rhs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(res))


# ---------------------------------------------------------------------------
# Spec-derived accounting: traffic + VMEM (satellite: every registered
# spec must have a traffic entry — derived, not hand-kept)
# ---------------------------------------------------------------------------

def test_every_registered_spec_has_a_traffic_entry():
    n, m = 512, 1024
    for spec in REGISTRY.values():
        words = spec.traffic_words(n, m)
        assert isinstance(words, int) and words > 0
        assert spec.traffic_bytes(n, m, jnp.float64) == 8 * words
        if isinstance(spec, RecurrenceSpec):
            # single-pass family: streaming revisits nothing, and the ops
            # resolver lands on the same registered spec
            assert words == (spec.order + 2) * n * m
            assert kops.recurrence_hbm_traffic_bytes(
                spec.order, n, m, streamed=spec.streamed,
                reverse=spec.reverse) == spec.traffic_bytes(n, m)
            continue
        if spec.layout == "batch":
            continue
        # the dispatcher resolves the same spec to the same number
        assert kops.solver_hbm_traffic_bytes(
            spec.bandwidth, spec.mode, n, m, streamed=spec.streamed,
            fused=getattr(spec, "fused", False),
            transposed=spec.transposed) == spec.traffic_bytes(n, m)
    # batch entries resolve through the mode path (incl. the rolled adjoint)
    for bw in (3, 5):
        b = kops.solver_hbm_traffic_bytes(bw, "batch", n, m)
        assert kops.solver_hbm_traffic_bytes(bw, "batch", n, m,
                                             transposed=True) == b
        assert kops.solver_hbm_traffic_bytes(
            bw, "batch", n, m, streamed=True) > b


def test_traffic_derivation_matches_paper_numbers():
    """The derived model reproduces the hand-derived paper/PR-3 numbers."""
    n, m = 1024, 4096
    sweeps = [s for s in REGISTRY.values() if isinstance(s, SweepSpec)]
    tri = {s.name: s for s in sweeps if s.bandwidth == 3}
    assert tri["thomas_constant"].traffic_words(n, m) == 2 * n * m + 3 * n
    assert tri["thomas_batch"].traffic_words(n, m) == 5 * n * m
    assert tri["thomas_constant_streamed"].traffic_words(n, m) \
        == 2 * (2 * n * m + 3 * n)
    # batch streamed: 4 in + 2 out (fwd, c_hat spilled) + 2 in + 1 out (bwd)
    assert tri["thomas_batch_streamed"].traffic_words(n, m) == 9 * n * m
    pen = {s.name: s for s in sweeps if s.bandwidth == 5}
    assert pen["penta_uniform"].traffic_words(n, m) == 2 * n * m + 4 * n + 1
    # batch streamed: 6 in + 3 out (fwd, gamma/delta spilled) + 3 in + 1 out
    assert pen["penta_batch_streamed"].traffic_words(n, m) == 13 * n * m
    # transposed twins move the same streams
    for k in ("thomas_constant", "thomas_constant_streamed",
              "penta_uniform"):
        reg = {s.name: s for s in REGISTRY.values()}
        assert reg[k + "_t"].traffic_words(n, m) == reg[k].traffic_words(n, m)


def test_vmem_counts_are_spec_derived():
    """The budget checks reason from the spec's stream structure:
    (pipelined blocks, SMEM LHS rows, VMEM carry rows) + full-N scratch."""
    assert REGISTRY["thomas_constant"].vmem_counts() == (2, 3, 1)
    assert REGISTRY["penta_constant"].vmem_counts() == (2, 5, 2)
    assert REGISTRY["penta_uniform"].vmem_counts() == (2, 4, 2)
    # resident batch: diagonals + rhs in, x out; coefs in full-N scratch
    assert REGISTRY["thomas_batch"].vmem_counts() == (5, 0, 2)
    assert REGISTRY["thomas_batch"].sweep_scratch() == 1
    assert REGISTRY["penta_batch"].vmem_counts() == (7, 0, 6)
    assert REGISTRY["penta_batch"].sweep_scratch() == 2
    # streamed batch fwd: diagonals + rhs in, intermediate + coefs out
    assert REGISTRY["thomas_batch_streamed"].vmem_counts() == (6, 0, 2)
    assert REGISTRY["penta_batch_streamed"].vmem_counts() == (9, 0, 6)
    # transposed shares the forward's working set
    assert REGISTRY["thomas_constant_t"].vmem_counts() \
        == REGISTRY["thomas_constant"].vmem_counts()


def test_find_spec_errors_name_valid_choices():
    """Unknown combos raise informative ValueErrors, never bare KeyErrors
    leaking the internal registry key."""
    with pytest.raises(ValueError, match="bandwidth 3 .* and 5"):
        find_spec(7, "constant")
    with pytest.raises(ValueError, match="'constant'.*'uniform'.*'batch'"):
        find_spec(3, "dense")
    with pytest.raises(ValueError, match="rolls the per-lane diagonals"):
        find_spec(3, "batch", transposed=True)
    # tridiag uniform aliases to the constant kernel (no eps row to drop)
    assert find_spec(3, "uniform").name == "thomas_constant"
    # the recurrence lookup names its valid orders the same way
    with pytest.raises(ValueError, match="order 1 .* and order 2"):
        find_recurrence_spec(3)
    assert find_recurrence_spec(2, reverse=True,
                                streamed=True).name == "recur2_streamed_rev"


def test_traffic_bytes_errors_are_informative():
    with pytest.raises(ValueError, match="bandwidth"):
        kops.solver_hbm_traffic_bytes(4, "constant", 64, 64)
    with pytest.raises(ValueError, match="storage mode"):
        kops.solver_hbm_traffic_bytes(3, "woops", 64, 64)
    # the batch adjoint reuses the forward batch kernels - same streams
    assert kops.solver_hbm_traffic_bytes(3, "batch", 64, 64,
                                         transposed=True) \
        == kops.solver_hbm_traffic_bytes(3, "batch", 64, 64)
