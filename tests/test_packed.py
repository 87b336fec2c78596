"""The packed shared sweep: 8 lane tiles on the sublanes of every vreg.

The packed kernels (``SweepSpec.packed``) run the row sweep's arithmetic in
the same order, so their results are bit-equal to it; the dispatch picks
them from the shape alone (``ops._packed_choice``), and a CN step through
the steppers is the row sweep's step bit for bit.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import penta_factor, thomas_factor
from repro.kernels import common, engine, ops
from repro.pde import DiffusionCN, HyperdiffusionCN


def _factor(bandwidth, mode, n, rng):
    if bandwidth == 3:
        a = rng.uniform(-1, 1, n).astype(np.float32)
        c = rng.uniform(-1, 1, n).astype(np.float32)
        b = (np.abs(a) + np.abs(c) + 2.5).astype(np.float32)
        return thomas_factor(*map(jnp.asarray, (a, b, c)))
    if mode == "uniform":
        s = 0.11
        coeffs = [np.full(n, v, np.float32)
                  for v in (s, -4 * s, 1 + 6 * s, -4 * s, s)]
    else:
        off = [rng.uniform(-1, 1, n).astype(np.float32) for _ in range(4)]
        main = (sum(np.abs(o) for o in off) + 4.0).astype(np.float32)
        coeffs = [off[0], off[1], main, off[2], off[3]]
    return penta_factor(*map(jnp.asarray, coeffs))


def _solve(bandwidth, mode, f, rhs, **kw):
    """The ops-layer dispatch, as every backend calls it."""
    if bandwidth == 3:
        return ops.thomas_constant(f, rhs, **kw)
    return ops.penta_constant(f, rhs, uniform=mode == "uniform", **kw)


def _row_solve(bandwidth, mode, f, rhs, transposed):
    """The row sweep on the same operands, called past the dispatch."""
    spec = engine.find_spec(bandwidth, mode, transposed=transposed)
    eps = None
    if bandwidth == 3:
        lhs = ops.stack_tridiag_lhs(f, transposed=transposed)
    else:
        lhs = ops.stack_penta_lhs(f, uniform=spec.uniform,
                                  transposed=transposed)
        if spec.uniform:
            eps = ops._uniform_eps_param(f, rhs.dtype)
    return engine.shared_solver(spec)(lhs, rhs, block_m=512, eps=eps)


def _names(fn, shape, dtype=jnp.float32) -> set:
    """The ``name=`` of every call ``fn`` traces on an array of ``shape``."""
    text = str(jax.make_jaxpr(fn)(jax.ShapeDtypeStruct(shape, dtype)))
    return set(re.findall(r"name=(\w+)", text))


def _kernels(fn, shape, dtype=jnp.float32) -> set:
    """Names of the engine kernels ``fn`` runs on an array of ``shape``."""
    return _names(fn, shape, dtype) & set(engine.REGISTRY)


@pytest.mark.parametrize("n", [1024, 64])
@pytest.mark.parametrize("m", [1024, 4096])
@pytest.mark.parametrize("transposed", [False, True])
@pytest.mark.parametrize("bandwidth,mode", [(3, "constant"), (3, "uniform"),
                                            (5, "constant"), (5, "uniform")])
def test_packed_sweep_is_bit_equal_to_the_row_sweep(bandwidth, mode,
                                                    transposed, m, n):
    rng = np.random.default_rng(bandwidth * 100 + m // 1024 + n)
    f = _factor(bandwidth, mode, n, rng)
    rhs = jnp.asarray(rng.normal(size=(n, m)).astype(np.float32))
    fn = lambda d: _solve(bandwidth, mode, f, d, transposed=transposed)
    row = engine.find_spec(bandwidth, mode, transposed=transposed)
    assert _kernels(fn, (n, m)) == {row.packed_name}
    np.testing.assert_array_equal(
        np.asarray(fn(rhs)),
        np.asarray(_row_solve(bandwidth, mode, f, rhs, transposed)))


@pytest.mark.parametrize("n,m,dtype,storage,packed", [
    (1024, 1024, jnp.float32, None, True),
    (64, 4096, jnp.float32, None, True),
    (1024, 524288, jnp.float32, None, True),
    (8, 3072, jnp.float32, None, True),
    (64, 128, jnp.float32, None, False),       # under 8 lane tiles
    (64, 1000, jnp.float32, None, False),      # ragged M
    (64, 1152, jnp.float32, None, False),      # not whole packed groups
    (12, 1024, jnp.float32, None, False),      # N % 8 != 0
    (64, 1024, jnp.float32, "bf16", False),    # bf16 storage
    (64, 1024, jnp.bfloat16, None, False),     # RHS below the compute dtype
])
@pytest.mark.parametrize("bandwidth", [3, 5])
def test_dispatch_packs_exactly_when_the_shape_allows(bandwidth, n, m, dtype,
                                                      storage, packed):
    f = _factor(bandwidth, "constant", n, np.random.default_rng(0))
    fn = lambda d: _solve(bandwidth, "constant", f, d, block_m=128,
                          storage_dtype=storage)
    row = engine.find_spec(bandwidth, "constant")
    want = row.packed_name if packed else row.name
    assert _kernels(fn, (n, m), dtype) == {want}


def test_streamed_solves_never_pack():
    f = _factor(3, "constant", 64, np.random.default_rng(0))
    fn = lambda d: ops.thomas_constant(f, d, block_m=128, block_n=32)
    assert {"thomas_constant_streamed_fwd", "thomas_constant_streamed_bwd"} \
        <= _names(fn, (64, 4096))
    assert not any("packed" in name for name in _names(fn, (64, 4096)))


@pytest.mark.parametrize("n,m,block_m", [
    (1024, 524288, 4096),     # the CN cells: 4 packed vregs a trip
    (1024, 3072, 1024),       # the widest tile that divides M
    (2048, 8192, 2048),       # the widest tile that fits the budget
    (4096, 8192, 1024),
])
def test_packed_lane_tile_is_the_widest_that_fits(n, m, block_m):
    spec = engine.REGISTRY["penta_constant"]
    packed, got = ops._packed_choice(spec, n, m, jnp.float32, None)
    assert (packed.name, got) == ("penta_constant_packed", block_m)
    vmem, _ = common.spec_working_set(packed, n, got)
    assert vmem <= common.PACKED_VMEM_BUDGET_BYTES
    if got < ops._PACKED_BLOCK_M[0] and m % (2 * got) == 0:
        assert not common.spec_fits(packed, n, 2 * got)


def test_packed_lane_tile_gives_way_to_the_row_sweep_when_nothing_fits():
    spec = engine.REGISTRY["thomas_constant"]
    assert ops._packed_choice(spec, 8192, 8192, jnp.float32, None) is None


def test_packed_specs_are_registered_beside_their_row_siblings():
    packed = sorted(n for n, s in engine.REGISTRY.items()
                    if getattr(s, "packed", False))
    assert packed == ["penta_constant_packed", "penta_constant_packed_t",
                      "penta_uniform_packed", "penta_uniform_packed_t",
                      "thomas_constant_packed", "thomas_constant_packed_t"]
    for name in packed:
        spec = engine.REGISTRY[name]
        row = engine.REGISTRY[spec.row_name]
        assert spec.passes() == row.passes()
        assert spec.traffic_words(1024, 4096) == row.traffic_words(1024, 4096)
        assert spec.vmem_counts() == (2, row.lhs_rows, 0)
        assert spec.sweep_scratch() == 1
        assert spec.twin_name() in engine.REGISTRY
    with pytest.raises(ValueError, match="resident shared"):
        engine.SweepSpec(3, "shared", streamed=True, packed=True)
    with pytest.raises(ValueError, match="resident shared"):
        engine.SweepSpec(3, "batch", packed=True)


@pytest.mark.parametrize("cls", [DiffusionCN, HyperdiffusionCN])
def test_cn_step_on_packed_sweep_is_the_row_sweep_step(cls, monkeypatch):
    n, m = 64, 2048
    if cls is DiffusionCN:
        stepper = DiffusionCN(n=n, dt=1e-4, backend="pallas")
    else:
        stepper = HyperdiffusionCN(n=n, dt=1e-9, backend="pallas",
                                   mode="constant")
    field = jax.random.normal(jax.random.PRNGKey(3), (n, m), jnp.float32)
    _, step = stepper.step_fn()
    row = engine.find_spec(3 if cls is DiffusionCN else 5, "constant")
    assert _kernels(step, (n, m)) == {row.packed_name}
    packed = np.asarray(jax.jit(step)(field))
    # the row sweep's step, as before the packed sweep existed; the solve's
    # cached traces hold the dispatch's choice, so they go on both sides
    monkeypatch.setattr(ops, "_packed_choice", lambda *a: None)
    jax.clear_caches()
    try:
        assert _kernels(step, (n, m)) == {row.name}
        rows = np.asarray(jax.jit(step)(field))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    np.testing.assert_array_equal(packed, rows)
