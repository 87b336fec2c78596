"""The layer scopes of ``repro.scopes`` in the compiled programs, and the
names the kernel builders give their ``pallas_call``s."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import scopes
from repro.analysis.capture import capture_pallas_calls, trace_spec_calls
from repro.core import periodic_thomas_factor, thomas_solve
from repro.core import tridiag
from repro.kernels import fused_cn, fused_cn_penta, ops
from repro.kernels.engine import REGISTRY
from repro.pde import DiffusionCN, HyperdiffusionCN
from repro.solver import BandedSystem, factorize, solve

N, M = 32, 256


def op_names(fn, *args) -> set:
    """Every ``op_name`` in the optimized HLO of ``jax.jit(fn)(*args)``."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


def scope_set(names) -> set:
    return {seg for name in names for seg in name.split("/")
            if seg.startswith("repro.")}


def stepper(cls, backend):
    if cls is DiffusionCN:
        return DiffusionCN(n=N, dt=1e-4, backend=backend)
    return HyperdiffusionCN(n=N, dt=1e-9, backend=backend, mode="constant")


@pytest.mark.parametrize("backend", ["reference", "pallas"])
@pytest.mark.parametrize("cls", [DiffusionCN, HyperdiffusionCN])
def test_cn_step_names_stencil_and_corner_correction(cls, backend):
    _, step = stepper(cls, backend).step_fn()
    found = scope_set(op_names(step, jax.ShapeDtypeStruct((N, M),
                                                          jnp.float32)))
    assert {scopes.STENCIL, scopes.CORNER_CORRECTION} <= found
    kernels = {s for s in found if s.startswith("repro.kernel.")}
    if backend == "pallas":
        name = "thomas_constant" if cls is DiffusionCN else "penta_constant"
        assert kernels == {scopes.kernel(name)}
    else:
        assert not kernels


@pytest.mark.parametrize("name", sorted(REGISTRY))
def test_every_engine_pallas_call_is_named_by_its_spec(name):
    spec = REGISTRY[name]
    records = trace_spec_calls(spec)
    assert len(records) == spec.num_pallas_calls
    if len(records) == 1:
        assert records[0].name == spec.name
    else:
        assert [r.name for r in records] == [f"{spec.name}_fwd",
                                             f"{spec.name}_bwd"]


@pytest.mark.parametrize("module,rows,z_cols,extra", [
    (fused_cn, 3, 1, ()),
    (fused_cn_penta, 5, 4, ((4, 4),)),
])
def test_fused_cn_pallas_calls_are_named(module, rows, z_cols, extra):
    pallas = (fused_cn.fused_cn_tridiag_pallas if module is fused_cn
              else fused_cn_penta.fused_cn_penta_pallas)
    n, m = 16, 128
    params = jnp.zeros((1, 8 if module is fused_cn else 16), jnp.float32)
    args = ([jnp.zeros((rows, n)), jnp.zeros((n, z_cols))]
            + [jnp.zeros(s) for s in extra] + [params, jnp.zeros((n, m))])
    with capture_pallas_calls() as records:
        pallas.__wrapped__(*args, block_m=128, interpret=True)
    assert [r.name for r in records] == [module.NAME]


def test_fused_cn_step_runs_in_its_kernel_scope():
    pf = DiffusionCN(n=N, dt=1e-4).factor()
    names = op_names(lambda c: ops.fused_cn_step(pf, 0.1, c),
                     jax.ShapeDtypeStruct((N, M), jnp.float32))
    assert scopes.kernel(fused_cn.NAME) in scope_set(names)


def test_pads_casts_and_slices_are_scoped_inside_the_kernel_scope():
    """Ragged M pads lanes and slices them off; bf16 storage casts."""
    pf = DiffusionCN(n=N, dt=1e-4).factor()
    names = op_names(
        lambda d: ops.thomas_constant(pf.factor, d, storage_dtype="bf16"),
        jax.ShapeDtypeStruct((N, 200), jnp.float32))
    kernel = scopes.kernel("thomas_constant")
    padded = [n for n in names if scopes.PAD_CAST in n.split("/")]
    assert padded
    assert all(n.split("/").index(kernel) < n.split("/").index(
        scopes.PAD_CAST) for n in padded)
    ops_in = {n.split("/")[-1] for n in padded}
    assert {"pad", "convert_element_type"} <= ops_in


@pytest.mark.parametrize("shape", [(N,), (N, 7)])
def test_tridiag_corner_correction_is_the_sherman_morrison_step(shape):
    s = 0.3
    a = jnp.full((N,), -s)
    b = jnp.full((N,), 1 + 2 * s)
    pf = periodic_thomas_factor(a, b, a)
    d = jax.random.normal(jax.random.PRNGKey(0), shape)
    y = thomas_solve(pf.factor, d)
    z = pf.z if y.ndim == 1 else pf.z[:, None]
    want = y - (y[0] + pf.v_last * y[-1]) * pf.inv_denom_sm * z
    got = tridiag.periodic_corner_correction(pf, y)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    dense = tridiag.dense_tridiag(a, b, a, periodic=True)
    np.testing.assert_allclose(np.asarray(dense @ got), np.asarray(d),
                               rtol=1e-4, atol=1e-4)


def test_pallas_backend_corrects_through_the_shared_home():
    """The ``pallas`` backend's periodic tridiagonal solve is the kernel's
    y plus ``periodic_corner_correction``: equal to the reference."""
    s = 0.4
    system = BandedSystem.tridiag(-s, 1 + 2 * s, -s, n=N, periodic=True)
    d = jax.random.normal(jax.random.PRNGKey(1), (N, 128))
    ref = solve(factorize(system, backend="reference"), d)
    got = solve(factorize(system, backend="pallas"), d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
