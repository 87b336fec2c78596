"""The periodic stencil of ``repro.pde.stencil``: the same numbers as a sum
of ``jnp.roll``s, for every N and trailing shape, and the CN right-hand
sides against float64 NumPy."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import scopes
from repro.pde.stencil import (apply_periodic_stencil, cn_rhs_diffusion,
                               cn_rhs_hyperdiffusion)

#: Centred weights, r = 1 and r = 2, some with zeros (skipped terms).
WEIGHTS = {
    "diffusion": [0.3, 0.4, 0.3],
    "adi": [1.0, -2.0, 1.0],
    "hyperdiffusion": [-0.2, 0.8, -0.2, 0.8, -0.2],
    "zeros": [0.0, 1.5, 0.0, -0.5, 0.0],
}
TRAILING = {"1d": (), "2d": (6,), "3d": (5, 3)}


def roll_sum(field, weights):
    """The stencil as a sum of rolls, in the same order from the same zeros."""
    r = (len(weights) - 1) // 2
    out = jnp.zeros_like(field)
    for k, w in enumerate(weights):
        if w == 0:
            continue
        out = out + w * jnp.roll(field, -(k - r), axis=0)
    return out


def field_of(n, trailing, dtype, seed=0):
    rng = np.random.default_rng(seed + n)
    return jnp.asarray(rng.normal(size=(n,) + trailing), dtype=dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("trailing", sorted(TRAILING))
@pytest.mark.parametrize("weights", sorted(WEIGHTS))
@pytest.mark.parametrize("n", [1, 2, 3, 4, 5, 7, 64, 1024])
def test_stencil_equals_roll_sum(n, weights, trailing, dtype):
    field = field_of(n, TRAILING[trailing], dtype)
    got = apply_periodic_stencil(field, WEIGHTS[weights])
    want = roll_sum(field, WEIGHTS[weights])
    assert got.shape == field.shape and got.dtype == field.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def numpy_stencil(field, weights):
    r = (len(weights) - 1) // 2
    return sum(w * np.roll(field, -(k - r), axis=0)
               for k, w in enumerate(weights))


@pytest.mark.parametrize("n", [1, 2, 3, 5, 64, 1024])
@pytest.mark.parametrize("rhs,weights_of", [
    (cn_rhs_diffusion, lambda s: [s, 1 - 2 * s, s]),
    (cn_rhs_hyperdiffusion,
     lambda s: [-s, 4 * s, 1 - 6 * s, 4 * s, -s]),
])
def test_cn_rhs_matches_float64_numpy(rhs, weights_of, n):
    sigma = 0.37
    field = field_of(n, (8,), jnp.float32, seed=1)
    got = np.asarray(jax.jit(rhs, static_argnums=1)(field, sigma))
    want = numpy_stencil(np.asarray(field, np.float64), weights_of(sigma))
    # float32 rounding of each weight, product and partial sum
    scale = sum(abs(w) for w in weights_of(sigma)) * np.abs(field).max()
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-6 * scale)


@pytest.mark.parametrize("weights", ["diffusion", "hyperdiffusion"])
def test_every_stencil_op_is_in_the_stencil_scope(weights):
    """The halo's wrap slices, as well as the sum, carry ``repro.stencil``."""
    def rhs(field):
        return apply_periodic_stencil(field, WEIGHTS[weights])

    text = jax.jit(rhs).lower(jax.ShapeDtypeStruct((64, 256), jnp.float32)) \
        .compile().as_text()
    # every op_name but the parameter's own, which is its argument's name
    ops = [name for name in re.findall(r'op_name="([^"]*)"', text)
           if name != "field"]
    assert ops
    assert all(scopes.STENCIL in name.split("/") for name in ops)
