"""cuSten-equivalent: periodic finite-difference stencils on interleaved
(N, M) field batches (the paper computes its CN right-hand sides with
cuSten [13]; this is the JAX analogue)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro import scopes


def apply_periodic_stencil(field: jax.Array, weights) -> jax.Array:
    """Apply a centred periodic stencil along axis 0 of ``field``.

    field:   (N, ...) interleaved batch (N = grid axis).
    weights: sequence of length 2r+1 (offset -r..+r).

    The field is wrap-padded by ``r`` rows at each end once (which also
    holds for N < r), and the stencil sums ``2r+1`` static windows of that
    halo-extended field, so the compiler fuses the whole RHS into one read
    of the field and one write.  A ``jnp.roll`` per offset would lower to a
    slice pair and a concatenate that do not fuse into the sum, each a
    full-field pass.  Each output element is the same sum, in the same
    order, as ``sum_k w_k * roll(field, r - k)``.
    """
    weights = list(weights)
    r = (len(weights) - 1) // 2
    n = field.shape[0]
    with jax.named_scope(scopes.STENCIL):
        pad = ((r, r),) + ((0, 0),) * (field.ndim - 1)
        ext = jnp.pad(field, pad, mode="wrap")
        out = jnp.zeros_like(field)
        for k, w in enumerate(weights):
            if w == 0:
                continue
            out = out + w * lax.slice_in_dim(ext, k, k + n, axis=0)
    return out


def cn_rhs_diffusion(field: jax.Array, sigma: float) -> jax.Array:
    """Paper Eq. (9) RHS: sigma C_{i-1} + (1-2 sigma) C_i + sigma C_{i+1}."""
    return apply_periodic_stencil(field, [sigma, 1.0 - 2.0 * sigma, sigma])


def cn_rhs_hyperdiffusion(field: jax.Array, sigma: float) -> jax.Array:
    """Paper Eq. (20b) RHS:
    -sigma C_{i-2} + 4 sigma C_{i-1} + (1-6 sigma) C_i + 4 sigma C_{i+1} - sigma C_{i+2}."""
    return apply_periodic_stencil(
        field, [-sigma, 4.0 * sigma, 1.0 - 6.0 * sigma, 4.0 * sigma, -sigma])
