"""cuSten-equivalent: periodic finite-difference stencils on interleaved
(N, M) field batches (the paper computes its CN right-hand sides with
cuSten [13]; this is the JAX analogue)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import scopes


def apply_periodic_stencil(field: jax.Array, weights) -> jax.Array:
    """Apply a centred periodic stencil along axis 0 of ``field``.

    field:   (N, ...) interleaved batch (N = grid axis).
    weights: sequence of length 2r+1 (offset -r..+r).
    """
    weights = list(weights)
    r = (len(weights) - 1) // 2
    with jax.named_scope(scopes.STENCIL):
        out = jnp.zeros_like(field)
        for k, w in enumerate(weights):
            off = k - r
            if w == 0:
                continue
            out = out + w * jnp.roll(field, -off, axis=0)
    return out


def cn_rhs_diffusion(field: jax.Array, sigma: float) -> jax.Array:
    """Paper Eq. (9) RHS: sigma C_{i-1} + (1-2 sigma) C_i + sigma C_{i+1}."""
    return apply_periodic_stencil(field, [sigma, 1.0 - 2.0 * sigma, sigma])


def cn_rhs_hyperdiffusion(field: jax.Array, sigma: float) -> jax.Array:
    """Paper Eq. (20b) RHS:
    -sigma C_{i-2} + 4 sigma C_{i-1} + (1-6 sigma) C_i + 4 sigma C_{i+1} - sigma C_{i+2}."""
    return apply_periodic_stencil(
        field, [-sigma, 4.0 * sigma, 1.0 - 6.0 * sigma, 4.0 * sigma, -sigma])
