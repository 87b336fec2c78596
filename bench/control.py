"""Controls: the reference put in the program's place and computed in
bfloat16, the precision below the configurations' float32.

The comparison that decides ``correct`` has to fail a control.  A kind
(``kinds/<kind>.py``) builds its control from these when ``run.py
--control`` asks for it; the benchmark's own runs never do.  JAX and the
host references only, nothing from ``src/repro``.
"""

from __future__ import annotations

import numpy as np

import reference as ref

#: Largest N whose dense inverse the periodic control forms on the host.
DENSE_MAX_N = 4096


def cn_step(stencil, dx_power: int, n: int, dt: float, dtype=None):
    """One periodic Crank-Nicolson step: the stencil RHS, then the product
    with the LHS's inverse (formed in float64 on the host), in ``dtype``
    (bfloat16 unless given)."""
    import jax.numpy as jnp
    dtype = dtype or jnp.bfloat16
    _, diags, weights = ref.cn_operator(stencil, dx_power, n, dt)
    a_inv = np.linalg.inv(ref.dense_matrix([np.full(n, d) for d in diags],
                                           True))
    a_inv = jnp.asarray(a_inv, dtype)
    r = len(weights) // 2

    def step(field):
        f = field.astype(dtype)
        rhs = sum(dtype(w) * jnp.roll(f, -(k - r), axis=0)
                  for k, w in enumerate(weights))
        return jnp.matmul(a_inv, rhs, preferred_element_type=dtype)

    return step


def banded_solve(diags, periodic: bool, dtype=None):
    """A solve of the banded system ``diags`` (sub-most first, one value
    per row) for the columns of the right-hand side, in ``dtype``
    (bfloat16 unless given).

    Dirichlet systems: banded Gaussian elimination without pivoting (the
    systems are diagonally dominant), one row at a time, then back
    substitution; for three diagonals this is the Thomas algorithm.
    Periodic systems: the product with the inverse, formed in float64 on
    the host, for N up to ``DENSE_MAX_N``."""
    import jax
    import jax.numpy as jnp
    dtype = dtype or jnp.bfloat16
    n = len(diags[0])
    if periodic:
        if n > DENSE_MAX_N:
            raise NotImplementedError(f"periodic control for N={n} > "
                                      f"{DENSE_MAX_N}")
        a_inv = jnp.asarray(np.linalg.inv(ref.dense_matrix(diags, True)),
                            dtype)
        return lambda rhs: jnp.matmul(a_inv, rhs.astype(dtype),
                                      preferred_element_type=dtype)

    k = len(diags) // 2
    rows = np.stack([np.asarray(d, np.float64) for d in diags], axis=1)
    cols = np.arange(n)[:, None] + np.arange(2 * k + 1)[None, :] - k
    rows[(cols < 0) | (cols >= n)] = 0.0      # entries outside the matrix
    rows = jnp.asarray(rows, dtype)

    def forward(carry, item):
        upper, rhs_prev = carry     # rows i-k..i-1: upper coefficients, RHS
        row, rhs = item
        for t in range(k):          # eliminate column i-k+t, oldest first
            lower = row[t]
            row = row.at[t + 1:t + k + 1].add(-lower * upper[t])
            rhs = rhs - lower * rhs_prev[t]
        pivot = row[k]
        u_new, d_new = row[k + 1:] / pivot, rhs / pivot
        carry = (jnp.concatenate([upper[1:], u_new[None]]),
                 jnp.concatenate([rhs_prev[1:], d_new[None]]))
        return carry, (u_new, d_new)

    def backward(x_next, item):     # x_next: x of rows i+1..i+k
        u, d = item
        x = d - jnp.sum(u[:, None] * x_next, axis=0)
        return jnp.concatenate([x[None], x_next[:-1]]), x

    def solve(rhs):
        d = rhs.astype(dtype)
        m = d.shape[1]
        init = (jnp.zeros((k, k), dtype), jnp.zeros((k, m), dtype))
        _, (u, dd) = jax.lax.scan(forward, init, (rows, d))
        _, x = jax.lax.scan(backward, jnp.zeros((k, m), dtype), (u, dd),
                            reverse=True)
        return x

    return solve
