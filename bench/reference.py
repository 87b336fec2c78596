"""float64 host references and seeded generators.

Independent of the code under test: NumPy and SciPy only, nothing from
``src/repro``.  The benchmark compares what its timed path produced
against these, on columns drawn from the seed.
"""

from __future__ import annotations

import numpy as np


def jax_seed(seed: int) -> int:
    """A 32-bit key seed for ``jax.random.PRNGKey`` from any whole seed."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0])


def host_rng(seed: int, stream: int) -> np.random.Generator:
    """An independent NumPy stream ``stream`` of ``seed``."""
    return np.random.default_rng([seed, stream])


def check_columns(seed: int, m: int, count: int) -> np.ndarray:
    """``count`` column indices drawn from the seed, one in each of
    ``count`` equal strata of ``[0, m)``, so that every lane tile range and
    every chip's share of the columns is represented."""
    count = min(count, m)
    edges = np.linspace(0, m, count + 1).astype(np.int64)
    rng = host_rng(seed, 1)
    return np.array([rng.integers(lo, hi) for lo, hi in zip(edges[:-1],
                                                           edges[1:])])


def make_diags(rng: np.random.Generator, bandwidth: int, n: int,
               mode: str) -> list:
    """Diagonally dominant diagonals, ordered sub-most first: random
    vectors, or scalars repeated along N for the uniform mode."""
    k = bandwidth - 1
    size = 1 if mode == "uniform" else n
    off = [rng.uniform(-1.0, 1.0, size) for _ in range(k)]
    main = sum(np.abs(o) for o in off) + 1.0 + rng.uniform(0.0, 1.0, size)
    diags = off[:k // 2] + [main] + off[k // 2:]
    return [np.broadcast_to(d, (n,)).astype(np.float32) for d in diags]


def dense_matrix(diags, periodic: bool) -> np.ndarray:
    """Dense (N, N) float64 matrix with ``diags[k]`` on offset k - half;
    periodic systems wrap the band around the corners."""
    half = len(diags) // 2
    n = len(diags[0])
    a = np.zeros((n, n))
    rows = np.arange(n)
    for k, d in enumerate(diags):
        cols = rows + k - half
        keep = slice(None) if periodic else (cols >= 0) & (cols < n)
        a[rows[keep], cols[keep] % n] = np.asarray(d, np.float64)[keep]
    return a


def ref_solve(diags, periodic: bool, rhs: np.ndarray) -> np.ndarray:
    """float64 solve of the banded system for the columns of ``rhs``."""
    from scipy.linalg import solve_banded
    rhs = np.asarray(rhs, np.float64)
    if periodic:
        return np.linalg.solve(dense_matrix(diags, True), rhs)
    half = len(diags) // 2
    n = len(diags[0])
    ab = np.zeros((2 * half + 1, n))
    for k, d in enumerate(diags):
        off = k - half                      # A[i, i + off] = d[i]
        d = np.asarray(d, np.float64)
        if off >= 0:
            ab[half - off, off:] = d[:n - off]
        else:
            ab[half - off, :n + off] = d[-off:]
    return solve_banded((half, half), ab, rhs)


def cn_operator(stencil, dx_power: int, n: int, dt: float) -> tuple:
    """(sigma, LHS diagonals, RHS stencil weights) of the periodic
    Crank-Nicolson step on the unit domain with ``n`` points, for the
    spatial operator L given by its ``stencil`` (sub-most first):
    (I + sigma L) x' = (I - sigma L) x with sigma = dt / (2 dx^dx_power).

    diffusion (paper Eq. 9/11):      stencil (-1, 2, -1),      dx_power 2
    hyperdiffusion (Eq. 20a/20b):    stencil (1, -4, 6, -4, 1), dx_power 4
    """
    dx = 1.0 / n
    s = dt / (2.0 * dx ** dx_power)
    half = len(stencil) // 2
    eye = [1.0 if k == half else 0.0 for k in range(len(stencil))]
    return (s, [i + s * c for i, c in zip(eye, stencil)],
            [i - s * c for i, c in zip(eye, stencil)])


def ref_cn_steps(stencil, dx_power: int, n: int, dt: float,
                 field0: np.ndarray, steps: int) -> np.ndarray:
    """``steps`` float64 Crank-Nicolson steps of the columns ``field0``.

    One step is x <- A^-1 B x, with A the implicit LHS and B the explicit
    stencil; the step matrix is raised to the power ``steps`` by repeated
    squaring, so a run of any length costs a few dense products."""
    _, diags, weights = cn_operator(stencil, dx_power, n, dt)
    lhs = dense_matrix([np.full(n, d) for d in diags], True)
    stencil_matrix = dense_matrix([np.full(n, w) for w in weights], True)
    step = np.linalg.solve(lhs, stencil_matrix)
    return np.linalg.matrix_power(step, steps) @ np.asarray(field0,
                                                           np.float64)


def rel_err(got, want) -> float:
    """Largest absolute error over the largest reference magnitude."""
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
