"""Pentadiagonal LR solver with a single shared LHS (paper §IV).

Diagonals follow the paper's naming for the matrix rows
    a_i x_{i-2} + b_i x_{i-1} + c_i x_i + d_i x_{i+1} + e_i x_{i+2} = f_i
(0-based here; a_0 = a_1 = b_0 = 0 and d_{N-1} = e_{N-2} = e_{N-1} = 0 are
outside the matrix and forced to zero).

Factored form A = L R (Engeln-Müllges & Uhlig; storage O(5N) — the paper's
O(5N + MN) total; the *uniform* variant drops eps for O(4N + MN)):
    eps       = a                       (L sub-sub diagonal)
    beta      (L sub diagonal)
    inv_alpha = 1/alpha                 (L diagonal, stored inverted)
    gamma     (R super diagonal)
    delta     (R super-super diagonal)

Solve:
    L g = f :  g_i = (f_i - eps_i g_{i-2} - beta_i g_{i-1}) * inv_alpha_i
    R x = g :  x_i = g_i - gamma_i x_{i+1} - delta_i x_{i+2}

Periodic boundaries use a rank-4 Woodbury correction (the periodic
pentadiagonal matrix has 2x2 corner blocks, each full-rank, so rank 4 is the
minimum; see DESIGN.md). Like the paper's Sherman-Morrison step, the four
auxiliary solves A' Z = U happen once per operator and are shared by every
system in the batch.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .recurrence import _align, linear_recurrence2


class PentaFactor(NamedTuple):
    eps: jax.Array        # (N,) / (N, M) — equals a; scalar 0-d for uniform mode
    beta: jax.Array
    inv_alpha: jax.Array
    gamma: jax.Array
    delta: jax.Array


class PeriodicPentaFactor(NamedTuple):
    factor: PentaFactor
    Z: jax.Array          # (N, 4)  A'^{-1} U
    Minv: jax.Array       # (4, 4)  (I + V^T Z)^{-1}
    vcoef: jax.Array      # (6,) corner coefficients [a0, b0, a1, eN2, dN1, eN1]
    Zt: jax.Array         # (N, 4)  A'^{-T} V — the adjoint's corner aux


def penta_factor(a, b, c, d, e, *, unroll: int = 1) -> PentaFactor:
    """LR factorisation (paper §IV.A steps 1-14), single ``lax.scan``."""
    a = jnp.asarray(a); b = jnp.asarray(b); c = jnp.asarray(c)
    d = jnp.asarray(d); e = jnp.asarray(e)
    # entries outside the band (wrap entries of the periodic problem) are not
    # part of the core matrix — force them to zero for robustness.
    a = a.at[jnp.array([0, 1])].set(0)
    b = b.at[0].set(0)
    d = d.at[-1].set(0)
    e = e.at[jnp.array([-2, -1])].set(0)

    def step(carry, abcde):
        g1, g2, d1, d2 = carry  # gamma_{i-1}, gamma_{i-2}, delta_{i-1}, delta_{i-2}
        a_i, b_i, c_i, d_i, e_i = abcde
        beta_i = b_i - a_i * g2
        alpha_i = c_i - a_i * d2 - beta_i * g1
        inv_i = 1.0 / alpha_i
        gamma_i = (d_i - beta_i * d1) * inv_i
        delta_i = e_i * inv_i
        return (gamma_i, g1, delta_i, d1), (beta_i, inv_i, gamma_i, delta_i)

    zero = jnp.zeros_like(c[0])
    _, (beta, inv_alpha, gamma, delta) = jax.lax.scan(
        step, (zero, zero, zero, zero), (a, b, c, d, e), unroll=unroll
    )
    # entries beyond the band are mathematically unused; zero them so storage
    # accounting and the uniform variant stay exact.
    gamma = gamma.at[-1].set(0)
    delta = delta.at[jnp.array([-2, -1])].set(0)
    return PentaFactor(eps=a, beta=beta, inv_alpha=inv_alpha, gamma=gamma, delta=delta)


def penta_solve(f: PentaFactor, rhs: jax.Array, *,
                method: str = "scan", unroll: int = 1) -> jax.Array:
    """Solve A x = rhs given the LR factorisation. rhs: (N,) or (N, M...)."""
    rhs = jnp.asarray(rhs)
    eps = _align(jnp.broadcast_to(f.eps, f.beta.shape), rhs)
    beta = _align(f.beta, rhs)
    inv_alpha = _align(f.inv_alpha, rhs)
    gamma = _align(f.gamma, rhs)
    delta = _align(f.delta, rhs)

    # L g = f : g_i = (-beta_i inv_i) g_{i-1} + (-eps_i inv_i) g_{i-2} + f_i inv_i
    g = linear_recurrence2(-beta * inv_alpha, -eps * inv_alpha, rhs * inv_alpha,
                           method=method, unroll=unroll)
    # R x = g : x_i = (-gamma_i) x_{i+1} + (-delta_i) x_{i+2} + g_i
    x = linear_recurrence2(-gamma, -delta, g, reverse=True,
                           method=method, unroll=unroll)
    return x


def penta_solve_t(f: PentaFactor, g: jax.Array, *,
                  method: str = "scan", unroll: int = 1) -> jax.Array:
    """Solve the TRANSPOSED system A^T x = g from the SAME LR factorisation.

    A = L R (L: diagonal 1/inv_alpha, sub beta, sub-sub eps; R: unit diagonal,
    super gamma, super-super delta), so A^T = R^T L^T reuses the stored O(5N)
    factor — no transposed refactorisation:

        R^T y = g :  y_i = g_i - gamma_{i-1} y_{i-1} - delta_{i-2} y_{i-2}
        L^T x = y :  x_i = (y_i - beta_{i+1} x_{i+1} - eps_{i+2} x_{i+2})
                           * inv_alpha_i

    ``f.eps`` must be vector-shaped here (expand uniform-mode factors with
    ``repro.solver.reference.expand_uniform`` first, exactly as for the
    forward solve).
    """
    g = jnp.asarray(g)
    eps = _align(jnp.broadcast_to(f.eps, f.beta.shape), g)
    beta = _align(f.beta, g)
    inv_alpha = _align(f.inv_alpha, g)
    gamma = _align(f.gamma, g)
    delta = _align(f.delta, g)

    zero1 = jnp.zeros_like(gamma[:1])
    zero2 = jnp.zeros_like(gamma[:2])
    gamma_prev = jnp.concatenate([zero1, gamma[:-1]], axis=0)   # gamma_{i-1}
    delta_prev2 = jnp.concatenate([zero2, delta[:-2]], axis=0)  # delta_{i-2}
    beta_next = jnp.concatenate([beta[1:], zero1], axis=0)      # beta_{i+1}
    eps_next2 = jnp.concatenate([eps[2:], zero2], axis=0)       # eps_{i+2}

    y = linear_recurrence2(-gamma_prev, -delta_prev2, g,
                           method=method, unroll=unroll)
    x = linear_recurrence2(-beta_next * inv_alpha, -eps_next2 * inv_alpha,
                           y * inv_alpha, reverse=True,
                           method=method, unroll=unroll)
    return x


def penta_factor_solve(a, b, c, d, e, rhs, *, method: str = "scan") -> jax.Array:
    """Fused factor+solve (cuPentBatch semantics — re-factors every call)."""
    return penta_solve(penta_factor(a, b, c, d, e), rhs, method=method)


# ---------------------------------------------------------------------------
# Periodic boundaries — rank-4 Woodbury
# ---------------------------------------------------------------------------

def _vty(vcoef: jax.Array, y: jax.Array) -> jax.Array:
    """V^T y for the rank-4 corner correction. y: (N,) or (N, M) -> (4,) / (4, M)."""
    a0, b0, a1, eN2, dN1, eN1 = vcoef
    return jnp.stack(
        [
            a0 * y[-2] + b0 * y[-1],   # v_0: row-0 wrap entries at cols N-2, N-1
            a1 * y[-1],                # v_1: row-1 wrap entry  at col  N-1
            eN2 * y[0],                # v_2: row-(N-2) wrap    at col  0
            dN1 * y[0] + eN1 * y[1],   # v_3: row-(N-1) wraps   at cols 0, 1
        ],
        axis=0,
    )


def periodic_penta_factor(a, b, c, d, e) -> PeriodicPentaFactor:
    """Factor the periodic pentadiagonal operator.

    Corner entries of the periodic matrix P (0-based):
        P[0, N-2] = a_0, P[0, N-1] = b_0, P[1, N-1] = a_1,
        P[N-2, 0] = e_{N-2}, P[N-1, 0] = d_{N-1}, P[N-1, 1] = e_{N-1}.
    P = A' + U V^T with U = [e_0, e_1, e_{N-2}, e_{N-1}] and V as in ``_vty``
    (disjoint row/column supports -> A' is the plain truncated band, no
    diagonal modification, preserving diagonal dominance).
    """
    a = jnp.asarray(a); b = jnp.asarray(b); c = jnp.asarray(c)
    d = jnp.asarray(d); e = jnp.asarray(e)
    n = c.shape[0]
    vcoef = jnp.stack([a[0], b[0], a[1], e[-2], d[-1], e[-1]])

    f = penta_factor(a, b, c, d, e)
    U = jnp.zeros((n, 4), c.dtype)
    U = U.at[0, 0].set(1.0).at[1, 1].set(1.0).at[-2, 2].set(1.0).at[-1, 3].set(1.0)
    Z = penta_solve(f, U)                      # (N, 4)
    M4 = jnp.eye(4, dtype=c.dtype) + _vty(vcoef, Z)  # (4, 4)
    # the adjoint's auxiliary solves A'^{-T} V, also once per operator
    Zt = penta_solve_t(f, _corner_V(vcoef, n))       # (N, 4)
    # the TPU's default matmul precision is one bf16 pass
    with jax.default_matmul_precision("highest"):
        minv = jnp.linalg.inv(M4)
    return PeriodicPentaFactor(factor=f, Z=Z, Minv=minv, vcoef=vcoef, Zt=Zt)


#: The corner corrections' small matmuls run at full fp32 precision: at
#: the TPU's default (one bf16 pass) they would cost ~3 significant digits.
_HIGHEST = jax.lax.Precision.HIGHEST


def periodic_corner_correction(pf: PeriodicPentaFactor,
                               y: jax.Array) -> jax.Array:
    """Rank-4 Woodbury corner step on y = A'^{-1} rhs:
    x = y - Z (I + V^T Z)^{-1} V^T y.  Shared by the reference solve below
    and the ``pallas`` backend's kernel-produced y."""
    w = jnp.matmul(pf.Minv, _vty(pf.vcoef, y), precision=_HIGHEST)
    return y - jnp.tensordot(pf.Z, w, axes=([1], [0]), precision=_HIGHEST)


def periodic_penta_solve(pf: PeriodicPentaFactor, rhs: jax.Array, *,
                         method: str = "scan", unroll: int = 1) -> jax.Array:
    """x = y - Z (I + V^T Z)^{-1} V^T y  with  y = A'^{-1} rhs."""
    y = penta_solve(pf.factor, rhs, method=method, unroll=unroll)
    return periodic_corner_correction(pf, y)


def _corner_V(vcoef: jax.Array, n: int) -> jax.Array:
    """Materialise V (N, 4) of the rank-4 correction P = A' + U V^T."""
    a0, b0, a1, eN2, dN1, eN1 = vcoef
    V = jnp.zeros((n, 4), vcoef.dtype)
    return (V.at[-2, 0].set(a0).at[-1, 0].set(b0)
             .at[-1, 1].set(a1)
             .at[0, 2].set(eN2)
             .at[0, 3].set(dN1).at[1, 3].set(eN1))


def periodic_corner_correction_t(pf: PeriodicPentaFactor,
                                 y: jax.Array) -> jax.Array:
    """Transposed rank-4 Woodbury corner step on y = A'^{-T} g.

    P = A' + U V^T, so P^T = A'^T + V U^T and Woodbury gives
        x = y - Zt (I + U^T A'^{-T} V)^{-1} U^T y,
    with Zt = A'^{-T} V (solved once at factor time, like the forward's
    Z).  Since U^T A'^{-T} V = (V^T Z)^T, the 4x4 inverse is just the
    stored ``Minv`` transposed — the adjoint needs no second LHS.  Shared
    by the reference transposed solve below and the ``pallas`` backend's
    kernel-produced y — ONE home for the corner algebra.
    """
    uty = jnp.stack([y[0], y[1], y[-2], y[-1]], axis=0)            # U^T y
    h = jnp.matmul(pf.Minv.T, uty, precision=_HIGHEST)
    return y - jnp.tensordot(pf.Zt, h, axes=([1], [0]), precision=_HIGHEST)


def periodic_penta_solve_t(pf: PeriodicPentaFactor, g: jax.Array, *,
                           method: str = "scan", unroll: int = 1) -> jax.Array:
    """Transposed periodic penta solve P^T x = g from the SAME stored
    factor (see ``periodic_corner_correction_t`` for the corner algebra)."""
    y = penta_solve_t(pf.factor, g, method=method, unroll=unroll)
    return periodic_corner_correction_t(pf, y)


def dense_penta(a, b, c, d, e, periodic: bool = False) -> jax.Array:
    """Materialise the (N, N) matrix — test oracle only."""
    a = jnp.asarray(a); b = jnp.asarray(b); c = jnp.asarray(c)
    d = jnp.asarray(d); e = jnp.asarray(e)
    n = c.shape[0]
    A = (jnp.diag(c) + jnp.diag(b[1:], -1) + jnp.diag(a[2:], -2)
         + jnp.diag(d[:-1], 1) + jnp.diag(e[:-2], 2))
    if periodic:
        A = (A.at[0, n - 2].add(a[0]).at[0, n - 1].add(b[0])
              .at[1, n - 1].add(a[1])
              .at[n - 2, 0].add(e[n - 2])
              .at[n - 1, 0].add(d[n - 1]).at[n - 1, 1].add(e[n - 1]))
    return A
