"""Pre-factorised Thomas solver with a single shared LHS (paper §III).

Layout convention (the paper's *interleaved* format): RHS batches are
``(N, M)`` — unknown index ``i`` major, system index ``m`` minor — so each
sweep step touches a contiguous ``(M,)`` slab. The factored LHS is three
``(N,)`` vectors stored **once** (constant mode) or three ``(N, M)`` arrays
(per-system baseline, cuThomasBatch-equivalent); both flow through the same
code path via broadcasting.

Factored form (storage O(3N) — matches the paper's O(3N + MN) total):
    a         : sub-diagonal (a[0] unused, forced to 0)
    inv_denom : 1 / (b_i - a_i * c_hat_{i-1})      (inv_denom[0] = 1/b_0)
    c_hat     : c_i * inv_denom_i                   (c_hat[N-1] unused)

Solve:
    forward   d_hat_i = (d_i - a_i d_hat_{i-1}) * inv_denom_i
    backward  x_i     = d_hat_i - c_hat_i * x_{i+1}

Note: the paper's Eq. (6) prints ``x_i = d̂_i − a_i ĉ_i``; the standard (and
reference-implementation) back-substitution is ``x_i = d̂_i − ĉ_i x_{i+1}``,
which is what we implement.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import scopes

from .recurrence import _align, linear_recurrence


class TridiagFactor(NamedTuple):
    a: jax.Array          # (N,) or (N, M)
    inv_denom: jax.Array  # (N,) or (N, M)
    c_hat: jax.Array      # (N,) or (N, M)


class PeriodicTridiagFactor(NamedTuple):
    factor: TridiagFactor  # factor of the Sherman-Morrison core matrix A'
    z: jax.Array           # A'^{-1} u, shape (N,) or (N, M)
    v_last: jax.Array      # a_0 / gamma (v = e_0 + v_last * e_{N-1})
    inv_denom_sm: jax.Array  # 1 / (1 + v . z)
    zt: jax.Array          # A'^{-T} v — the adjoint's corner aux, same (N,)


def thomas_factor(a: jax.Array, b: jax.Array, c: jax.Array, *,
                  method: str = "scan", unroll: int = 1) -> TridiagFactor:
    """Pre-factorisation (paper Eqs. 1-2). a, b, c: (N,) shared or (N, M)."""
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    c = jnp.asarray(c)
    a = a.at[0].set(0)  # a_0 is outside the matrix

    if method == "scan":
        def step(c_hat_prev, abc):
            a_i, b_i, c_i = abc
            inv = 1.0 / (b_i - a_i * c_hat_prev)
            c_hat_i = c_i * inv
            return c_hat_i, (inv, c_hat_i)

        init = jnp.zeros_like(b[0])
        _, (inv_denom, c_hat) = jax.lax.scan(step, init, (a, b, c), unroll=unroll)
        return TridiagFactor(a=a, inv_denom=inv_denom, c_hat=c_hat)

    if method == "assoc":
        # NOTE: factorisation runs ONCE per operator (the paper's whole point),
        # so the parallel form is provided for completeness only; the running
        # determinant ``den`` grows like prod(denom_i) and can overflow for
        # large N — prefer method="scan" for factorisation, use "assoc" for
        # the per-step *solve* sweeps (whose coefficients |p| < 1 when
        # diagonally dominant).
        # c_hat_i = c_i / (b_i - a_i c_hat_{i-1}) is a Möbius recurrence; track
        # it as a ratio c_hat_i = num_i / den_i with the 2x2 companion form:
        #   (num_i, den_i) = [[0, c_i], [-a_i, b_i]] @ (num_{i-1}, den_{i-1})
        zero = jnp.zeros_like(b)
        A = jnp.stack(
            [jnp.stack([zero, c], axis=1), jnp.stack([-a, b], axis=1)], axis=1
        )  # (N, 2, 2)

        def matmul2(Y, X):  # combine(earlier=X? see below)
            return jnp.einsum("...ij,...jk->...ik", Y, X)

        def combine(fst, snd):
            return matmul2(snd, fst)

        P = jax.lax.associative_scan(combine, A, axis=0)
        num = P[:, 0, 0] * 0 + P[:, 0, 1]  # applied to (num,den)=(0,1)
        den = P[:, 1, 1]
        c_hat = num / den
        # denom_i = b_i - a_i c_hat_{i-1} = den_i / den_{i-1}
        den_prev = jnp.concatenate([jnp.ones_like(den[:1]), den[:-1]], axis=0)
        inv_denom = den_prev / den
        return TridiagFactor(a=a, inv_denom=inv_denom, c_hat=c_hat)

    raise ValueError(f"unknown method {method!r}")


def thomas_solve(f: TridiagFactor, d: jax.Array, *,
                 method: str = "scan", unroll: int = 1) -> jax.Array:
    """Solve A x = d given the factorisation. d: (N,) or (N, M...)."""
    d = jnp.asarray(d)
    a = _align(f.a, d)
    inv_denom = _align(f.inv_denom, d)
    c_hat = _align(f.c_hat, d)

    # forward sweep: d_hat_i = (-a_i inv_i) d_hat_{i-1} + d_i inv_i
    d_hat = linear_recurrence(-a * inv_denom, d * inv_denom,
                              method=method, unroll=unroll)
    # backward sweep: x_i = (-c_hat_i) x_{i+1} + d_hat_i
    x = linear_recurrence(-c_hat, d_hat, reverse=True,
                          method=method, unroll=unroll)
    return x


def thomas_solve_t(f: TridiagFactor, g: jax.Array, *,
                   method: str = "scan", unroll: int = 1) -> jax.Array:
    """Solve the TRANSPOSED system A^T x = g from the SAME factorisation.

    The stored factor is A = L U (L lower bidiagonal with diagonal
    ``1/inv_denom`` and sub-diagonal ``a``; U unit upper bidiagonal with
    super-diagonal ``c_hat``), so A^T = U^T L^T needs no second factor —
    the adjoint of every forward solve reuses the forward's O(3N) storage:

        U^T y = g :  y_i = g_i - c_hat_{i-1} y_{i-1}
        L^T x = y :  x_i = (y_i - a_{i+1} x_{i+1}) * inv_denom_i
    """
    g = jnp.asarray(g)
    a = _align(f.a, g)
    inv_denom = _align(f.inv_denom, g)
    c_hat = _align(f.c_hat, g)

    zero = jnp.zeros_like(c_hat[:1])
    c_hat_prev = jnp.concatenate([zero, c_hat[:-1]], axis=0)   # c_hat_{i-1}
    a_next = jnp.concatenate([a[1:], zero], axis=0)            # a_{i+1}

    y = linear_recurrence(-c_hat_prev, g, method=method, unroll=unroll)
    x = linear_recurrence(-a_next * inv_denom, y * inv_denom,
                          reverse=True, method=method, unroll=unroll)
    return x


def thomas_factor_solve(a, b, c, d, *, method: str = "scan") -> jax.Array:
    """Fused factor+solve (cuThomasBatch semantics: the baseline re-factors on
    every call because its in-place sweeps destroy the LHS copy)."""
    return thomas_solve(thomas_factor(a, b, c, method=method), d, method=method)


# ---------------------------------------------------------------------------
# Periodic boundaries — Sherman-Morrison, paper §III.C (rank-1, paper-faithful)
# ---------------------------------------------------------------------------

def periodic_thomas_factor(a: jax.Array, b: jax.Array, c: jax.Array, *,
                           method: str = "scan") -> PeriodicTridiagFactor:
    """Factor the periodic tridiagonal system (corner entries A[0,N-1] = a_0,
    A[N-1,0] = c_{N-1}) via Sherman-Morrison:  A = A' + u v^T with
        gamma = -b_0,  u = gamma e_0 + c_{N-1} e_{N-1},
        v = e_0 + (a_0/gamma) e_{N-1},
        A'[0,0] = b_0 - gamma = 2 b_0,
        A'[N-1,N-1] = b_{N-1} - c_{N-1} a_0 / gamma.
    The solve of A' z = u happens once here ("need only be performed once at
    the beginning of a given simulation" — paper).
    """
    a = jnp.asarray(a)
    b = jnp.asarray(b)
    c = jnp.asarray(c)
    gamma = -b[0]
    b_mod = b.at[0].add(-gamma).at[-1].add(-c[-1] * a[0] / gamma)
    f = thomas_factor(a, b_mod, c, method=method)

    u = jnp.zeros_like(b).at[0].set(gamma).at[-1].set(c[-1])
    z = thomas_solve(f, u, method=method)
    v_last = a[0] / gamma
    v_dot_z = z[0] + v_last * z[-1]
    # the adjoint's auxiliary solve A'^{-T} v, also once per operator (the
    # backward pass of every solve reuses it, like the forward reuses z)
    v = jnp.zeros_like(b).at[0].set(1.0).at[-1].set(v_last)
    zt = thomas_solve_t(f, v, method=method)
    return PeriodicTridiagFactor(
        factor=f, z=z, v_last=v_last, inv_denom_sm=1.0 / (1.0 + v_dot_z),
        zt=zt,
    )


def periodic_corner_correction(pf: PeriodicTridiagFactor,
                               y: jax.Array) -> jax.Array:
    """Sherman-Morrison corner step on y = A'^{-1} d (paper Eq. 15):
        x = y - (v.y / (1 + v.z)) z.
    Shared by the reference solve below and the ``pallas`` backend's
    kernel-produced y."""
    with jax.named_scope(scopes.CORNER_CORRECTION):
        v_dot_y = y[0] + pf.v_last * y[-1]          # (M,) for batched y
        corr = v_dot_y * pf.inv_denom_sm
        z = _align(pf.z, y) if pf.z.ndim < y.ndim else pf.z
        return y - corr * z


def periodic_thomas_solve(pf: PeriodicTridiagFactor, d: jax.Array, *,
                          method: str = "scan", unroll: int = 1) -> jax.Array:
    """x = y - (v.y / (1 + v.z)) z  with  y = A'^{-1} d  (paper Eq. 15)."""
    y = thomas_solve(pf.factor, d, method=method, unroll=unroll)
    return periodic_corner_correction(pf, y)


def periodic_corner_correction_t(pf: PeriodicTridiagFactor,
                                 y: jax.Array) -> jax.Array:
    """Transposed Sherman-Morrison corner step on y = A'^{-T} g.

    A = A' + u v^T, so A^T = A'^T + v u^T and Sherman-Morrison gives
        x = y - (u . y) / (1 + u . w) * w,
    with w = A'^{-T} v = ``pf.zt`` (solved once at factor time, exactly
    like the forward's z).  The denominator 1 + u.w = 1 + v.z is the
    stored ``inv_denom_sm`` (scalar transpose); and u is recovered from
    the factor itself (gamma = -b_0 = -1/(2 inv_denom_0), c_{N-1} =
    c_hat_{N-1} / inv_denom_{N-1}) — no second LHS copy anywhere in the
    adjoint.  Shared by the reference transposed solve below and the
    ``pallas`` backend, whose kernels produce the same y — ONE home for
    the factor-convention algebra.
    """
    f = pf.factor
    with jax.named_scope(scopes.CORNER_CORRECTION):
        gamma = -0.5 / f.inv_denom[0]
        c_last = f.c_hat[-1] / f.inv_denom[-1]
        u_dot_y = gamma * y[0] + c_last * y[-1]
        corr = u_dot_y * pf.inv_denom_sm
        zt = _align(pf.zt, y) if pf.zt.ndim < y.ndim else pf.zt
        return y - corr * zt


def periodic_thomas_solve_t(pf: PeriodicTridiagFactor, g: jax.Array, *,
                            method: str = "scan", unroll: int = 1) -> jax.Array:
    """Transposed periodic solve A^T x = g from the SAME stored factor
    (see ``periodic_corner_correction_t`` for the corner algebra)."""
    y = thomas_solve_t(pf.factor, g, method=method, unroll=unroll)
    return periodic_corner_correction_t(pf, y)


def dense_tridiag(a, b, c, periodic: bool = False) -> jax.Array:
    """Materialise the (N, N) matrix — test oracle only."""
    a = jnp.asarray(a); b = jnp.asarray(b); c = jnp.asarray(c)
    n = b.shape[0]
    A = jnp.diag(b) + jnp.diag(a[1:], -1) + jnp.diag(c[:-1], 1)
    if periodic:
        A = A.at[0, n - 1].add(a[0]).at[n - 1, 0].add(c[-1])
    return A
