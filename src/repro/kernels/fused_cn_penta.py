"""Beyond-paper Pallas kernel #2: fused Crank-Nicolson step for periodic 1-D
HYPERDIFFUSION (paper §IV benchmark) — 5-point stencil RHS + pentadiagonal
LR solve + rank-4 Woodbury periodic correction in ONE kernel.

Same argument as fused_cn.py: the paper's pipeline (cuSten RHS kernel ->
cuPentConstantBatch -> correction) moves ~6 N M words of HBM per time step;
fused it is ~2 N M (read C^n once, write C^{n+1} once).

Inputs per block:
    lhs_ref:  (5, N)  [eps, beta, inv_alpha, gamma, delta] of A' (SMEM)
    z_ref:    (N, 4)  Z = A'^{-1} U (Woodbury directions)
    minv_ref: (4, 4)  (I + V^T Z)^{-1} (SMEM)
    p_ref:    (1, 16) [sm2, sm1, s0, sp1, sp2,  a0, b0, a1, eN2, dN1, eN1, ...]
                      (5 CN stencil weights + 6 wrap coefficients; SMEM)
    c_ref:    (N, BLOCK_M) current field -> x_ref: (N, BLOCK_M) next field
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import default_interpret, lhs_spec, row, store_row
from .fused_cn import cn_compiler_params

EPS, BETA, INV_ALPHA, GAMMA, DELTA = range(5)


def fused_cn_penta_kernel(lhs_ref, z_ref, minv_ref, p_ref, c_ref, x_ref, *,
                          n: int, unroll: int):
    w = [p_ref[0, i] for i in range(5)]                      # stencil
    a0, b0, a1, eN2, dN1, eN1 = (p_ref[0, 5 + i] for i in range(6))

    def rhs(i):
        idx = [jnp.where(i + off < 0, i + off + n,
                         jnp.where(i + off >= n, i + off - n, i + off))
               for off in (-2, -1, 0, 1, 2)]
        acc = w[0] * row(c_ref, idx[0])
        for t in range(1, 5):
            acc = acc + w[t] * row(c_ref, idx[t])
        return acc

    # ---- forward: g_i = (rhs_i - eps_i g_{i-2} - beta_i g_{i-1}) inv_i ----
    g0 = rhs(0) * lhs_ref[INV_ALPHA, 0]
    store_row(x_ref, 0, g0)
    g1 = (rhs(1) - lhs_ref[BETA, 1] * g0) * lhs_ref[INV_ALPHA, 1]
    store_row(x_ref, 1, g1)

    def fwd(i, carry):
        gm1, gm2 = carry
        g = (rhs(i) - lhs_ref[EPS, i] * gm2
             - lhs_ref[BETA, i] * gm1) * lhs_ref[INV_ALPHA, i]
        store_row(x_ref, i, g)
        return g, gm1

    gN1, gN2 = jax.lax.fori_loop(2, n, fwd, (g1, g0), unroll=unroll)

    # ---- backward: y_i = g_i - gamma_i y_{i+1} - delta_i y_{i+2} ----------
    y_last = gN1                                             # y_{N-1}
    y_prev = gN2 - lhs_ref[GAMMA, n - 2] * y_last            # y_{N-2}
    store_row(x_ref, n - 2, y_prev)

    def bwd(k, carry):
        yp1, yp2 = carry
        i = n - 3 - k
        y_i = (row(x_ref, i) - lhs_ref[GAMMA, i] * yp1
               - lhs_ref[DELTA, i] * yp2)
        store_row(x_ref, i, y_i)
        return y_i, yp1

    y0, y1 = jax.lax.fori_loop(0, n - 2, bwd, (y_prev, y_last), unroll=unroll)
    # after the loop: y0 = y_0, y1 = y_1 (the last two computed rows)

    # ---- fused rank-4 Woodbury correction: x = y - Z (I+V^T Z)^-1 V^T y ---
    yN2 = row(x_ref, n - 2)
    yN1 = row(x_ref, n - 1)
    vty = [a0 * yN2 + b0 * yN1,
           a1 * yN1,
           eN2 * y0,
           dN1 * y0 + eN1 * y1]                              # 4 x (1, M)
    wvec = []
    for r_i in range(4):
        acc = minv_ref[r_i, 0] * vty[0]
        for c_i in range(1, 4):
            acc = acc + minv_ref[r_i, c_i] * vty[c_i]
        wvec.append(acc)
    wmat = jnp.concatenate(wvec, axis=0)                     # (4, M)
    corr = jnp.dot(z_ref[...].astype(jnp.float32), wmat,
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)       # (N, M) via MXU
    x_ref[...] = x_ref[...] - corr.astype(x_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "unroll", "interpret"))
def fused_cn_penta_pallas(lhs, z, minv, params, c, *, block_m: int = 128,
                          unroll: int = 1, interpret: bool | None = None):
    """One periodic CN hyperdiffusion time step. c: (N, M) -> (N, M).
    ``interpret=None`` compiles on a TPU and interprets elsewhere."""
    if interpret is None:
        interpret = default_interpret()
    n, m = c.shape
    col = pl.BlockSpec((n, block_m), lambda j: (0, j))
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(fused_cn_penta_kernel, n=n, unroll=unroll),
        grid=(m // block_m,),
        in_specs=[lhs_spec(5, n),
                  pl.BlockSpec((n, 4), lambda j: (0, 0)),
                  smem((4, 4), lambda j: (0, 0)),
                  smem((1, 16), lambda j: (0, 0)),
                  col],
        out_specs=col,
        out_shape=jax.ShapeDtypeStruct((n, m), c.dtype),
        interpret=interpret,
        **cn_compiler_params(interpret),
    )(lhs, z, minv, params, c)


def hbm_traffic_bytes(n: int, m: int, dtype=jnp.float32) -> dict:
    itemsize = jnp.dtype(dtype).itemsize
    return {
        "fused": (2 * n * m + 9 * n + 32) * itemsize,
        "unfused_pipeline": (6 * n * m + 9 * n + 32) * itemsize,
    }
