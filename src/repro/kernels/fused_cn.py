"""Beyond-paper Pallas kernel: fused Crank-Nicolson step for periodic 1-D
diffusion (stencil RHS + Thomas solve + Sherman-Morrison correction in ONE
kernel).

The paper's pipeline is:  cuSten stencil kernel (writes RHS to RAM) ->
cuThomasConstantBatch (reads RHS, writes y) -> S-M correction (reads y,
writes x): ~6 N M words of HBM traffic per time step. Fusing the three into
one kernel the field is read once and the result written once: ~2 N M words
(a predicted ~3x reduction of the memory-roofline term; see EXPERIMENTS.md
§Perf for the accounting).

Inputs per block:
    lhs_ref: (3, N)  [a, inv_denom, c_hat] of the S-M core matrix A' (SMEM)
    z_ref:   (N, 1)  z = A'^{-1} u (periodic correction direction)
    p_ref:   (1, 8)  scalars [sl, sc, sr, v_last, inv_denom_sm, 0, 0, 0]
                     (SMEM)
                     (sl, sc, sr) = explicit CN stencil (sigma, 1-2sigma, sigma)
    c_ref:   (N, BLOCK_M) current field C^n (interleaved)
    x_ref:   (N, BLOCK_M) -> C^{n+1}
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import VMEM_LIMIT_BYTES, default_interpret, lhs_spec, row, \
    store_row


#: The kernel's name in traces and Mosaic dumps (``pallas_call(name=...)``).
NAME = "fused_cn_tridiag"


def fused_cn_tridiag_kernel(lhs_ref, z_ref, p_ref, c_ref, x_ref, *,
                            n: int, unroll: int):
    sl = p_ref[0, 0]
    sc = p_ref[0, 1]
    sr = p_ref[0, 2]
    v_last = p_ref[0, 3]
    inv_sm = p_ref[0, 4]

    def rhs(i):
        # periodic 3-point stencil, all rows VMEM-resident
        im1 = jnp.where(i == 0, n - 1, i - 1)
        ip1 = jnp.where(i == n - 1, 0, i + 1)
        return (sl * row(c_ref, im1) + sc * row(c_ref, i)
                + sr * row(c_ref, ip1))

    # forward sweep of A' (d_hat stored into x_ref)
    dh = rhs(0) * lhs_ref[1, 0]
    store_row(x_ref, 0, dh)

    def fwd(i, dh_prev):
        a_i = lhs_ref[0, i]
        inv_i = lhs_ref[1, i]
        dh_i = (rhs(i) - a_i * dh_prev) * inv_i
        store_row(x_ref, i, dh_i)
        return dh_i

    y_last = jax.lax.fori_loop(1, n, fwd, dh, unroll=unroll)  # y_{N-1}

    # backward sweep -> y in x_ref
    def bwd(k, x_next):
        i = n - 2 - k
        y_i = row(x_ref, i) - lhs_ref[2, i] * x_next
        store_row(x_ref, i, y_i)
        return y_i

    y0 = jax.lax.fori_loop(0, n - 1, bwd, y_last, unroll=unroll)  # y_0

    # fused Sherman-Morrison correction: x = y - ((v.y) / (1 + v.z)) z
    corr = (y0 + v_last * y_last) * inv_sm          # (1, BLOCK_M)
    x_ref[...] = x_ref[...] - corr * z_ref[...]


@functools.partial(jax.jit, static_argnames=("block_m", "unroll", "interpret"))
def fused_cn_tridiag_pallas(lhs, z, params, c, *, block_m: int = 128,
                            unroll: int = 1, interpret: bool | None = None):
    """One periodic CN diffusion time step. c: (N, M) -> (N, M).
    ``interpret=None`` compiles on a TPU and interprets elsewhere."""
    if interpret is None:
        interpret = default_interpret()
    n, m = c.shape
    col = pl.BlockSpec((n, block_m), lambda j: (0, j))
    return pl.pallas_call(
        functools.partial(fused_cn_tridiag_kernel, n=n, unroll=unroll),
        grid=(m // block_m,),
        in_specs=[lhs_spec(3, n),
                  pl.BlockSpec((n, 1), lambda j: (0, 0)),
                  pl.BlockSpec((1, 8), lambda j: (0, 0),
                               memory_space=pltpu.SMEM),
                  col],
        out_specs=col,
        out_shape=jax.ShapeDtypeStruct((n, m), c.dtype),
        interpret=interpret,
        name=NAME,
        **cn_compiler_params(interpret),
    )(lhs, z, params, c)


def cn_compiler_params(interpret: bool) -> dict:
    """Mosaic knobs of the fused CN step kernels: independent lane tiles,
    and the scoped-VMEM limit the fit checks budget against."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel",),
        vmem_limit_bytes=VMEM_LIMIT_BYTES)}


def hbm_traffic_bytes(n: int, m: int, dtype=jnp.float32) -> dict:
    """Fused vs the paper's 3-kernel pipeline (per CN step)."""
    itemsize = jnp.dtype(dtype).itemsize
    return {
        "fused": (2 * n * m + 4 * n + 8) * itemsize,
        "unfused_pipeline": (6 * n * m + 4 * n + 8) * itemsize,
    }
