"""Batched pentadiagonal LR solvers (paper §IV) — engine spec table.

Generated by ``repro.kernels.engine`` from declarative ``SweepSpec``s —
only the pentadiagonal family naming lives here:

cuPentConstantBatch -> ``penta_constant_pallas``: shared (5, N) factored
LHS [eps, beta, inv_alpha, gamma, delta] in one VMEM-resident block;
interleaved (N, BLOCK_M) RHS, one system per lane.

cuPentUniformBatch -> ``penta_uniform_pallas``: all diagonal entries
equal (paper §IV.C), so the eps vector degenerates to one value and the
LHS drops to (4, N).  eps rides in as a (1, 1) ARRAY operand — not a
Python float closed over by the kernel — so a traced ``Factorization``
leaf can feed it and ``jax.jit(solve)`` never hits a
``ConcretizationTypeError``.

cuPentBatch (baseline) -> ``penta_batch_pallas``: five (N, BLOCK_M)
per-lane diagonal blocks, factorisation fused into every solve.

``penta_constant_t_pallas`` / ``penta_uniform_t_pallas`` solve the
TRANSPOSED system from the SAME LR factor (A = L·R -> A^T = R^T·L^T):
identical engine sweeps with the inv_alpha scale moved to the backward
pass and the coefficient rows host-shifted (gamma_{i-1}/delta_{i-2}
forward, beta_{i+1}/eps_{i+2} backward) — DESIGN.md §5.1's adjoint.

Row indices of the stacked constant LHS are kept for reference:
``EPS, BETA, INV_ALPHA, GAMMA, DELTA = range(5)`` (the uniform stack
drops the EPS row).
"""

from __future__ import annotations

import jax.numpy as jnp

from .engine import REGISTRY, batch_solver, shared_solver, traffic_table

# row indices in the stacked constant LHS
EPS, BETA, INV_ALPHA, GAMMA, DELTA = range(5)

penta_constant_pallas = shared_solver(REGISTRY["penta_constant"])
penta_uniform_pallas = shared_solver(REGISTRY["penta_uniform"])
penta_constant_t_pallas = shared_solver(REGISTRY["penta_constant_t"])
penta_uniform_t_pallas = shared_solver(REGISTRY["penta_uniform_t"])
penta_batch_pallas = batch_solver(REGISTRY["penta_batch"])


def hbm_traffic_bytes(n: int, m: int, dtype=jnp.float32) -> dict:
    """Analytic HBM<->VMEM traffic per pentadiagonal variant, derived from
    the engine's ``SweepSpec.traffic_bytes``: ``constant`` (2NM + 5N),
    ``uniform`` (2NM + 4N + 1), ``batch`` (7NM), the ``_streamed``
    variants (two passes: one extra intermediate round trip, re-streamed
    LHS; batch additionally round-trips the spilled gamma/delta = 13NM),
    the ``_t`` transposed twins of the shared variants, and the
    ``_packed`` variants (the same streams as their row siblings)."""
    return traffic_table(5, n, m, dtype)
