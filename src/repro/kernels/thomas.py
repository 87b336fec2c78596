"""Batched Thomas solvers — spec table over the sweep engine (DESIGN.md §2.2).

The hand-written kernel bodies that used to live here are now generated by
``repro.kernels.engine`` from declarative ``SweepSpec``s; this module only
names the tridiagonal family:

cuThomasConstantBatch (paper) -> ``thomas_constant_pallas``:
    * RHS block   (N, BLOCK_M) — interleaved, one system per lane.
    * LHS block   (3, N)       — a / inv_denom / c_hat stored ONCE; its
      BlockSpec index_map is constant so the same VMEM block serves every
      grid step (the broadcast-read of the paper, made explicit).

``thomas_constant_t_pallas`` solves A^T x = g from the SAME stored factor
(A = L·U -> A^T = U^T·L^T): the engine runs the identical two-pass sweep
with the scale moved to the backward pass and the host pre-shifting the
coefficient rows (c_hat_{i-1} forward, a_{i+1} backward) — the adjoint of
DESIGN.md §5.1 on the fast path.

cuThomasBatch (baseline, prior SoTA) -> ``thomas_batch_pallas``:
    * each lane owns its LHS: three (N, BLOCK_M) diagonal blocks + RHS;
      the factorisation is fused into the solve (the real cuThomasBatch
      destroys the LHS copy in-place, forcing a re-factor each step).

The sweeps are sequential in N (Thomas is inherently serial per system)
and vectorised across 128 lanes; ``unroll`` trades instruction count for
VREG pressure along the sublane axis.  ``hbm_traffic_bytes`` is derived
from the registered specs — every generated variant automatically has a
roofline entry.
"""

from __future__ import annotations

import jax.numpy as jnp

from .engine import REGISTRY, batch_solver, shared_solver, traffic_table

thomas_constant_pallas = shared_solver(REGISTRY["thomas_constant"])
thomas_constant_t_pallas = shared_solver(REGISTRY["thomas_constant_t"])
thomas_batch_pallas = batch_solver(REGISTRY["thomas_batch"])


def hbm_traffic_bytes(n: int, m: int, dtype=jnp.float32) -> dict:
    """Analytic HBM<->VMEM traffic per tridiagonal variant, derived from
    the engine's ``SweepSpec.traffic_bytes`` (itemsize from the dtype).

    Keys: ``constant`` (2NM + 3N), ``batch`` (5NM), ``constant_streamed``
    (4NM + 6N — the intermediate d_hat makes one extra HBM round trip and
    both passes re-stream the shared LHS), ``batch_streamed`` (9NM — the
    spilled c_hat and the intermediate each round-trip), plus the ``_t``
    transposed twins of the shared variants (same streams, shifted rows)
    and ``constant_packed`` (the same streams as ``constant``: the packed
    sweep relayouts in VMEM).
    """
    return traffic_table(3, n, m, dtype)
