"""First-order / second-order linear recurrence engine.

This is the computational primitive shared by:
  * the Thomas tridiagonal sweeps   (h_i = p_i h_{i-1} + q_i),
  * the pentadiagonal LR sweeps     (h_i = s_i h_{i-1} + t_i h_{i-2} + u_i),
  * the SSM layers (Mamba-2 SSD inter-chunk state scan, RG-LRU) in
    ``repro.models`` — the paper's "single shared LHS, many interleaved RHS"
    pattern shows up here as shared (N,)-shaped coefficients broadcast across a
    batch of (N, M)-shaped operands.

Execution strategies (the ``method`` dispatch):
  * ``"scan"``   — sequential ``lax.scan`` (work-optimal, O(N) depth).
  * ``"assoc"``  — ``lax.associative_scan`` (O(log N) depth, ~2x work),
    the TPU analogue of parallel cyclic reduction for long N.
  * ``"pallas"`` — the engine-generated gated-recurrence Pallas kernels
    (``repro.kernels.engine.RecurrenceSpec``): the recurrence rides the
    same sweep machine as the banded solvers, with VMEM-aware lane/chunk
    tuning (``_auto_blocks``, reusing ``kernels.common``'s budget model)
    and a ``custom_vjp`` running the ADJOINT recurrence on the same
    kernels (the reverse sweep with gates shifted by one lag — exactly
    the transposed-solver trick of DESIGN.md §5.1 applied to gates).
  * ``"auto"``   — ``"pallas"`` for floating-point operands, ``"scan"``
    otherwise.  This is the policy the sequence models use.

All methods share one dtype/broadcast contract: coefficients are (N,),
or broadcastable against the operand (singleton dims allowed), the
computation runs in ``jnp.result_type`` of the inputs (so bf16 operands
with fp32 gates run fp32 — the models' fp32-carry convention), ``h0``
seeds the incoming carry state, and ``reverse=True`` runs from i = N-1
down to 0.  The parity across methods is pinned by
``tests/test_recurrence.py``'s (method x reverse x h0 x dtype) sweep.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

METHODS = ("scan", "assoc", "pallas", "auto")


def _align(coef: jax.Array, ref: jax.Array) -> jax.Array:
    """Right-pad ``coef`` with singleton dims so it broadcasts against ``ref``.

    ``coef`` has shape (N,) (shared coefficients — the paper's constant-LHS
    case) or broadcasts against ``ref.shape`` (per-system coefficients,
    singleton dims allowed — the SSD inter-chunk decay is (N, B, H, 1, 1)).
    """
    coef = jnp.asarray(coef)
    if coef.ndim == ref.ndim:
        return coef
    if coef.ndim != 1:
        raise ValueError(f"coefficient rank {coef.ndim} vs operand rank {ref.ndim}")
    return coef.reshape(coef.shape + (1,) * (ref.ndim - 1))


def _resolve(method: str, dtype) -> str:
    """The auto policy: Pallas serves every floating recurrence (interpret
    mode off-TPU); integer/bool recurrences stay on the XLA scan."""
    if method not in METHODS:
        raise ValueError(f"unknown method {method!r}; valid: {METHODS}")
    if method != "auto":
        return method
    return "pallas" if jnp.issubdtype(dtype, jnp.floating) else "scan"


def _shift_up(v: jax.Array, k: int) -> jax.Array:
    """Row i reads v at i+k (zeros shift in at the bottom)."""
    return jnp.concatenate([v[k:], jnp.zeros_like(v[:k])], axis=0)


def _shift_down(v: jax.Array, k: int) -> jax.Array:
    """Row i reads v at i-k (zeros shift in at the top)."""
    return jnp.concatenate([jnp.zeros_like(v[:k]), v[:-k]], axis=0)


# ---------------------------------------------------------------------------
# Pallas dispatch: block tuning + flattening onto the (N, M) kernel layout
# ---------------------------------------------------------------------------

_BLOCK_M_CANDIDATES = (1024, 512, 256, 128)
_BLOCK_N_CANDIDATES = (2048, 1024, 512, 256)


def _auto_blocks(order: int, n: int, m: int, itemsize: int) -> tuple:
    """(block_m, block_n) for an order-``order`` recurrence over an (n, m)
    batch: the largest resident lane tile whose working set fits the VMEM
    budget (``block_n=None``), else the streamed split-N kernel at the
    largest chunk that fits — the same budget model as the banded solvers
    (``kernels.common``), with the counts derived from the registered
    ``RecurrenceSpec``."""
    from repro.kernels import common as kcommon
    from repro.kernels.engine import find_recurrence_spec
    resident = find_recurrence_spec(order)
    streamed = find_recurrence_spec(order, streamed=True)
    cap = max(kcommon.LANE, m)
    for bm in _BLOCK_M_CANDIDATES:
        if bm > max(cap, _BLOCK_M_CANDIDATES[-1]):
            continue
        if kcommon.spec_fits(resident, n, bm, itemsize=itemsize):
            return bm, None
    bm = _BLOCK_M_CANDIDATES[-1]
    for bn in _BLOCK_N_CANDIDATES:
        if kcommon.spec_fits(streamed, n, bm, bn, itemsize=itemsize):
            return bm, bn
    return bm, _BLOCK_N_CANDIDATES[-1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _recur1_pallas(reverse, block_m, block_n, interpret, p, q, h0):
    """Order-1 Pallas recurrence on flattened (N, M) operands; ``h0`` is a
    concrete (M,) seed (zeros when the caller passed None)."""
    from repro.kernels import ops as kops
    return kops.recurrence(p, q, h0=h0, reverse=reverse, block_m=block_m,
                           block_n=block_n, interpret=interpret)


def _recur1_fwd(reverse, block_m, block_n, interpret, p, q, h0):
    h = _recur1_pallas(reverse, block_m, block_n, interpret, p, q, h0)
    return h, (p, h, h0)


def _recur1_bwd(reverse, block_m, block_n, interpret, res, g):
    """Adjoint of h_i = p_i h_{i-1} + q_i: the SAME recurrence walked the
    other way with the gate shifted one step (lambda_i = g_i +
    p_{i+1} lambda_{i+1}), run on the same Pallas kernels; then
    dp_i = lambda_i h_{i-1}, dq = lambda, dh0 = lambda_0 p_0."""
    from repro.kernels import ops as kops
    p, h, h0 = res
    if reverse:
        p_adj, lam_rev = _shift_down(p, 1), False
        h_prev = jnp.concatenate([h[1:], h0[None]], axis=0)
    else:
        p_adj, lam_rev = _shift_up(p, 1), True
        h_prev = jnp.concatenate([h0[None], h[:-1]], axis=0)
    lam = kops.recurrence(p_adj, g, reverse=lam_rev, block_m=block_m,
                          block_n=block_n, interpret=interpret)
    edge = -1 if reverse else 0
    return lam * h_prev, lam, lam[edge] * p[edge]


_recur1_pallas.defvjp(_recur1_fwd, _recur1_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _recur2_pallas(reverse, block_m, block_n, interpret, s, t, u, h1, h2):
    """Order-2 Pallas recurrence on flattened (N, M) operands; ``(h1, h2)``
    are the concrete (M,) seeds (h_{-1}, h_{-2}), zeros for None."""
    from repro.kernels import ops as kops
    return kops.recurrence(s, t, u, h0=(h1, h2), reverse=reverse,
                           block_m=block_m, block_n=block_n,
                           interpret=interpret)


def _recur2_fwd(reverse, block_m, block_n, interpret, s, t, u, h1, h2):
    h = _recur2_pallas(reverse, block_m, block_n, interpret, s, t, u, h1, h2)
    return h, (s, t, h, h1, h2)


def _recur2_bwd(reverse, block_m, block_n, interpret, res, g):
    """Adjoint of the order-2 recurrence: lambda_i = g_i +
    s_{i+1} lambda_{i+1} + t_{i+2} lambda_{i+2} — the reverse recurrence
    with each gate shifted by its own lag."""
    from repro.kernels import ops as kops
    s, t, h, h1, h2 = res
    n = h.shape[0]
    if reverse:
        s_adj, t_adj, lam_rev = _shift_down(s, 1), _shift_down(t, 2), False
        hp1 = jnp.concatenate([h[1:], h1[None]], axis=0)
        hp2 = jnp.concatenate([h[2:], h1[None], h2[None]], axis=0)[:n]
        e0, e1 = n - 1, n - 2
    else:
        s_adj, t_adj, lam_rev = _shift_up(s, 1), _shift_up(t, 2), True
        hp1 = jnp.concatenate([h1[None], h[:-1]], axis=0)
        hp2 = jnp.concatenate([h2[None], h1[None], h[:-2]], axis=0)[:n]
        e0, e1 = 0, 1
    lam = kops.recurrence(s_adj, t_adj, g, reverse=lam_rev, block_m=block_m,
                          block_n=block_n, interpret=interpret)
    dh1 = lam[e0] * s[e0]
    if n > 1:
        dh1 = dh1 + lam[e1] * t[e1]
    dh2 = lam[e0] * t[e0]
    return lam * hp1, lam * hp2, lam, dh1, dh2


_recur2_pallas.defvjp(_recur2_fwd, _recur2_bwd)


def _pallas_dispatch(gates: tuple, q: jax.Array, h0: tuple | None, *,
                     reverse: bool, block_m: int | None,
                     block_n: int | None, interpret: bool | None
                     ) -> jax.Array:
    """Flatten (N, ...) operands onto the kernels' (N, M) layout, tune the
    blocks against the VMEM budget, and run the differentiable Pallas
    recurrence.  Gates broadcast to the operand shape on the host (a
    shared (N,) gate becomes a full gate operand — the recurrence layout
    has no shared-LHS stream)."""
    order = len(gates)
    n = q.shape[0]
    shape = q.shape
    m = math.prod(shape[1:])
    gates = tuple(jnp.broadcast_to(g, shape).reshape(n, m) for g in gates)
    qf = q.reshape(n, m)
    if h0 is None:
        seeds = tuple(jnp.zeros((m,), q.dtype) for _ in range(order))
    else:
        seeds = tuple(jnp.broadcast_to(h.astype(q.dtype),
                                       shape[1:]).reshape(m) for h in h0)
    if block_m is None:
        block_m, auto_bn = _auto_blocks(order, n, m, jnp.dtype(q.dtype).itemsize)
        if block_n is None:
            block_n = auto_bn
    if order == 1:
        h = _recur1_pallas(reverse, block_m, block_n, interpret,
                           gates[0], qf, seeds[0])
    else:
        h = _recur2_pallas(reverse, block_m, block_n, interpret,
                           gates[0], gates[1], qf, seeds[0], seeds[1])
    return h.reshape(shape)


# ---------------------------------------------------------------------------
# Public front end
# ---------------------------------------------------------------------------

def linear_recurrence(
    p: jax.Array,
    q: jax.Array,
    h0: jax.Array | None = None,
    *,
    reverse: bool = False,
    method: str = "scan",
    unroll: int = 1,
    block_m: int | None = None,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Solve h_i = p_i * h_{i-1} + q_i for i = 0..N-1 (h_{-1} = h0, default 0).

    p: (N,) or broadcastable against q — multiplicative gates.
    q: (N, ...)  — additive operands (e.g. interleaved RHS batch (N, M)).
    reverse: run the recurrence from i = N-1 down to 0 (h_i depends on h_{i+1}).
    method: "scan" | "assoc" | "pallas" | "auto" (see module docstring);
    every method computes in ``jnp.result_type(p, q)`` and honours
    (h0 x reverse) identically.  ``block_m``/``block_n``/``interpret``
    tune the pallas path only (None = VMEM-aware auto).
    Returns h with q's shape in the promoted dtype.
    """
    q = jnp.asarray(q)
    p = _align(p, q)
    dtype = jnp.result_type(p.dtype, q.dtype)
    p, q = p.astype(dtype), q.astype(dtype)
    method = _resolve(method, dtype)

    if method == "pallas":
        return _pallas_dispatch(
            (p,), q, None if h0 is None else (jnp.asarray(h0),),
            reverse=reverse, block_m=block_m, block_n=block_n,
            interpret=interpret)

    if h0 is not None:
        h0 = jnp.broadcast_to(jnp.asarray(h0), q.shape[1:]).astype(dtype)

    if method == "scan":
        def step(h, pq):
            p_i, q_i = pq
            h_new = p_i * h + q_i
            return h_new, h_new

        # built from the operand so that, inside shard_map, the carry is
        # typed as varying over the same mesh axes as q
        init = jnp.zeros_like(q[0]) if h0 is None else h0
        _, h = jax.lax.scan(step, init, (p, q), reverse=reverse, unroll=unroll)
        return h

    # assoc
    def combine(fst, snd):
        # fst happened earlier in scan order; composition:
        # h -> p2*(p1*h + q1) + q2 = (p1*p2)*h + (p2*q1 + q2)
        p1, q1 = fst
        p2, q2 = snd
        return p1 * p2, p2 * q1 + q2

    p_full = jnp.broadcast_to(p, q.shape)
    pp, qq = jax.lax.associative_scan(combine, (p_full, q), reverse=reverse,
                                      axis=0)
    if h0 is not None:
        return pp * h0 + qq
    return qq


def linear_recurrence2(
    s: jax.Array,
    t: jax.Array,
    u: jax.Array,
    h0: tuple | None = None,
    *,
    reverse: bool = False,
    method: str = "scan",
    unroll: int = 1,
    block_m: int | None = None,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Solve h_i = s_i h_{i-1} + t_i h_{i-2} + u_i  (seeds default to 0).

    With ``reverse=True`` solves h_i = s_i h_{i+1} + t_i h_{i+2} + u_i
    (h_N = h_{N+1} = 0) — the pentadiagonal back-substitution shape.

    s, t: (N,) or broadcastable against u;  u: (N, ...).
    h0: optional ``(h_{-1}, h_{-2})`` seed pair (``(h_N, h_{N+1})`` when
    reversed), each broadcastable over the batch dims.  Methods and
    dtype/broadcast rules match ``linear_recurrence``.
    """
    u = jnp.asarray(u)
    s = _align(s, u)
    t = _align(t, u)
    dtype = jnp.result_type(s.dtype, t.dtype, u.dtype)
    s, t, u = s.astype(dtype), t.astype(dtype), u.astype(dtype)
    method = _resolve(method, dtype)

    if h0 is not None:
        if len(h0) != 2:
            raise ValueError("h0 must be a (h_{-1}, h_{-2}) pair")
        h0 = tuple(jnp.broadcast_to(jnp.asarray(h), u.shape[1:]).astype(dtype)
                   for h in h0)

    if method == "pallas":
        return _pallas_dispatch((s, t), u, h0, reverse=reverse,
                                block_m=block_m, block_n=block_n,
                                interpret=interpret)

    if method == "scan":
        def step(carry, stu):
            h1, h2 = carry  # h_{i-1}, h_{i-2}
            s_i, t_i, u_i = stu
            h_new = s_i * h1 + t_i * h2 + u_i
            return (h_new, h1), h_new

        # from the operand: varying over u's mesh axes inside shard_map
        zeros = jnp.zeros_like(u[0])
        init = (zeros, zeros) if h0 is None else h0
        _, h = jax.lax.scan(step, init, (s, t, u), reverse=reverse,
                            unroll=unroll)
        return h

    if method == "assoc":
        # 2x2 companion-matrix associative scan:
        #   H_i = [[s_i, t_i], [1, 0]] H_{i-1} + [u_i, 0],  H = (h_i, h_{i-1}).
        s = jnp.broadcast_to(s, u.shape)
        t = jnp.broadcast_to(t, u.shape)
        one = jnp.ones_like(s)
        zero = jnp.zeros_like(s)
        # A: (N, 2, 2, ...), b: (N, 2, ...) — move the 2x2 in axes 1,2.
        A = jnp.stack(
            [jnp.stack([s, t], axis=1), jnp.stack([one, zero], axis=1)], axis=1
        )  # (N, 2, 2, ...)
        b = jnp.stack([u, jnp.zeros_like(u)], axis=1)  # (N, 2, ...)

        def matmul2(X, Y):
            # X, Y: (k, 2, 2, ...) — contract the inner 2-dims explicitly.
            return jnp.stack(
                [
                    jnp.stack(
                        [
                            X[:, 0, 0] * Y[:, 0, 0] + X[:, 0, 1] * Y[:, 1, 0],
                            X[:, 0, 0] * Y[:, 0, 1] + X[:, 0, 1] * Y[:, 1, 1],
                        ],
                        axis=1,
                    ),
                    jnp.stack(
                        [
                            X[:, 1, 0] * Y[:, 0, 0] + X[:, 1, 1] * Y[:, 1, 0],
                            X[:, 1, 0] * Y[:, 0, 1] + X[:, 1, 1] * Y[:, 1, 1],
                        ],
                        axis=1,
                    ),
                ],
                axis=1,
            )

        def matvec2(X, v):
            # X: (k, 2, 2, ...), v: (k, 2, ...)
            return jnp.stack(
                [
                    X[:, 0, 0] * v[:, 0] + X[:, 0, 1] * v[:, 1],
                    X[:, 1, 0] * v[:, 0] + X[:, 1, 1] * v[:, 1],
                ],
                axis=1,
            )

        def combine(fst, snd):
            A1, b1 = fst
            A2, b2 = snd
            return matmul2(A2, A1), matvec2(A2, b1) + b2

        AA, bb = jax.lax.associative_scan(combine, (A, b), reverse=reverse,
                                          axis=0)
        if h0 is not None:
            # H_i = AA_i @ H_seed + bb_i with H_seed = (h_{-1}, h_{-2})
            return AA[:, 0, 0] * h0[0] + AA[:, 0, 1] * h0[1] + bb[:, 0]
        return bb[:, 0]

    raise ValueError(f"unknown method {method!r}")
