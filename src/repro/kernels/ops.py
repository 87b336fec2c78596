"""Public jit'd entry points for the solver kernels.

These wrap the engine-generated ``pallas_call`` kernels
(``repro.kernels.engine``) with:
  * factored-LHS stacking from ``repro.core`` factor types — including the
    host-side row SHIFTS that turn the stored forward factor into the
    transposed kernels' coefficient rows (A^T = U^T·L^T needs c_hat_{i-1}
    / a_{i+1} etc., never a second factor),
  * lane padding (the batch axis is padded to the lane-tile multiple) and
    sweep padding (streamed kernels pad N to the chunk multiple; batch
    operands identity-pad the main diagonal on BOTH axes because the
    fused factorisation divides in-kernel),
  * the layer scopes of ``repro.scopes``: each dispatch runs in
    ``repro.kernel.<spec name>``, its pads, casts and output slices in
    ``repro.pad_cast`` nested inside it,
  * automatic ``interpret=True`` off-TPU (validation mode on CPU),
  * spec-derived VMEM-budget checks,
  * an optional ``shard_map`` distribution over the system/batch axis — the
    paper's single-LHS idea at cluster scale: ONE LHS copy per device
    (replicated), RHS systems sharded across the mesh, zero collectives in
    the solve (embarrassingly parallel over M).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import scopes
from repro.core import (PentaFactor, PeriodicPentaFactor,
                        PeriodicTridiagFactor, TridiagFactor)
from . import common as _common
from .common import (LANE, PACK, SUBLANE, canonical_storage_dtype,
                     check_fits, default_interpret, pad_lanes, pad_sweep,
                     smem_working_set, spec_fits, vmem_working_set)
from .engine import (REGISTRY, RecurrenceSpec, SweepSpec, batch_solver,
                     find_recurrence_spec, find_spec, recurrence_solver,
                     shared_solver)
from . import fused_cn, fused_cn_penta


def _shift_down(v: jax.Array, k: int) -> jax.Array:
    """Row i reads the stored vector at i-k (zeros shift in at the top)."""
    return jnp.concatenate([jnp.zeros_like(v[:k]), v[:-k]], axis=0)


def _shift_up(v: jax.Array, k: int) -> jax.Array:
    """Row i reads the stored vector at i+k (zeros shift in at the bottom)."""
    return jnp.concatenate([v[k:], jnp.zeros_like(v[:k])], axis=0)


def stack_tridiag_lhs(f: TridiagFactor, *,
                      transposed: bool = False) -> jax.Array:
    """(3, N) kernel LHS: [a, inv_denom, c_hat], or the transposed rows
    [c_hat_{i-1}, inv_denom, a_{i+1}] — same stored vectors, shifted."""
    if transposed:
        return jnp.stack([_shift_down(f.c_hat, 1), f.inv_denom,
                          _shift_up(f.a, 1)])
    return jnp.stack([f.a, f.inv_denom, f.c_hat])


def stack_penta_lhs(f: PentaFactor, uniform: bool = False, *,
                    transposed: bool = False) -> jax.Array:
    """(5, N) kernel LHS [eps, beta, inv_alpha, gamma, delta] ((4, N) when
    ``uniform`` drops the eps row); transposed: [delta_{i-2}, gamma_{i-1},
    inv_alpha, beta_{i+1}(, eps_{i+2})]."""
    if transposed:
        rows = [_shift_down(f.delta, 2), _shift_down(f.gamma, 1),
                f.inv_alpha, _shift_up(f.beta, 1)]
        if not uniform:
            eps = jnp.broadcast_to(f.eps, f.beta.shape)
            rows.append(_shift_up(eps, 2))
        return jnp.stack(rows)
    if uniform:
        return jnp.stack([f.beta, f.inv_alpha, f.gamma, f.delta])
    eps = jnp.broadcast_to(f.eps, f.beta.shape)
    return jnp.stack([eps, f.beta, f.inv_alpha, f.gamma, f.delta])


def _check_spec_vmem(spec: SweepSpec, n: int, block_m: int,
                     block_n: int | None, dtype) -> None:
    """Spec-derived working-set check (no hand-kept per-kernel counts),
    priced at the fp32-promoted compute itemsize — an upper bound for
    blocks stored at a packed storage dtype."""
    check_fits(spec, n, block_m, block_n,
               itemsize=jnp.promote_types(dtype, jnp.float32).itemsize)


#: Lane tiles of the packed sweep, widest first: ``block_m / PACK`` whole
#: vregs are swept per trip.
_PACKED_BLOCK_M = (4 * PACK, 2 * PACK, PACK)


def _packed_choice(spec: SweepSpec, n: int, m: int, dtype, sdt):
    """``(packed spec, block_m)`` when the packed sweep serves a resident
    shared ``spec`` on an (n, m) RHS, else None.  It does when the shape
    allows whole packed groups and nothing else: M a multiple of 1024
    (8 lane tiles, so no lane padding), N % 8 == 0 (the lane-tile-major
    copy stays tile-aligned), and the RHS stored at the compute dtype.
    Its lane tile is the widest that divides M and fits the spec-derived
    working set."""
    cdt = jnp.promote_types(dtype, jnp.float32)
    if (spec.streamed or n % SUBLANE or m % PACK or dtype != cdt
            or (sdt is not None and sdt != cdt)):
        return None
    packed = REGISTRY[spec.packed_name]
    for block_m in _PACKED_BLOCK_M:
        if m % block_m == 0 and spec_fits(packed, n, block_m,
                                          itemsize=cdt.itemsize):
            return packed, block_m
    return None


def _shared_spec(bandwidth: int, mode: str, n: int, m: int, dtype, sdt, *,
                 block_m: int, block_n: int | None, transposed: bool,
                 fused: bool) -> tuple:
    """``(spec, block_m)`` of the shared-LHS kernel that serves this solve:
    the packed sweep where ``_packed_choice`` allows it, else the row
    sweep (resident or streamed) at the caller's blocks, fit-checked."""
    spec = find_spec(bandwidth, mode, streamed=block_n is not None,
                     transposed=transposed, fused=fused)
    choice = _packed_choice(spec, n, m, dtype, sdt)
    if choice is not None:
        return choice
    _check_spec_vmem(spec, n, block_m, block_n, dtype)
    return spec, block_m


def _pad_cast(operands, block_m: int, block_n: int | None, sdt,
              idents) -> list:
    """Lane pads, storage-dtype casts and (streamed) sweep pads of the
    (N, M) operands; ``idents`` marks the main diagonals, padded with
    ones."""
    with jax.named_scope(scopes.PAD_CAST):
        out = [pad_lanes(x, block_m, identity=i)[0]
               for x, i in zip(operands, idents)]
        if sdt is not None:
            out = [x.astype(sdt) for x in out]
        if block_n is not None:
            out = [pad_sweep(x, block_n, axis=0, identity=i)[0]
                   for x, i in zip(out, idents)]
    return out


def _unpad(x: jax.Array, n: int, m: int) -> jax.Array:
    """The (n, m) solution out of the padded kernel output."""
    with jax.named_scope(scopes.PAD_CAST):
        return x[:n, :m]


def _shared_dispatch(spec: SweepSpec, lhs: jax.Array, rhs: jax.Array, sdt,
                     *, block_m: int, block_n: int | None, unroll: int,
                     interpret: bool, prefetch: bool,
                     eps=None) -> jax.Array:
    """Pad, run and unpad one shared-LHS kernel ((rows, N) stacked LHS)."""
    n, m = rhs.shape
    (rhs_pad,) = _pad_cast([rhs], block_m, block_n, sdt, [False])
    opts = dict(block_m=block_m, unroll=unroll, interpret=interpret, eps=eps)
    if block_n is not None:
        with jax.named_scope(scopes.PAD_CAST):
            lhs, _ = pad_sweep(lhs, block_n, axis=1)
        opts.update(block_n=block_n, prefetch=prefetch)
    return _unpad(shared_solver(spec)(lhs, rhs_pad, **opts), n, m)


def _batch_dispatch(spec: SweepSpec, operands, idents, sdt, *, block_m: int,
                    block_n: int | None, unroll: int, interpret: bool,
                    prefetch: bool) -> jax.Array:
    """Pad, run and unpad one per-lane-LHS kernel (diagonals, then rhs)."""
    n, m = operands[-1].shape
    args = _pad_cast(operands, block_m, block_n, sdt, idents)
    opts = dict(block_m=block_m, unroll=unroll, interpret=interpret)
    if block_n is not None:
        opts.update(block_n=block_n, prefetch=prefetch)
    return _unpad(batch_solver(spec)(*args, **opts), n, m)


def thomas_constant(f: TridiagFactor, d: jax.Array, *, block_m: int = 128,
                    block_n: int | None = None, unroll: int = 1,
                    interpret: bool | None = None,
                    transposed: bool = False, fused: bool = False,
                    storage_dtype=None,
                    prefetch: bool = False) -> jax.Array:
    """Constant-LHS batched Thomas solve (cuThomasConstantBatch). d: (N, M).

    ``block_n=None`` runs the VMEM-resident kernel (full N per grid step);
    an integer ``block_n`` runs the HBM-streamed split-N kernel pair,
    which lifts the VMEM wall for large N — or, with ``fused=True``, the
    single-call ascend/descend kernel that keeps the intermediate in VMEM
    (half the streamed HBM traffic).  ``storage_dtype="bf16"`` stores the
    RHS stream at bf16 in HBM (fp32 factor and accumulation in-kernel;
    the solve returns fp32).  ``prefetch=True`` marks the lane-tile grid
    axis parallel on hardware (no-op under interpret).  ``transposed=True`` solves
    A^T x = d from the SAME stored factor (the adjoint sweeps).  A resident
    solve whose shape allows it runs the packed sweep (8 lane tiles per
    vreg; ``_packed_choice``), bit-equal to the row sweep, at the lane tile
    the packed fit picks instead of ``block_m``."""
    if interpret is None:
        interpret = default_interpret()
    n, m = d.shape
    sdt = canonical_storage_dtype(storage_dtype)
    spec, block_m = _shared_spec(3, "constant", n, m, d.dtype, sdt,
                                 block_m=block_m, block_n=block_n,
                                 transposed=transposed, fused=fused)
    with jax.named_scope(scopes.kernel(spec.name)):
        lhs = stack_tridiag_lhs(f, transposed=transposed)
        return _shared_dispatch(spec, lhs, d, sdt, block_m=block_m,
                                block_n=block_n, unroll=unroll,
                                interpret=interpret, prefetch=prefetch)


def thomas_batch(a, b, c, d, *, block_m: int = 128,
                 block_n: int | None = None, unroll: int = 1,
                 interpret: bool | None = None, fused: bool = False,
                 storage_dtype=None, prefetch: bool = False) -> jax.Array:
    """Per-system-LHS baseline (cuThomasBatch). a/b/c/d: (N, M).

    Dead padded lanes get an IDENTITY main diagonal (b = 1), not the zero
    pad — the fused factorisation would otherwise compute 1/0 and flood
    the padding with inf/NaN (they are sliced off, but they poison
    ``JAX_DEBUG_NANS`` runs and waste the flush-to-zero path).  An integer
    ``block_n`` selects the HBM-streamed split-N pair, which additionally
    identity-pads the main diagonal along the sweep axis for the same
    reason and spills the fused c_hat to HBM between the passes —
    ``fused=True`` keeps the spill in full-N VMEM scratch instead (one
    ascend/descend kernel).  ``storage_dtype="bf16"`` streams the
    diagonals/RHS at bf16 (fp32 in-kernel; interpret mode only — the
    compiled batch kernels refuse packed operands); ``prefetch=True``
    marks the lane-tile grid axis parallel on hardware."""
    if interpret is None:
        interpret = default_interpret()
    n = d.shape[0]
    sdt = canonical_storage_dtype(storage_dtype)
    spec = find_spec(3, "batch", streamed=block_n is not None, fused=fused)
    _check_spec_vmem(spec, n, block_m, block_n, d.dtype)
    idents = (False, True, False, False)          # b is the main diagonal
    with jax.named_scope(scopes.kernel(spec.name)):
        return _batch_dispatch(spec, (a, b, c, d), idents, sdt,
                               block_m=block_m, block_n=block_n,
                               unroll=unroll, interpret=interpret,
                               prefetch=prefetch)


def _uniform_eps_param(f: PentaFactor, dtype) -> jax.Array:
    """The all-equal eps value as a (1, 1) ARRAY operand.

    Must stay an array end to end: ``float(f.eps[2])`` on a traced
    ``Factorization`` leaf raises ``ConcretizationTypeError`` under
    ``jax.jit(solve)`` / ``lax.scan`` PDE loops.  Index [2] because the
    factor forces eps[0] = eps[1] = 0 (outside the matrix)."""
    eps = jnp.broadcast_to(jnp.asarray(f.eps), f.beta.shape)
    return eps[2].reshape(1, 1).astype(dtype)


def penta_constant(f: PentaFactor, rhs: jax.Array, *, block_m: int = 128,
                   block_n: int | None = None, unroll: int = 1,
                   interpret: bool | None = None, uniform: bool = False,
                   transposed: bool = False, fused: bool = False,
                   storage_dtype=None, prefetch: bool = False) -> jax.Array:
    """Constant-LHS batched penta solve (cuPentConstantBatch /
    cuPentUniformBatch when ``uniform``).  ``block_n`` selects the
    HBM-streamed split-N kernel pair (``fused=True``: the single-call
    ascend/descend kernel — half the streamed traffic);
    ``storage_dtype="bf16"`` streams the RHS at bf16 (fp32 factor and
    carries in-kernel); ``transposed=True`` solves A^T x = rhs from the SAME
    stored factor.  Resident solves take the packed sweep where the shape
    allows it, as in ``thomas_constant``."""
    if interpret is None:
        interpret = default_interpret()
    n, m = rhs.shape
    sdt = canonical_storage_dtype(storage_dtype)
    spec, block_m = _shared_spec(5, "uniform" if uniform else "constant",
                                 n, m, rhs.dtype, sdt, block_m=block_m,
                                 block_n=block_n, transposed=transposed,
                                 fused=fused)
    with jax.named_scope(scopes.kernel(spec.name)):
        eps = _uniform_eps_param(f, rhs.dtype) if uniform else None
        lhs = stack_penta_lhs(f, uniform=uniform, transposed=transposed)
        return _shared_dispatch(spec, lhs, rhs, sdt, block_m=block_m,
                                block_n=block_n, unroll=unroll,
                                interpret=interpret, prefetch=prefetch,
                                eps=eps)


def penta_batch(a, b, c, d, e, rhs, *, block_m: int = 128,
                block_n: int | None = None, unroll: int = 1,
                interpret: bool | None = None, fused: bool = False,
                storage_dtype=None, prefetch: bool = False) -> jax.Array:
    """Per-system-LHS baseline (cuPentBatch).  Identity-pads the MAIN
    diagonal c on the lane axis (and on the sweep axis when streamed):
    dead lanes/rows must factor as identity, not divide by the zero pad.
    ``block_n`` selects the streamed pair (gamma/delta spill to HBM);
    ``fused=True`` keeps the spill in full-N VMEM scratch instead (one
    ascend/descend kernel); ``storage_dtype="bf16"`` streams the
    diagonals/RHS at bf16 (fp32 in-kernel; interpret mode only — the
    compiled batch kernels refuse packed operands)."""
    if interpret is None:
        interpret = default_interpret()
    n = rhs.shape[0]
    sdt = canonical_storage_dtype(storage_dtype)
    spec = find_spec(5, "batch", streamed=block_n is not None, fused=fused)
    _check_spec_vmem(spec, n, block_m, block_n, rhs.dtype)
    idents = (False, False, True, False, False, False)  # c is the main diag
    with jax.named_scope(scopes.kernel(spec.name)):
        return _batch_dispatch(spec, (a, b, c, d, e, rhs), idents, sdt,
                               block_m=block_m, block_n=block_n,
                               unroll=unroll, interpret=interpret,
                               prefetch=prefetch)


def recurrence(*operands, h0=None, reverse: bool = False,
               block_m: int = 128, block_n: int | None = None,
               unroll: int = 1, interpret: bool | None = None) -> jax.Array:
    """Gated linear recurrence over an interleaved (N, M) batch.

    ``operands`` is ``(p, q)`` for the order-1 recurrence
    ``h_i = p_i h_{i-1} + q_i`` or ``(s, t, u)`` for the order-2
    ``h_i = s_i h_{i-1} + t_i h_{i-2} + u_i`` — per-token (N, M) gate
    arrays plus the additive operand, the recurrence-layout analogue of
    the batch solvers' per-lane diagonals.  ``reverse=True`` runs from
    i = N-1 down to 0 (carries index i+1/i+2).

    ``h0`` seeds the incoming carries (an array broadcastable over lanes
    for order 1, a ``(h_{-1}, h_{-2})`` pair for order 2).  It is folded
    into the boundary rows of ``q`` ON THE HOST — the kernels keep the
    zero-carry protocol every sweep kernel shares (``reset_carry``), so
    streamed chunking and the zero sweep-padding stay exact: a padded
    gate row multiplies a finite carry by 0.

    ``block_n=None`` runs the VMEM-resident kernel; an integer selects
    the HBM-streamed split-N kernel (a SINGLE kernel, not a pair — a
    recurrence has no back-substitution partner)."""
    if interpret is None:
        interpret = default_interpret()
    *gates, q = (jnp.asarray(x) for x in operands)
    order = len(gates)
    if order not in (1, 2):
        raise ValueError(
            f"recurrence takes (p, q) or (s, t, u); got {order + 1} operands")
    n, m = q.shape
    hs = None
    if h0 is not None:
        hs = (h0,) if order == 1 and not isinstance(h0, (tuple, list)) \
            else tuple(h0)
        if len(hs) != order:
            raise ValueError(f"h0 must carry {order} state(s), got {len(hs)}")
    spec = find_recurrence_spec(order, reverse=reverse,
                                streamed=block_n is not None)
    _check_spec_vmem(spec, n, block_m, block_n, q.dtype)
    with jax.named_scope(scopes.kernel(spec.name)):
        if hs is not None:
            hs = tuple(jnp.broadcast_to(jnp.asarray(h), q.shape[1:]).astype(
                q.dtype) for h in hs)
            e0 = n - 1 if reverse else 0
            fold = gates[0][e0] * hs[0]
            if order == 2:
                fold = fold + gates[1][e0] * hs[1]
            q = q.at[e0].add(fold)
            if order == 2 and n > 1:
                e1 = n - 2 if reverse else 1
                q = q.at[e1].add(gates[1][e1] * hs[0])
        args = _pad_cast([*gates, q], block_m, block_n, None,
                         [False] * (order + 1))
        opts = dict(block_m=block_m, unroll=unroll, interpret=interpret)
        if block_n is not None:
            opts.update(block_n=block_n)
        return _unpad(recurrence_solver(spec)(*args, **opts), n, m)


def _check_cn_vmem(n: int, block_m: int, lhs_rows: int, dtype) -> None:
    """Fit check of the fused CN step kernels: the field in and out as
    (N, BLOCK_M) blocks, plus the (N, <=4) correction directions, whose
    block pads to one lane tile — all double-buffered — and the factored
    LHS in SMEM."""
    ws = (vmem_working_set(n, block_m, 2, itemsize=dtype.itemsize)
          + vmem_working_set(n, LANE, 1, itemsize=dtype.itemsize))
    if (ws > _common.VMEM_BUDGET_BYTES or smem_working_set(
            lhs_rows, n, dtype.itemsize) > _common.SMEM_BUDGET_BYTES):
        raise ValueError(
            f"fused CN step working set {ws/2**20:.1f} MiB exceeds VMEM "
            f"budget ({_common.VMEM_BUDGET_BYTES/2**20:.0f} MiB) or its "
            f"LHS exceeds SMEM: N={n}, BLOCK_M={block_m}. Reduce block_m "
            f"or N.")


def fused_cn_step(pf: PeriodicTridiagFactor, sigma: float, c: jax.Array, *,
                  block_m: int = 128, unroll: int = 1,
                  interpret: bool | None = None) -> jax.Array:
    """Fused periodic CN diffusion step (beyond-paper; see fused_cn.py)."""
    if interpret is None:
        interpret = default_interpret()
    n, m = c.shape
    _check_cn_vmem(n, block_m, 3, c.dtype)
    with jax.named_scope(scopes.kernel(fused_cn.NAME)):
        lhs = stack_tridiag_lhs(pf.factor)
        z = pf.z.reshape(n, 1)
        params = jnp.zeros((1, 8), c.dtype)
        params = params.at[0, 0].set(sigma).at[0, 1].set(1 - 2 * sigma) \
                       .at[0, 2].set(sigma).at[0, 3].set(pf.v_last) \
                       .at[0, 4].set(pf.inv_denom_sm)
        (c_pad,) = _pad_cast([c], block_m, None, None, [False])
        x = fused_cn.fused_cn_tridiag_pallas(lhs, z, params, c_pad,
                                             block_m=block_m, unroll=unroll,
                                             interpret=interpret)
        return _unpad(x, n, m)


def fused_cn_penta_step(pf: PeriodicPentaFactor, sigma: float, c: jax.Array,
                        *, block_m: int = 128, unroll: int = 1,
                        interpret: bool | None = None) -> jax.Array:
    """Fused periodic CN hyperdiffusion step (beyond-paper #2;
    see fused_cn_penta.py). c: (N, M) -> (N, M)."""
    if interpret is None:
        interpret = default_interpret()
    n, m = c.shape
    _check_cn_vmem(n, block_m, 5, c.dtype)
    with jax.named_scope(scopes.kernel(fused_cn_penta.NAME)):
        lhs = stack_penta_lhs(pf.factor)
        params = jnp.zeros((1, 16), c.dtype)
        stencil = [-sigma, 4 * sigma, 1 - 6 * sigma, 4 * sigma, -sigma]
        for i, v in enumerate(stencil):
            params = params.at[0, i].set(v)
        for i in range(6):
            params = params.at[0, 5 + i].set(pf.vcoef[i])
        (c_pad,) = _pad_cast([c], block_m, None, None, [False])
        x = fused_cn_penta.fused_cn_penta_pallas(
            lhs, pf.Z, pf.Minv, params, c_pad, block_m=block_m,
            unroll=unroll, interpret=interpret)
        return _unpad(x, n, m)


# ---------------------------------------------------------------------------
# Analytic HBM traffic for one solve as dispatched by this module — derived
# from the registered SweepSpec, so every generated variant (transposed,
# batch-streamed, ...) automatically has a roofline entry.
# ---------------------------------------------------------------------------

#: Dispatch entry point per (bandwidth, layout) — the introspection hook
#: behind ``repro.analysis``'s registry-driven sweeps: every REGISTRY spec
#: resolves to exactly one of these public callables, so an analysis (or a
#: sanitizer sweep) can exercise a NEW spec without a hand-kept case list.
ENTRY_POINTS = {
    (3, "shared"): thomas_constant,
    (3, "batch"): thomas_batch,
    (5, "shared"): penta_constant,
    (5, "batch"): penta_batch,
    (1, "recurrence"): recurrence,
    (2, "recurrence"): recurrence,
}


def entry_key(spec) -> tuple:
    """The ``ENTRY_POINTS`` key a registered spec dispatches through —
    sweep specs key on (bandwidth, layout), recurrence specs on
    (order, 'recurrence')."""
    if isinstance(spec, RecurrenceSpec):
        return (spec.order, spec.layout)
    return (spec.bandwidth, spec.layout)


def entry_point(spec):
    """The ops-layer callable that dispatches ``spec`` (see the per-entry
    docstrings for the keyword contract: shared specs take a factor +
    ``transposed``/``uniform`` flags, batch specs take raw diagonals,
    recurrence specs take per-token gate operands + ``reverse``)."""
    return ENTRY_POINTS[entry_key(spec)]


def solver_hbm_traffic_bytes(bandwidth: int, mode: str, n: int, m: int, *,
                             dtype=jnp.float32, streamed: bool = False,
                             transposed: bool = False, fused: bool = False,
                             storage_dtype=None) -> int:
    """Bytes moved HBM<->VMEM by one batched solve of an (n, m) RHS.

    ``fused`` selects the single-call streamed variant's (halved) model;
    ``storage_dtype`` prices the stored-operand streams at that itemsize
    (the bf16 storage path) while intermediates stay at ``dtype``.
    Unknown (bandwidth, mode, streamed, transposed) combinations raise an
    informative ``ValueError`` (via ``find_spec``) naming the valid
    choices."""
    if mode == "batch" and transposed:
        # the adjoint of a batch solve rolls the per-lane diagonals and
        # runs the FORWARD batch kernels — identical streams.
        transposed = False
    spec = find_spec(bandwidth, mode, streamed=streamed,
                     transposed=transposed, fused=fused)
    return spec.traffic_bytes(n, m, dtype,
                              canonical_storage_dtype(storage_dtype))


def recurrence_hbm_traffic_bytes(order: int, n: int, m: int, *,
                                 dtype=jnp.float32, streamed: bool = False,
                                 reverse: bool = False) -> int:
    """Bytes moved HBM<->VMEM by one gated recurrence over an (n, m)
    batch — derived from the registered ``RecurrenceSpec`` exactly like
    the solver model (unknown orders raise via ``find_recurrence_spec``)."""
    spec = find_recurrence_spec(order, reverse=reverse, streamed=streamed)
    return spec.traffic_bytes(n, m, dtype)


def sharded_solver_hbm_traffic_bytes(bandwidth: int, mode: str, n: int,
                                     m: int, n_shards: int, *,
                                     dtype=jnp.float32, streamed: bool = False,
                                     transposed: bool = False,
                                     fused: bool = False,
                                     storage_dtype=None) -> int:
    """PER-DEVICE bytes when the ``sharded`` backend runs this module's
    kernels on each device's local slice of the interleaved batch
    (``repro.solver.sharded`` with engine kernels active).  The solve has
    no collectives, so this IS the single-device model at the local lane
    count (``shard_lanes``) — same ``SweepSpec`` derivation, so the
    sharded x streamed composition can never silently miss the roofline
    table."""
    from .common import shard_lanes
    return solver_hbm_traffic_bytes(bandwidth, mode, n,
                                    shard_lanes(m, n_shards), dtype=dtype,
                                    streamed=streamed, transposed=transposed,
                                    fused=fused, storage_dtype=storage_dtype)


# ---------------------------------------------------------------------------
# Distributed batch solving: one LHS copy per DEVICE, systems sharded.
# ---------------------------------------------------------------------------

def sharded_solve(solve_fn, mesh: Mesh, batch_axes) -> callable:
    """Wrap a (factor, rhs (N, M)) -> x solver so the M axis is sharded over
    ``batch_axes`` of ``mesh`` and the factored LHS is replicated (the
    paper's storage saving, applied per-device). The solve needs no
    collectives — systems are independent.
    """
    spec_rhs = P(None, batch_axes)
    fn = jax.shard_map(solve_fn, mesh=mesh,
                       in_specs=(P(), spec_rhs), out_specs=spec_rhs,
                       check_vma=True)

    def wrapped(factor, rhs):
        return fn(factor, rhs)

    return wrapped
