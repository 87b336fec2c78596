"""``pallas`` backend: the interleaved Pallas TPU kernels from
``repro.kernels``, with VMEM-aware ``(block_m, block_n)`` auto-tuning.

Layout (DESIGN.md §2): the system index M rides the 128-wide lane axis
(one system per lane — the paper's one system per CUDA thread), the
unknown index N is the sequential sweep axis, and the shared LHS sits in a
single VMEM block whose index_map is constant across the grid.  Every
kernel this backend dispatches is generated by the declarative sweep
engine (``repro.kernels.engine``, DESIGN.md §2.2) from a ``SweepSpec`` —
which also supplies the VMEM working-set counts and the HBM traffic model
this module's auto-tuner reasons with (no hand-kept tables).

Auto-tuning is a 2-D search (DESIGN.md §2.1).  The resident kernels
(``block_n=None``) are preferred — one pass, minimum HBM traffic — at the
largest lane tile from ``_BLOCK_M_CANDIDATES`` whose working set
(``vmem_working_set``) fits the VMEM budget.  When no resident tile fits
(N too large), EVERY storage mode falls through to the HBM-streamed
split-N kernels: constant/uniform systems stream their shared-LHS chunks,
and batch (per-system-LHS) systems stream their per-lane diagonal blocks
while the fused factorisation's c_hat / gamma+delta scratch spills to HBM
between the two passes.  The VMEM wall therefore no longer caps N for ANY
mode — ``supports()`` keeps returning True and ``plan(backend="auto")``
keeps picking pallas at any N the HBM holds (the only missing kernel is
periodic x batch, which keeps the reference fallback).

Adjoints run on Pallas too: ``_pure_transpose`` dispatches the engine's
TRANSPOSED kernels (resident or streamed, per the forward's tuned blocks)
on the SAME stored factor — A^T = U^T·L^T from the forward's vectors, the
coefficient rows pre-shifted on the host — so large-N ``grad(solve)``
stays off the reference fallback.  Batch-mode adjoints roll the per-lane
diagonals into another batch system and reuse the forward batch kernels.

Periodic boundaries: the kernels solve the truncated band; the rank-1
Sherman-Morrison (tridiag) / rank-4 Woodbury (penta) corner corrections are
applied outside the kernel — a handful of O(M) dots, exactly the paper's
"2-kernel pipeline".  The adjoint corners transpose analytically from the
stored ``zt``/``Zt`` aux (DESIGN.md §5.1).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import penta as _penta
from repro.core import tridiag as _tridiag
from repro.kernels import common as _kcommon
from repro.kernels import engine as _kengine
from repro.kernels import ops as _kops

from .registry import register_backend, register_pure_backend
from .system import BandedSystem

_BLOCK_M_CANDIDATES = (1024, 512, 256, 128)
_BLOCK_N_CANDIDATES = (2048, 1024, 512, 256)


def _spec_for(system: BandedSystem, *, streamed: bool,
              fused: bool = False) -> "_kengine.SweepSpec":
    return _kengine.find_spec(system.bandwidth, system.mode,
                              streamed=streamed, fused=fused)


def _itemsize(system: BandedSystem) -> int:
    """The fp32-promoted compute itemsize the fit checks price blocks at
    (an upper bound for blocks kept at a packed storage dtype)."""
    return jnp.promote_types(system.dtype, jnp.float32).itemsize


def _lane_cap(system: BandedSystem) -> int | None:
    if system.batch is None:
        return None
    # no point tiling wider than the (lane-padded) batch itself
    return -(-system.batch // _kcommon.LANE) * _kcommon.LANE


def _lane_tiles(system: BandedSystem) -> tuple:
    """Candidate lane tiles, widest first, capped at the lane-padded batch."""
    cap = _lane_cap(system)
    return tuple(bm for bm in _BLOCK_M_CANDIDATES
                 if cap is None or bm <= max(cap, _BLOCK_M_CANDIDATES[-1]))


def _fits(system: BandedSystem, block_m: int, block_n: int | None = None,
          *, fused: bool = False) -> bool:
    """Does the kernel serving ``system`` at ``(block_m, block_n)`` fit the
    VMEM and SMEM budgets?  One spec-derived model
    (``common.spec_working_set``) for resident, two-call streamed and
    fused kernels alike."""
    spec = _spec_for(system, streamed=block_n is not None, fused=fused)
    return _kcommon.spec_fits(spec, system.n, block_m, block_n,
                              itemsize=_itemsize(system))


def _fused_fits(system: BandedSystem, block_m: int, block_n: int) -> bool:
    """Does the FUSED single-call sweep fit at ``(block_m, block_n)``?

    The fused kernel keeps the inter-pass intermediates (d_hat / the
    backward coefficients) for the current lane tile in full-N VMEM
    scratch — that scratch grows with N, so this is the spill rule: when
    it busts the budget the tuner falls back to the two-call pair."""
    return _fits(system, block_m, block_n, fused=True)


def auto_block_m(system: BandedSystem) -> int | None:
    """Largest candidate lane tile whose RESIDENT (full-N) working set fits
    the budgets (None if even the smallest does not fit)."""
    for bm in _lane_tiles(system):
        if _fits(system, bm):
            return bm
    return None


def _pick_fused(system: BandedSystem, block_m: int, block_n: int, *,
                storage_dtype=None) -> bool:
    """Cost-model leg of the auto-tuner, once streaming is resolved: among
    the streamed variants that FIT at ``(block_m, block_n)`` — the two-call
    pair always, the fused single-call kernel when its full-N scratch fits
    — pick the one the traffic model says moves the fewest HBM bytes."""
    candidates = [False]
    if _fused_fits(system, block_m, block_n):
        candidates.append(True)
    sdt = _kcommon.canonical_storage_dtype(storage_dtype)
    m = system.batch if system.batch is not None else _kcommon.LANE
    return min(candidates, key=lambda fused: _spec_for(
        system, streamed=True, fused=fused).traffic_bytes(
            system.n, m, system.dtype, sdt))


def _fused_block_n(system: BandedSystem, block_m: int) -> int | None:
    """Largest N-chunk at which BOTH streamed variants fit at ``block_m``
    — the fused kernel's full-N scratch often needs a smaller chunk than
    the two-call pair alone would pick."""
    for bn in _BLOCK_N_CANDIDATES:
        if _fits(system, block_m, bn) and _fused_fits(system, block_m, bn):
            return bn
    return None


def resolve_fused(system: BandedSystem, block_m: int, block_n: int | None, *,
                  fused: bool | None = None, storage_dtype=None) -> bool:
    """Resolve the ``fused`` option once ``(block_m, block_n)`` is tuned.

    ``None`` (auto) runs the traffic-model argmin over the fitting
    streamed variants; explicit values pass through unchanged (the ops
    layer's fit check raises if an explicit ``True`` busts the budget —
    that error names the two-call spill rule).  Resident dispatch
    (``block_n=None``) has nothing to fuse.  Shared between this backend
    and the per-device ``sharded`` tuner."""
    if block_n is None:
        return False
    if fused is None:
        return _pick_fused(system, block_m, block_n,
                           storage_dtype=storage_dtype)
    return bool(fused)


def auto_block_n(system: BandedSystem, block_m: int) -> int | None:
    """Largest streamed N-chunk that fits the budgets at ``block_m`` (None
    if even the smallest does not fit)."""
    for bn in _BLOCK_N_CANDIDATES:
        if _fits(system, block_m, bn):
            return bn
    return None


def auto_tune(system: BandedSystem, *, block_m: int | None = None,
              block_n: int | None = None) -> tuple | None:
    """Resolve ``(block_m, block_n)``; ``block_n=None`` means resident.

    Resident is preferred (one pass, half the RHS traffic); the streamed
    split-N pair is the fallback that lifts the VMEM wall — for batch
    (per-system-LHS) systems as well as shared-LHS ones.  Explicit user
    choices are honoured when they fit, never silently overridden.
    Whether the streamed dispatch then runs FUSED (one call) or as the
    two-call pair is resolved separately by ``_pick_fused`` — the 2-tuple
    contract of this function is unchanged.
    ``None`` also means "no kernel family at all" (periodic x batch —
    this check lives HERE so every tuner caller inherits it;
    ``supports()`` spells out the reason)."""
    if system.periodic and system.mode == "batch":
        return None
    if block_n is not None:
        # explicit streaming request
        for bm in ((block_m,) if block_m is not None else _BLOCK_M_CANDIDATES):
            if _fits(system, bm, block_n):
                return bm, block_n
        return None
    if block_m is not None:
        if _fits(system, block_m):
            return block_m, None
        bn = auto_block_n(system, block_m)
        return (block_m, bn) if bn is not None else None
    bm = auto_block_m(system)
    if bm is not None:
        return bm, None
    best = None
    for bm in _lane_tiles(system):
        bn = auto_block_n(system, bm)
        if bn is None:
            continue
        if best is None:
            best = (bm, bn)  # largest two-call-capable lane tile
        fbn = _fused_block_n(system, bm)
        if fbn is not None and _pick_fused(system, bm, fbn):
            # The modelled HBM bytes are lane-tile independent, so a
            # fused-capable pair at a SMALLER tile still moves ~half the
            # bytes of the two-call pair at a larger one — prefer it (the
            # fused full-N scratch only fits at small block_m for large N).
            return bm, fbn
    return best


def supports(system: BandedSystem, *, block_m: int | None = None,
             block_n: int | None = None) -> tuple:
    """(ok, reason). Used by ``plan(backend="auto")`` for fallback."""
    if system.periodic and system.mode == "batch":
        return False, ("no Pallas kernel for periodic per-system-LHS solves; "
                       "use backend='reference'")
    tuned = auto_tune(system, block_m=block_m, block_n=block_n)
    if tuned is None:
        bm = block_m if block_m is not None else _BLOCK_M_CANDIDATES[-1]
        spec = _spec_for(system, streamed=block_n is not None)
        vmem, smem = _kcommon.spec_working_set(
            spec, system.n, bm, block_n, itemsize=_itemsize(system))
        if block_n is not None:
            # the failing candidate was an explicit streamed request —
            # report the streamed chunk working set, not the resident one
            desc = (f"streamed working set {vmem / 2**20:.1f} MiB "
                    f"(SMEM {smem / 2**10:.0f} KiB) at block_n={block_n}")
            extra = ""
        else:
            desc = (f"working set {vmem / 2**20:.1f} MiB "
                    f"(SMEM {smem / 2**10:.0f} KiB)")
            extra = "; no streamed (block_m, block_n) pair fits either"
        return False, (f"{desc} exceeds the budget "
                       f"(VMEM {_kcommon.VMEM_BUDGET_BYTES / 2**20:.0f} MiB, "
                       f"SMEM {_kcommon.SMEM_BUDGET_BYTES / 2**10:.0f} "
                       f"KiB){extra}")
    bm, bn = tuned
    if bn is None:
        return True, f"block_m={bm}"
    return True, f"streamed block_m={bm} block_n={bn}"


def build_stored(system: BandedSystem):
    """Factor once into the kernel-facing stored pytree.

    Same factors as the reference backend, except uniform mode is kept
    full-vector — the kernel reads a stacked LHS block."""
    from .reference import build_stored as _ref_build
    return _ref_build(system, scalarize_uniform=False)


def _cap_block_m(rhs: jax.Array, block_m: int) -> int:
    # no point tiling wider than the (lane-padded) RHS itself — padding
    # up to a 1024-wide tile for a 96-wide batch wastes ~10x the sweeps
    m_pad = -(-rhs.shape[1] // _kcommon.LANE) * _kcommon.LANE
    return min(block_m, max(m_pad, _kcommon.LANE))


def solve_stored(bandwidth: int, mode: str, periodic: bool, stored,
                 rhs: jax.Array, *, block_m: int, block_n: int | None = None,
                 unroll: int = 1, interpret: bool | None = None,
                 fused: bool = False, storage_dtype=None,
                 prefetch: bool = False) -> jax.Array:
    """Pure kernel dispatch given (static meta, stored pytree, rhs).

    ``block_n=None`` dispatches the VMEM-resident kernels; an integer
    selects the HBM-streamed kernels — the two-call split-N pair, or the
    fused single-call sweep when ``fused=True``.  ``storage_dtype``
    down-casts the streamed RHS (and per-lane diagonals) in HBM (the
    shared factor and carries stay at the fp32-promoted compute dtype);
    ``prefetch`` marks the streamed kernels' lane-tile axis parallel."""
    squeeze = rhs.ndim == 1
    if squeeze:
        rhs = rhs[:, None]
    kw = dict(block_m=_cap_block_m(rhs, block_m), interpret=interpret,
              unroll=unroll, block_n=block_n, fused=fused,
              storage_dtype=storage_dtype, prefetch=prefetch)

    if bandwidth == 3:
        if mode == "batch":
            x = _kops.thomas_batch(stored["a"], stored["b"], stored["c"],
                                   rhs, **kw)
        elif periodic:
            pf = stored
            y = _kops.thomas_constant(pf.factor, rhs, **kw)
            # rank-1 Sherman-Morrison corner correction (paper Eq. 15),
            # shared with the reference sweeps
            x = _tridiag.periodic_corner_correction(pf, y)
        else:
            x = _kops.thomas_constant(stored, rhs, **kw)
    else:
        uniform = mode == "uniform"
        if mode == "batch":
            x = _kops.penta_batch(stored["a"], stored["b"], stored["c"],
                                  stored["d"], stored["e"], rhs, **kw)
        elif periodic:
            pf = stored
            y = _kops.penta_constant(pf.factor, rhs, uniform=uniform, **kw)
            # rank-4 Woodbury corner correction (4 x M dots), shared with
            # the reference sweeps
            x = _penta.periodic_corner_correction(pf, y)
        else:
            x = _kops.penta_constant(stored, rhs, uniform=uniform, **kw)
    return x[:, 0] if squeeze else x


def transpose_solve_stored(bandwidth: int, mode: str, periodic: bool, stored,
                           rhs: jax.Array, *, block_m: int,
                           block_n: int | None = None, unroll: int = 1,
                           interpret: bool | None = None, fused: bool = False,
                           storage_dtype=None,
                           prefetch: bool = False) -> jax.Array:
    """Solve A^T x = rhs ON PALLAS from the SAME stored factor.

    constant/uniform: the engine's transposed kernels (A^T = U^T·L^T from
    the forward's factor vectors — zero extra LHS storage, resident or
    streamed per the forward's tuned blocks).  Periodic corners transpose
    analytically around the kernel: Sherman-Morrison's denominator is the
    stored ``inv_denom_sm`` and the aux solve is the stored ``zt``/``Zt``
    (solved once at factor time, like the forward's ``z``/``Z``).  batch:
    rolling the per-lane diagonal copies turns A^T into another batch
    system, so the FORWARD batch kernels serve the adjoint (the factor
    routines' zero-carry boundary handling makes the rolled-in entries
    inert, exactly as in ``reference.transpose_solve_stored``)."""
    squeeze = rhs.ndim == 1
    if squeeze:
        rhs = rhs[:, None]
    kw = dict(block_m=_cap_block_m(rhs, block_m), interpret=interpret,
              unroll=unroll, block_n=block_n, fused=fused,
              storage_dtype=storage_dtype, prefetch=prefetch)

    if bandwidth == 3:
        if mode == "batch":
            s = stored
            at = jnp.roll(s["c"], 1, axis=0)
            ct = jnp.roll(s["a"], -1, axis=0)
            x = _kops.thomas_batch(at, s["b"], ct, rhs, **kw)
        elif periodic:
            pf = stored
            y = _kops.thomas_constant(pf.factor, rhs, transposed=True, **kw)
            # transposed Sherman-Morrison corner step, shared with the
            # reference sweeps (one home for the factor-convention algebra)
            x = _tridiag.periodic_corner_correction_t(pf, y)
        else:
            x = _kops.thomas_constant(stored, rhs, transposed=True, **kw)
    else:
        uniform = mode == "uniform"
        if mode == "batch":
            s = stored
            at = jnp.roll(s["e"], 2, axis=0)
            bt = jnp.roll(s["d"], 1, axis=0)
            dt = jnp.roll(s["b"], -1, axis=0)
            et = jnp.roll(s["a"], -2, axis=0)
            x = _kops.penta_batch(at, bt, s["c"], dt, et, rhs, **kw)
        elif periodic:
            pf = stored
            y = _kops.penta_constant(pf.factor, rhs, uniform=uniform,
                                     transposed=True, **kw)
            # transposed rank-4 Woodbury corner step, shared with the
            # reference sweeps
            x = _penta.periodic_corner_correction_t(pf, y)
        else:
            x = _kops.penta_constant(stored, rhs, uniform=uniform,
                                     transposed=True, **kw)
    return x[:, 0] if squeeze else x


def tuned_solve_stored(bandwidth: int, mode: str, periodic: bool, stored,
                       rhs: jax.Array, *, block_m: int,
                       block_n: int | None = None, unroll: int = 1,
                       interpret: bool | None = None, fused: bool = False,
                       storage_dtype=None, prefetch: bool = False,
                       transposed: bool = False) -> jax.Array:
    """Mesh-agnostic kernel dispatch on pre-tuned ``(block_m, block_n)``.

    One entry point for BOTH sweep directions of the engine's kernels
    (``transposed=True`` runs the adjoint from the same stored factor) and
    for ANY caller that already resolved its blocks — in particular the
    ``sharded`` backend, which calls this inside its ``shard_map`` body so
    every device runs the SweepSpec-compiled kernels on its local slice
    (DESIGN.md §7).  Nothing here inspects meshes or global shapes: the
    per-lane sweep arithmetic is independent of how M was partitioned, so
    local results are bit-identical to the single-device dispatch (the
    ``block_m`` cap adapts to the local RHS width via ``_cap_block_m``)."""
    fn = transpose_solve_stored if transposed else solve_stored
    return fn(bandwidth, mode, periodic, stored, rhs, block_m=block_m,
              block_n=block_n, unroll=unroll, interpret=interpret,
              fused=fused, storage_dtype=storage_dtype, prefetch=prefetch)


# -- the pure-function contract (repro.solver.functional) -------------------

def _pure_build(system: BandedSystem, *, block_m: int | None = None,
                block_n: int | None = None, unroll: int = 1,
                interpret: bool | None = None, fused: bool | None = None,
                storage_dtype=None, prefetch: bool = True, **_ignored):
    # validate the storage request up front (and freeze it hashably — the
    # dtype NAME goes into SolveMeta.options, ops re-canonicalises it)
    sdt = _kcommon.canonical_storage_dtype(storage_dtype)
    sdt_name = None if sdt is None else sdt.name
    tuned = auto_tune(system, block_m=block_m, block_n=block_n)
    if tuned is None:
        _, why = supports(system, block_m=block_m, block_n=block_n)
        raise NotImplementedError(
            f"pallas backend cannot run {system.describe()}: {why}")
    bm, bn = tuned
    if bn is None and fused:
        # explicit fused request: fused kernels are streamed by
        # construction, so force the streamed family even when the
        # resident kernel would have fit
        bn = auto_block_n(system, bm)
        if bn is None:
            raise NotImplementedError(
                f"pallas backend cannot stream {system.describe()} "
                f"(fused=True requested) at block_m={bm}")
    fused = resolve_fused(system, bm, bn, fused=fused, storage_dtype=sdt)
    return (build_stored(system),
            {"block_m": bm, "block_n": bn, "unroll": unroll,
             "interpret": interpret, "fused": fused,
             "storage_dtype": sdt_name, "prefetch": prefetch})


def _dispatch_opts(meta) -> dict:
    return dict(block_m=meta.opt("block_m"), block_n=meta.opt("block_n"),
                unroll=meta.opt("unroll", 1), interpret=meta.opt("interpret"),
                fused=meta.opt("fused", False),
                storage_dtype=meta.opt("storage_dtype"),
                prefetch=meta.opt("prefetch", False))


def _pure_solve(meta, stored, rhs):
    return solve_stored(meta.bandwidth, meta.mode, meta.periodic, stored, rhs,
                        **_dispatch_opts(meta))


def _pure_transpose(meta, stored, rhs):
    # The adjoint reuses the SAME stored factor via the engine's transposed
    # Pallas kernels (resident, streamed, or fused-streamed, matching the
    # forward's tuned blocks) — large-N gradients never fall back to the
    # reference sweeps.
    return transpose_solve_stored(meta.bandwidth, meta.mode, meta.periodic,
                                  stored, rhs, **_dispatch_opts(meta))


register_pure_backend("pallas", build=_pure_build, solve=_pure_solve,
                      transpose_solve=_pure_transpose)


@register_backend("pallas")
class PallasBackend:
    """Interleaved Pallas TPU kernels (``interpret=True`` off-TPU).

    Thin shim over ``factorize``/``solve``: holds a ``Factorization`` whose
    static meta froze the auto-tuned ``block_m``, and routes solves through
    the differentiable ``custom_vjp`` entry point.
    """

    def __init__(self, system: BandedSystem, *, block_m: int | None = None,
                 block_n: int | None = None, unroll: int = 1,
                 interpret: bool | None = None, fused: bool | None = None,
                 storage_dtype=None, prefetch: bool = True,
                 method=None, mesh=None, batch_axis=None, kernels=None):
        # option-set parity with other backends
        del method, mesh, batch_axis, kernels
        from .functional import factorize
        self.system = system
        self.fact = factorize(system, backend="pallas", block_m=block_m,
                              block_n=block_n, unroll=unroll,
                              interpret=interpret, fused=fused,
                              storage_dtype=storage_dtype, prefetch=prefetch)
        self.block_m = self.fact.meta.opt("block_m")
        self.block_n = self.fact.meta.opt("block_n")
        self.unroll = unroll
        self.interpret = interpret
        self.stored = self.fact.stored

    def solve(self, rhs: jax.Array, *, unroll: int | None = None,
              method=None) -> jax.Array:
        del method  # the sweep schedule is fixed by the kernel
        from .autodiff import solve as _solve
        from .functional import with_options
        fact = self.fact
        if unroll is not None:
            fact = with_options(fact, unroll=unroll)
        return _solve(fact, rhs)
