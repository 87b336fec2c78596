"""``sharded`` backend: the paper's single-LHS idea at cluster scale.

One LHS copy per DEVICE (replicated — the paper's storage saving applied
per device), the M system axis sharded across a mesh, zero collectives in
the solve: systems are independent, so each device solves its local slice
of the interleaved batch.

Since the sharded x streamed composition (DESIGN.md §7) each device runs
the sweep engine's Pallas kernels — the SAME ``SweepSpec``-compiled
resident or HBM-streamed pairs the single-device ``pallas`` backend
dispatches — instead of reference scans inside ``shard_map``.  A
per-device tuner (``local_tune``) resolves ``(block_m, block_n)`` against
the LOCAL lane count (``kernels.common.shard_lanes``): resident at the
largest lane tile the VMEM budget allows, falling through to the 2-D
streamed split-N pair past the wall, exactly the single-device policy but
sized to the shard.  Modes with no kernel (periodic x batch) and
pathologically small budgets degrade per-shard to the reference sweeps —
the ``kernels`` option ("auto" | "pallas" | "reference") makes the policy
explicit and ``SolveMeta`` records what was resolved.

For ``mode="batch"`` the per-system LHS copies are sharded *with* their
systems (each device only holds the diagonals of its own slice).  The M
axis is padded to a multiple of the mesh size with identity rows
(``main diagonal = 1``) so padded lanes solve trivially and are sliced off.

The pure-function contract: the resolved ``Mesh`` (hashable) rides in the
``Factorization``'s static meta, so a sharded solve crosses ``jit``/``grad``
/``lax.scan`` like any other — the ``shard_map`` is retraced only when the
mesh itself changes.  The adjoint solve is sharded too: the same
``shard_map`` dispatch runs the engine's TRANSPOSED kernels (or the
reference transposed sweeps when kernels are off) on the SAME stored
factor, so large-N ``grad(solve)`` through a mesh stays on Pallas.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.kernels.common import (canonical_storage_dtype, default_interpret,
                                  pad_lanes, shard_lanes)

from .reference import (build_stored, solve_stored, transpose_solve_stored)
from .registry import register_backend, register_pure_backend
from .system import BandedSystem

#: What each shard runs. "auto" = engine Pallas kernels when a SweepSpec
#: serves the mode and fits the per-device budget, else reference sweeps;
#: "pallas" forces the kernels (raising like the pallas backend when it
#: cannot); "reference" keeps the pre-composition scan sweeps.
KERNEL_POLICIES = ("auto", "pallas", "reference")


def default_mesh(axis_name: str = "batch") -> Mesh:
    """1-D mesh over every visible device."""
    return Mesh(np.array(jax.devices()), (axis_name,))


def resolve_mesh(mesh: Mesh | None, batch_axis):
    """(mesh, batch_axis, n_shards) with the defaulting rules of PR 1."""
    if mesh is None:
        mesh = default_mesh()
        batch_axis = mesh.axis_names[0]
    elif batch_axis is None:
        batch_axis = mesh.axis_names[-1]
    axes = batch_axis if isinstance(batch_axis, tuple) else (batch_axis,)
    # host-side: mesh axis sizes are static Python ints, never traced
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))  # speclint: allow-concretize
    return mesh, batch_axis, n_shards


def local_system(system: BandedSystem, n_shards: int) -> BandedSystem:
    """The spec one DEVICE sees: same N (the sweep axis is never sharded),
    batch-mode lane count divided by the mesh (after mesh padding).

    This is what the per-device tuner sizes against — the resident-vs-
    streamed decision depends on N and the VMEM budget, but the lane-tile
    cap must reflect the LOCAL slice, not the global batch."""
    if system.mode != "batch":
        return system
    return dataclasses.replace(system,
                               batch=shard_lanes(system.batch, n_shards))


def local_tune(system: BandedSystem, n_shards: int, *,
               block_m: int | None = None,
               block_n: int | None = None) -> tuple | None:
    """Per-device ``(block_m, block_n)`` — the single-device 2-D auto-tune
    (``pallas.auto_tune``) run on the LOCAL system view.  ``None`` when no
    kernel configuration fits, or no kernel family serves the mode at all
    (the caller falls back to reference sweeps per shard)."""
    from . import pallas as _pallas
    return _pallas.auto_tune(local_system(system, n_shards),
                             block_m=block_m, block_n=block_n)


def sharded_solve_stored(bandwidth: int, mode: str, periodic: bool, n: int,
                         stored, rhs: jax.Array, *, mesh: Mesh, batch_axis,
                         n_shards: int, diagonal_names: tuple = (),
                         method: str = "scan", unroll: int = 1,
                         kernels: str = "reference",
                         block_m: int | None = None,
                         block_n: int | None = None,
                         interpret: bool | None = None,
                         fused: bool = False, storage_dtype=None,
                         prefetch: bool = False,
                         transposed: bool = False) -> jax.Array:
    """Pure shard_map dispatch given (static meta, stored pytree, rhs).

    ``kernels="pallas"`` routes every shard through the engine's tuned
    kernel dispatch (``pallas.tuned_solve_stored`` — resident or
    HBM-streamed per the frozen ``(block_m, block_n)``, transposed for the
    adjoint); ``"reference"`` runs the scan sweeps per shard.  Padding the
    M axis to the mesh size uses the kernels' shared ``pad_lanes``:
    per-system MAIN-diagonal copies identity-pad (b = 1) so the dead
    padded lanes factor as identity solves instead of 1/0."""
    # The varying-axes check stays on, except around the Pallas
    # interpreter: JAX 0.9's interpreter runs a kernel body traced without
    # the operands' varying axes, so its grid loop fails the check.  The
    # compiled kernels type their outputs with the operands' axes
    # (``engine._out_struct``) and run checked.
    check_vma = True
    if kernels == "pallas":
        from . import pallas as _pallas
        if interpret is None:
            interpret = default_interpret()
        check_vma = not interpret

        def local_solve(st, r):
            return _pallas.tuned_solve_stored(
                bandwidth, mode, periodic, st, r, block_m=block_m,
                block_n=block_n, unroll=unroll, interpret=interpret,
                fused=fused, storage_dtype=storage_dtype, prefetch=prefetch,
                transposed=transposed)
    else:
        ref_fn = transpose_solve_stored if transposed else solve_stored

        def local_solve(st, r):
            return ref_fn(bandwidth, mode, periodic, n, st, r,
                          method=method, unroll=unroll)

    squeeze = rhs.ndim == 1
    if squeeze:
        rhs = rhs[:, None]
    m = rhs.shape[1]
    spec = P(None, batch_axis)

    if mode == "batch":
        main = diagonal_names[bandwidth // 2]
        padded = {k: pad_lanes(v, n_shards, identity=(k == main))[0]
                  for k, v in stored.items()}
        fn = jax.shard_map(local_solve, mesh=mesh, in_specs=(spec, spec),
                           out_specs=spec, check_vma=check_vma)
        x = fn(padded, pad_lanes(rhs, n_shards)[0])
    else:
        # replicated: closed over, one copy per device
        fn = jax.shard_map(lambda r: local_solve(stored, r), mesh=mesh,
                           in_specs=(spec,), out_specs=spec,
                           check_vma=check_vma)
        x = fn(pad_lanes(rhs, n_shards)[0])

    x = x[:, :m]
    return x[:, 0] if squeeze else x


# -- the pure-function contract (repro.solver.functional) -------------------

_TRI_NAMES = ("a", "b", "c")
_PENTA_NAMES = ("a", "b", "c", "d", "e")


def _pure_build(system: BandedSystem, *, mesh: Mesh | None = None,
                batch_axis=None, method: str = "scan", unroll: int = 1,
                kernels: str = "auto", block_m: int | None = None,
                block_n: int | None = None, interpret: bool | None = None,
                fused: bool | None = None, storage_dtype=None,
                prefetch: bool = True, **_ignored):
    if kernels not in KERNEL_POLICIES:
        raise ValueError(f"kernels must be one of {KERNEL_POLICIES}, "
                         f"got {kernels!r}")
    sdt = canonical_storage_dtype(storage_dtype)
    mesh, batch_axis, n_shards = resolve_mesh(mesh, batch_axis)
    opts = {"mesh": mesh, "batch_axis": batch_axis, "n_shards": n_shards,
            "method": method, "unroll": unroll}

    tuned = None
    if kernels != "reference":
        tuned = local_tune(system, n_shards, block_m=block_m,
                           block_n=block_n)
        if tuned is None and kernels == "pallas":
            from . import pallas as _pallas
            _, why = _pallas.supports(local_system(system, n_shards),
                                      block_m=block_m, block_n=block_n)
            raise NotImplementedError(
                f"sharded backend cannot run the engine kernels per shard "
                f"for {system.describe()}: {why}")

    # `shard_build` records which layout the stored factor was BUILT for;
    # `_dispatch` compares it against `kernels` so a post-hoc override
    # cannot route a mismatched pytree into the wrong sweep path.
    if tuned is not None:
        from . import pallas as _pallas
        bm, bn = tuned
        # per-device fused resolution: same traffic-model argmin as the
        # single-device tuner, sized against the LOCAL system view
        fused = _pallas.resolve_fused(local_system(system, n_shards), bm, bn,
                                      fused=fused, storage_dtype=sdt)
        opts.update(kernels="pallas", shard_build="pallas", block_m=bm,
                    block_n=bn, interpret=interpret, fused=fused,
                    storage_dtype=None if sdt is None else sdt.name,
                    prefetch=prefetch)
        return _pallas.build_stored(system), opts

    opts.update(kernels="reference", shard_build="reference")
    return build_stored(system, method=method), opts


def _dispatch(meta, stored, rhs, *, transposed: bool):
    # `kernels` is RESOLVED at factorize time: the stored-factor layout and
    # the tuned (block_m, block_n) are bound to the policy that built them
    # (recorded as `shard_build`), so a post-hoc `with_options(fact,
    # kernels=...)` flip would dispatch a mismatched pytree.
    kernels = meta.opt("kernels", "reference")
    if kernels != meta.opt("shard_build", kernels):
        raise ValueError(
            "the sharded backend's `kernels` policy is resolved at factorize "
            "time and cannot be overridden per call; re-factorize with "
            f"kernels={kernels!r} instead")
    names = _TRI_NAMES if meta.bandwidth == 3 else _PENTA_NAMES
    return sharded_solve_stored(
        meta.bandwidth, meta.mode, meta.periodic, meta.n, stored, rhs,
        mesh=meta.opt("mesh"), batch_axis=meta.opt("batch_axis"),
        n_shards=meta.opt("n_shards"), diagonal_names=names,
        method=meta.opt("method", "scan"), unroll=meta.opt("unroll", 1),
        kernels=meta.opt("kernels", "reference"),
        block_m=meta.opt("block_m"), block_n=meta.opt("block_n"),
        interpret=meta.opt("interpret"), fused=meta.opt("fused", False),
        storage_dtype=meta.opt("storage_dtype"),
        prefetch=meta.opt("prefetch", False), transposed=transposed)


def _pure_solve(meta, stored, rhs):
    return _dispatch(meta, stored, rhs, transposed=False)


def _pure_transpose(meta, stored, rhs):
    # The adjoint is sharded too: transposed systems are just as
    # independent over M, so the same shard_map dispatch runs the engine's
    # transposed kernels (or the reference transposed sweeps) per device,
    # reusing the SAME stored factor that served the forward solve.
    return _dispatch(meta, stored, rhs, transposed=True)


register_pure_backend("sharded", build=_pure_build, solve=_pure_solve,
                      transpose_solve=_pure_transpose)


@register_backend("sharded")
class ShardedBackend:
    """shard_map over a device mesh (thin functional shim).

    The LHS is replicated per device (batch mode: sharded with its
    systems) and each shard runs the engine's tuned Pallas kernels when
    ``kernels`` resolves to them (the default ``"auto"`` policy), else the
    reference sweeps.
    """

    def __init__(self, system: BandedSystem, *, mesh: Mesh | None = None,
                 batch_axis: str | tuple | None = None, method: str = "scan",
                 unroll: int = 1, kernels: str = "auto",
                 block_m: int | None = None, block_n: int | None = None,
                 interpret: bool | None = None, fused: bool | None = None,
                 storage_dtype=None, prefetch: bool = True):
        from .functional import factorize
        self.system = system
        self.fact = factorize(system, backend="sharded", mesh=mesh,
                              batch_axis=batch_axis, method=method,
                              unroll=unroll, kernels=kernels,
                              block_m=block_m, block_n=block_n,
                              interpret=interpret, fused=fused,
                              storage_dtype=storage_dtype, prefetch=prefetch)
        self.stored = self.fact.stored
        self.mesh = self.fact.meta.opt("mesh")
        self.batch_axis = self.fact.meta.opt("batch_axis")
        self.n_shards = self.fact.meta.opt("n_shards")
        self.kernels = self.fact.meta.opt("kernels")
        self.block_m = self.fact.meta.opt("block_m")
        self.block_n = self.fact.meta.opt("block_n")

    def solve(self, rhs: jax.Array, *, method: str | None = None,
              unroll: int | None = None) -> jax.Array:
        from .autodiff import solve as _solve
        from .functional import with_options
        fact = self.fact
        if method is not None or unroll is not None:
            fact = with_options(fact, method=method, unroll=unroll)
        return _solve(fact, rhs)
