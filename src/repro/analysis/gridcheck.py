"""gridcheck — prove the streamed 2-D grid's index maps and carry protocol.

The streamed (split-N) kernels run on a grid ``(M/block_m, N/block_n)``
whose LAST axis iterates fastest: for each lane tile the N-chunks execute
sequentially and the sweep state rides a small VMEM scratch between them.
Three things can silently go wrong, and none of them is caught by shape
checking: a write map that misses (or doubles) a block, a backward walk
that does not exactly reverse the forward one, and a carry scratch that
is not reset when the grid wraps to the next lane tile (a cross-lane-tile
carry RACE: tile j+1's first chunk starts from tile j's final sweep
state).  Pallas clamps out-of-range block indices instead of failing, so
an off-by-one index map produces wrong *values*, never an error.

This checker proves all three per registered streamed spec, statically:

  * **write coverage** — enumerating every output ``BlockSpec`` index map
    over the whole grid must hit every block of the output exactly once
    (a bijection onto the block range);
  * **read bounds + mirror** — every chunked input stays inside its
    operand's block range, and within each kernel all N-chunked walks
    agree on one direction: ascending ``0..num_n-1`` in the forward
    kernel, the exact reversal ``num_n-1..0`` in the backward kernel;
  * **carry protocol** — the kernel body is executed OUTSIDE Pallas on
    mock refs (``jax.lax.fori_loop`` / ``pl.when`` / ``pl.program_id``
    swapped for host equivalents), twice per probe: once with a
    zero-filled carry scratch and once with a sentinel-filled one.  At
    ``k == 0`` the outputs must be identical (stale state is dead — the
    ``reset_carry`` contract); at ``k > 0`` they must differ (the carry
    actually threads the sweep across chunks — a kernel that always
    resets is equally wrong).

The mock execution is the "abstract interpretation of the kernel
builders" leg of the tentpole: it runs the *generated* bodies — the same
``functools.partial`` objects ``pl.pallas_call`` would receive — with the
grid made explicit, so a defect in the generic builders (not just the
tables) is caught before anything touches a TPU.
"""

from __future__ import annotations

import contextlib

import jax
import numpy as np
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import engine
from repro.kernels.common import block_shape_of, index_map_of

from . import Finding
from .capture import trace_geometry, trace_spec_calls

_SENTINEL = 0.37  # finite, nonzero, far from any legit zero-carry value


# ---------------------------------------------------------------------------
# Index-map enumeration
# ---------------------------------------------------------------------------

def _block_range(array_shape: tuple, block_shape: tuple) -> tuple:
    return tuple(a // b for a, b in zip(array_shape, block_shape))


def _check_write_coverage(spec, rec, out: list) -> None:
    pts = rec.grid_points()
    for idx, (ospec, oshape) in enumerate(zip(rec.out_specs,
                                              rec.out_shapes)):
        sub = f"{spec.name}.out[{idx}]"
        rng = _block_range(tuple(oshape.shape), block_shape_of(ospec))
        index_map = index_map_of(ospec)
        seen: dict = {}
        for pt in pts:
            blk = tuple(index_map(*pt))
            if any(not (0 <= b < r) for b, r in zip(blk, rng)):
                out.append(Finding("gridcheck", sub,
                                   f"grid point {pt} writes block {blk} "
                                   f"outside the block range {rng} "
                                   f"(Pallas clamps — silent corruption)"))
            elif blk in seen:
                out.append(Finding("gridcheck", sub,
                                   f"grid points {seen[blk]} and {pt} both "
                                   f"write block {blk} — write coverage is "
                                   f"not a bijection"))
            else:
                seen[blk] = pt
        missing = {b for b in np.ndindex(*rng)} - set(seen)
        if missing and not any(f.subject == sub for f in out):
            out.append(Finding("gridcheck", sub,
                               f"blocks never written: {sorted(missing)}"))


def _check_fused_write_coverage(spec, rec, out: list) -> None:
    """Write coverage for the fused ascend/descend grid: the descend-phase
    points (``k >= num_n``) must hit every output block exactly once, and
    every ascend-phase point must PARK the output on the block the first
    descend step overwrites — Pallas writes the bound block back on every
    grid step, so parking anywhere else would clobber finished rows."""
    num_n = rec.grid[-1] // 2
    pts = rec.grid_points()
    for idx, (ospec, oshape) in enumerate(zip(rec.out_specs,
                                              rec.out_shapes)):
        sub = f"{spec.name}.out[{idx}]"
        rng = _block_range(tuple(oshape.shape), block_shape_of(ospec))
        index_map = index_map_of(ospec)
        seen: dict = {}
        for pt in pts:
            blk = tuple(index_map(*pt))
            if any(not (0 <= b < r) for b, r in zip(blk, rng)):
                out.append(Finding("gridcheck", sub,
                                   f"grid point {pt} writes block {blk} "
                                   f"outside the block range {rng} "
                                   f"(Pallas clamps — silent corruption)"))
                continue
            if pt[-1] < num_n:
                first = tuple(index_map(*pt[:-1], num_n))
                if blk != first:
                    out.append(Finding(
                        "gridcheck", sub,
                        f"ascend-phase grid point {pt} parks the output on "
                        f"block {blk}, not on the first descend step's "
                        f"block {first} — the write-back would clobber "
                        f"rows the descend phase has already finished"))
                continue
            if blk in seen:
                out.append(Finding("gridcheck", sub,
                                   f"descend-phase grid points {seen[blk]} "
                                   f"and {pt} both write block {blk} — "
                                   f"write coverage is not a bijection"))
            else:
                seen[blk] = pt
        missing = {b for b in np.ndindex(*rng)} - set(seen)
        if missing and not any(f.subject == sub for f in out):
            out.append(Finding("gridcheck", sub,
                               f"blocks never written by the descend "
                               f"phase: {sorted(missing)}"))


def _chunk_walks(rec, arg_shapes, specs) -> list:
    """(spec_idx, walk) for each N-chunked spec: the sequence of N-chunk
    indices visited as the fast grid axis k advances at fixed j=0."""
    walks = []
    num_n = rec.grid[-1]
    for idx, (spec_, shape) in enumerate(zip(specs, arg_shapes)):
        index_map = index_map_of(spec_)
        bshape = block_shape_of(spec_)
        if bshape == (1, 1):
            continue
        walk = [index_map(0, k) for k in range(num_n)]
        # which tuple position varies with k = the N-chunk coordinate
        varying = [d for d in range(len(walk[0]))
                   if len({w[d] for w in walk}) > 1]
        if not varying:
            continue  # constant over k (a resident block) — not a walk
        walks.append((idx, [w[varying[0]] for w in walk]))
    return walks


def _check_read_bounds(spec, rec, out: list) -> None:
    pts = rec.grid_points()
    for idx, (ispec, shape) in enumerate(zip(rec.in_specs, rec.arg_shapes)):
        sub = f"{spec.name}.in[{idx}]"
        rng = _block_range(tuple(shape), block_shape_of(ispec))
        index_map = index_map_of(ispec)
        bad = sorted({tuple(index_map(*pt)) for pt in pts
                      if any(not (0 <= b < r)
                             for b, r in zip(index_map(*pt), rng))})
        if bad:
            out.append(Finding("gridcheck", sub,
                               f"blocks read outside the block range "
                               f"{rng}: {bad} (Pallas clamps — the kernel "
                               f"would silently re-read an edge chunk)"))


def _check_walk(spec, rec, direction: str, want: list, out: list) -> None:
    """Every N-chunked operand of one kernel walks chunks in ``want``."""
    specs = tuple(rec.in_specs) + tuple(rec.out_specs)
    shapes = tuple(rec.arg_shapes) + tuple(
        tuple(o.shape) for o in rec.out_shapes)
    walks = _chunk_walks(rec, shapes, specs)
    if not walks:
        out.append(Finding("gridcheck", spec.name,
                           f"{direction} kernel has no N-chunked "
                           f"operand at all"))
        return
    for idx, walk in walks:
        if walk != want:
            out.append(Finding(
                "gridcheck", f"{spec.name}.{direction}",
                f"operand {idx} walks N-chunks {walk}, expected "
                f"{want} — the backward maps must exactly mirror the "
                f"forward chunk walk" if direction == "backward" else
                f"operand {idx} walks N-chunks {walk}, expected the "
                f"{direction} walk {want}"))


def _check_mirror(spec, records, out: list) -> None:
    """Forward kernel walks chunks ascending; backward exactly reversed."""
    num_n = records[0].grid[-1]
    ascending = list(range(num_n))
    _check_walk(spec, records[0], "forward", ascending, out)
    _check_walk(spec, records[1], "backward", ascending[::-1], out)


def _check_fused_walks(spec, rec, out: list) -> None:
    """One kernel, two phases on a ``2 * num_n`` chunk axis: the chunk
    inputs ascend ``0..num_n-1`` then park; the output parks then descends
    ``num_n-1..0`` (the mirrored maps); the shared LHS walks the mirror
    ``0..num_n-1..0``.  A descend map that forgets the mirror shows up
    here as the wrong walk."""
    from .capture import TRACE_BLOCK_M
    num_n = rec.grid[-1] // 2
    ks = range(2 * num_n)
    asc_park = [min(k, num_n - 1) for k in ks]
    park_desc = [min(2 * num_n - 1 - k, num_n - 1) for k in ks]
    mirror = [min(k, 2 * num_n - 1 - k) for k in ks]
    specs = tuple(rec.in_specs) + tuple(rec.out_specs)
    n_in = len(rec.in_specs)
    for idx, spec_ in enumerate(specs):
        bshape = block_shape_of(spec_)
        if bshape == (1, 1):
            continue
        sub = f"{spec.name}.fused[{'out' if idx >= n_in else 'in'}]"
        index_map = index_map_of(spec_)
        walk = [index_map(0, k) for k in ks]
        varying = [d for d in range(len(walk[0]))
                   if len({w[d] for w in walk}) > 1]
        if not varying:
            out.append(Finding("gridcheck", sub,
                               f"operand {idx} never varies with the "
                               f"N-chunk axis — a fused kernel streams "
                               f"every non-scalar operand"))
            continue
        got = [w[varying[0]] for w in walk]
        if idx >= n_in:
            want, label = park_desc, "park-then-descend (mirrored output)"
        elif bshape[-1] == TRACE_BLOCK_M:
            want, label = asc_park, "ascend-then-park (chunk operand)"
        else:
            want, label = mirror, "the shared-LHS mirror 0..num_n-1..0"
        if got != want:
            out.append(Finding(
                "gridcheck", sub,
                f"operand {idx} walks N-chunks {got}, expected "
                f"{label}: {want}"))


def _check_recurrence_walk(spec, rec, out: list) -> None:
    """The single recurrence kernel walks chunks ascending, or exactly
    reversed for the reverse variants — all operands agreeing."""
    num_n = rec.grid[-1]
    ascending = list(range(num_n))
    want = ascending[::-1] if spec.reverse else ascending
    direction = "descending" if spec.reverse else "ascending"
    _check_walk(spec, rec, direction, want, out)


# ---------------------------------------------------------------------------
# Mock-executing the kernel bodies (carry protocol)
# ---------------------------------------------------------------------------

class _MockRef:
    """A numpy-backed stand-in for a Pallas ref, good enough for the
    engine's access idioms: ``ref[pl.ds(i, 1), :]``, ``ref[r:r+1,
    pl.ds(i, 1)]``, ``ref[...] = x``, ``jnp.zeros_like(ref)``."""

    def __init__(self, arr):
        self.arr = np.array(arr, dtype=np.float32)

    @property
    def shape(self):
        return self.arr.shape

    @property
    def dtype(self):
        return self.arr.dtype

    def __jax_array__(self):
        return jnp.asarray(self.arr)

    @staticmethod
    def _one(ix):
        if hasattr(ix, "start") and hasattr(ix, "size") and \
                not isinstance(ix, slice):          # pl.ds -> Slice
            start, stride = int(ix.start), int(ix.stride)
            return slice(start, start + int(ix.size) * stride, stride)
        return ix

    def _key(self, key):
        if key is Ellipsis:
            return key
        if isinstance(key, tuple):
            return tuple(self._one(k) for k in key)
        return self._one(key)

    def __getitem__(self, key):
        # a copy: jnp.asarray may alias the numpy view, which the kernel's
        # later stores would then change under a pending computation
        return jnp.asarray(np.array(self.arr[self._key(key)]))

    def __setitem__(self, key, val):
        self.arr[self._key(key)] = np.asarray(val)


@contextlib.contextmanager
def _host_kernel_env(program_ids: list):
    """Run kernel bodies eagerly on the host: fori_loop becomes a Python
    loop (so ref indices stay concrete ints), ``pl.when`` executes on the
    concrete predicate, ``pl.program_id`` reads ``program_ids``."""
    real_fori = jax.lax.fori_loop
    real_when = pl.when
    real_pid = pl.program_id

    def fori(lo, hi, body, init, **_kw):
        carry = init
        for t in range(int(lo), int(hi)):
            carry = body(t, carry)
        return carry

    def when(cond):
        def deco(fn):
            if bool(cond):
                fn()
            return fn
        return deco

    jax.lax.fori_loop = fori
    pl.when = when
    pl.program_id = lambda axis: program_ids[axis]
    try:
        yield
    finally:
        jax.lax.fori_loop = real_fori
        pl.when = real_when
        pl.program_id = real_pid


def _operand_data(spec, rec, rng) -> list:
    """Finite, well-conditioned block data per input operand.  For batch
    layouts the main diagonal must dominate — the fused factorisation
    divides by it in-kernel.  (Recurrence gates at 0.2–0.9 are stable
    contractions; nothing divides.)"""
    data = []
    main = {3: 1, 5: 2}[spec.bandwidth] if spec.layout == "batch" else None
    for idx, ispec in enumerate(rec.in_specs):
        shape = block_shape_of(ispec)
        block = rng.uniform(0.2, 0.9, size=shape)
        if main is not None and idx == main and idx < spec.bandwidth:
            block = rng.uniform(2.5, 3.5, size=shape)
        data.append(block.astype(np.float32))
    return data


def _run_probe(rec, in_data, carry_fill, pid) -> list:
    """Execute the kernel body once; returns everything the grid step can
    write besides the carry: the outputs plus any non-carry (fused sweep)
    scratch.  The carry is the LAST scratch operand by builder convention
    and gets ``carry_fill``; other scratch (the fused kernels' full-N
    intermediates) is seeded with a fixed nonzero value so the descend
    phase has live coefficients to thread the carry through."""
    ins = [_MockRef(d) for d in in_data]
    outs = [_MockRef(np.zeros(block_shape_of(s), np.float32))
            for s in rec.out_specs]
    n_scr = len(rec.scratch_shapes)
    scratch = [_MockRef(np.full(tuple(s.shape),
                                carry_fill if i == n_scr - 1 else _SENTINEL,
                                np.float32))
               for i, s in enumerate(rec.scratch_shapes)]
    with _host_kernel_env(list(pid)):
        rec.kernel(*ins, *outs, *scratch)
    return [o.arr for o in outs] + [s.arr for s in scratch[:-1]]


def _check_carry_protocol(spec, records, out: list) -> None:
    fused = getattr(spec, "fused", False)
    labels = (("fused",) if fused
              else ("recurrence",) if len(records) == 1
              else ("forward", "backward"))
    for which, rec in zip(labels, records):
        if not rec.scratch_shapes:
            out.append(Finding("gridcheck", f"{spec.name}.{which}",
                               "streamed kernel has no carry scratch — "
                               "the sweep state cannot thread N-chunks"))
            continue
        rng = np.random.default_rng(3)
        in_data = _operand_data(spec, rec, rng)
        sub = f"{spec.name}.{which}"
        # probe both phase starts for fused kernels: the carry resets at
        # k == 0 (fresh lane tile) AND at k == num_n (descend handover)
        num_n = rec.grid[-1] // 2 if fused else None
        phases = [("k == 0", (1, 0), (0, 1))]
        if fused:
            phases.append((f"k == num_n ({num_n})",
                           (0, num_n), (0, num_n + 1)))
        for phase, reset_pid, thread_pid in phases:
            # phase start: stale carry state must be DEAD
            base = _run_probe(rec, in_data, 0.0, reset_pid)
            stale = _run_probe(rec, in_data, _SENTINEL, reset_pid)
            if any(not np.array_equal(b, s) for b, s in zip(base, stale)):
                out.append(Finding(
                    "gridcheck", sub,
                    f"stale carry scratch leaks into the {phase} chunk — "
                    f"reset_carry missing/broken: the next sweep phase "
                    f"would start from the previous one's final carry "
                    f"state (carry race)"))
            # mid-phase: the carry must actually participate
            base = _run_probe(rec, in_data, 0.0, thread_pid)
            threaded = _run_probe(rec, in_data, _SENTINEL, thread_pid)
            if all(np.array_equal(b, t) for b, t in zip(base, threaded)):
                out.append(Finding(
                    "gridcheck", sub,
                    f"carry scratch is ignored just after {phase} — the "
                    f"sweep state does not thread across N-chunks (the "
                    f"kernel resets unconditionally or never reads its "
                    f"carry)"))


def _check_packed_layout(spec, rec, out: list) -> None:
    """The packed kernel body and its row sibling's, both run on the host
    on one block of the same data, must agree bit for bit: a wrong group
    base or stride in the lane-tile-major layout sweeps other systems'
    rows (or the same row twice), which no index map shows."""
    row_rec = trace_spec_calls(engine.REGISTRY[spec.row_name],
                               geometry=trace_geometry(spec))[0]
    in_data = _operand_data(spec, rec, np.random.default_rng(5))
    try:
        got = _run_probe(rec, in_data, 0.0, (0,))[0]
    except (IndexError, ValueError) as exc:
        out.append(Finding("gridcheck", spec.name,
                           f"packed layout addresses outside its scratch "
                           f"({type(exc).__name__}: {exc})"))
        return
    want = _run_probe(row_rec, in_data, 0.0, (0,))[0]
    if not np.array_equal(got, want):
        bad = np.argwhere(got != want)
        out.append(Finding(
            "gridcheck", spec.name,
            f"packed layout differs from the row sweep at "
            f"{len(bad)} of {got.size} elements (first at row, lane "
            f"{tuple(int(v) for v in bad[0])}) — a wrong group base or "
            f"stride in the lane-tile-major scratch"))


def run() -> list:
    """All gridcheck invariants over every registered streamed spec (the
    resident kernels have a trivial 1-D grid, checked for coverage too;
    packed kernels are also run against their row sibling)."""
    out: list = []
    for name in sorted(engine.REGISTRY):
        spec = engine.REGISTRY[name]
        fused = getattr(spec, "fused", False)
        records = trace_spec_calls(spec)
        for rec in records:
            if fused:
                _check_fused_write_coverage(spec, rec, out)
            else:
                _check_write_coverage(spec, rec, out)
            _check_read_bounds(spec, rec, out)
        if getattr(spec, "packed", False):
            _check_packed_layout(spec, records[0], out)
        if not spec.streamed:
            continue
        if len(records) != spec.num_pallas_calls:
            out.append(Finding("gridcheck", spec.name,
                               f"streamed spec emitted {len(records)} "
                               f"pallas_call(s), expected "
                               f"{spec.num_pallas_calls}"))
            continue
        if isinstance(spec, engine.RecurrenceSpec):
            _check_recurrence_walk(spec, records[0], out)
        elif fused:
            _check_fused_walks(spec, records[0], out)
        else:
            _check_mirror(spec, records, out)
        _check_carry_protocol(spec, records, out)
    return out
