"""Abstract interpretation of the kernel builders: capture, don't run.

The engine's builders (``shared_solver`` / ``batch_solver``) are ordinary
Python that ends in ``pl.pallas_call(...)``.  Everything the static
checkers need — the grid, every ``BlockSpec`` index map, the scratch
shapes, which operands feed which pass — is fully determined at trace
time, before any kernel body executes.  So the capture layer swaps
``pl.pallas_call`` for a recorder that logs the call and returns
zero-filled outputs of the declared ``out_shape``, then drives the
UNJITTED builder entry point (``solver.__wrapped__``) on
``SweepSpec.dummy_args``.  No Pallas kernel ever runs; the records are
the kernels' complete stream structure.

From the records two independent recounts are derived:

  * ``recount_traffic_words`` — HBM<->VMEM words, counted as *distinct
    blocks touched* per operand per ``pallas_call`` (compulsory traffic:
    a constant index map keeps its block resident, a chunked map streams
    each chunk once).  ``(1, 1)`` blocks are broadcast scalar parameters
    (the uniform eps) and are counted once per solve, deduplicated by
    buffer identity across the pass pair — matching the model's ``+ eps``
    convention.
  * ``recount_vmem_counts`` — the per-grid-step working set
    ``(n_blocks, n_lhs_rows, n_carry_rows, n_sweep_scratch)``, classified
    from the specs: VMEM blocks of the in/out specs are the pipelined
    (double-buffered) blocks, SMEM blocks are the stacked shared LHS
    (their row count), small ``(c, block_m)`` scratch rows are the sweep
    carries, and scratch holding a whole lane block over the FULL output
    N extent is the full-N scratch (``SweepSpec.sweep_scratch``: the fused
    kernels' intermediate, the resident batch kernel's factor
    coefficients, the packed sweep's lane-tile-major copy).  The
    streamed pair reports the elementwise max over its two kernels (the
    forward's larger set — exactly what the budget check reasons with).

Both recounts are cross-checked in ``speccheck`` against the numbers
``SweepSpec`` *derives* (``traffic_words`` / ``vmem_counts``): the model
and the code can only drift together or not at all.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import math

import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.common import PACK, block_shape_of, in_smem, index_map_of
from repro.kernels.engine import (SweepSpec, batch_solver, recurrence_solver,
                                  shared_solver)

#: Reference shapes the checkers trace at — small enough to enumerate the
#: grid exhaustively, ragged-free (the builders require padded operands),
#: and chosen so the three block classes cannot collide: the lane tile
#: (8) differs from the N-chunk (16), the full sweep (48), and any carry
#: row count (<= 6).
TRACE_N, TRACE_M = 48, 24
TRACE_BLOCK_M, TRACE_BLOCK_N = 8, 16
#: The packed sweep's lane tile holds whole packed groups (1024 lanes
#: each): it is traced at two groups per tile and three tiles.
TRACE_PACKED_BLOCK_M = 2 * PACK


def trace_geometry(spec) -> tuple:
    """``(n, m, block_m)`` the checkers trace ``spec`` at: the reference
    shapes above, or for a packed spec a lane tile of whole packed
    groups."""
    if getattr(spec, "packed", False):
        return TRACE_N, 3 * TRACE_PACKED_BLOCK_M, TRACE_PACKED_BLOCK_M
    return TRACE_N, TRACE_M, TRACE_BLOCK_M


@dataclasses.dataclass(frozen=True)
class CallRecord:
    """One captured ``pl.pallas_call``: its grid, specs, and operands."""

    kernel: object        # the kernel body (a functools.partial)
    grid: tuple
    in_specs: tuple       # BlockSpec per operand
    out_specs: tuple      # BlockSpec per output
    out_shapes: tuple     # ShapeDtypeStruct per output
    scratch_shapes: tuple # MemoryRef per scratch operand
    arg_ids: tuple        # id() of each operand buffer (scalar-param dedupe)
    arg_shapes: tuple
    name: str | None = None   # the ``name=`` the builder gave the call

    def grid_points(self) -> list:
        return list(itertools.product(*(range(g) for g in self.grid)))

    def blocks_of(self, spec, shape=None) -> set:
        """Distinct block-index tuples ``spec`` touches over the grid."""
        index_map = index_map_of(spec)
        return {tuple(index_map(*pt)) for pt in self.grid_points()}


@contextlib.contextmanager
def capture_pallas_calls():
    """Swap ``pl.pallas_call`` for a recorder; yields the record list.

    The recorder returns zero-filled arrays of the declared ``out_shape``
    so multi-call builders (streamed pairs feeding the mid result into
    the second call) keep composing.  Single-threaded use only — the
    patch is process-global while the context is open.
    """
    records = []
    real = pl.pallas_call

    def fake_pallas_call(kernel, *, grid, in_specs, out_specs, out_shape,
                         scratch_shapes=(), name=None, **_kwargs):
        multi = isinstance(out_shape, (list, tuple))
        outs = tuple(out_shape) if multi else (out_shape,)
        ospecs = (tuple(out_specs) if isinstance(out_specs, (list, tuple))
                  else (out_specs,))

        def runner(*args):
            records.append(CallRecord(
                kernel=kernel, grid=tuple(grid),
                in_specs=tuple(in_specs), out_specs=ospecs, out_shapes=outs,
                scratch_shapes=tuple(scratch_shapes),
                arg_ids=tuple(id(a) for a in args),
                arg_shapes=tuple(tuple(a.shape) for a in args),
                name=name))
            res = [jnp.zeros(o.shape, o.dtype) for o in outs]
            return res if multi else res[0]

        return runner

    pl.pallas_call = fake_pallas_call
    try:
        yield records
    finally:
        pl.pallas_call = real


def trace_spec_calls(spec, *, block_n: int = TRACE_BLOCK_N,
                     geometry: tuple | None = None) -> list:
    """Drive ``spec``'s builder on dummy operands at ``geometry``
    (``(n, m, block_m)``, default its ``trace_geometry``), returning the
    captured ``CallRecord`` list — ``spec.num_pallas_calls`` records: one
    for resident variants and for every recurrence (single-pass), the
    forward/backward pair for streamed sweeps."""
    n, m, block_m = geometry or trace_geometry(spec)
    assert m % block_m == 0 and n % block_n == 0
    args, eps = spec.dummy_args(n, m)
    kwargs = dict(block_m=block_m, interpret=True)
    if spec.streamed:
        kwargs["block_n"] = block_n
    if getattr(spec, "uniform", False):
        kwargs["eps"] = eps
    builder = {"shared": shared_solver, "batch": batch_solver,
               "recurrence": recurrence_solver}[spec.layout]
    # .__wrapped__ bypasses jax.jit: the builder body re-executes on every
    # call, so the capture sees the pallas_calls even for cached specs.
    with capture_pallas_calls() as records:
        builder(spec).__wrapped__(*args, **kwargs)
    return records


def _is_scalar_param(shape: tuple) -> bool:
    """(1, 1) blocks are broadcast scalar parameters (the uniform eps)."""
    return math.prod(shape) == 1


def recount_traffic_words(records: list) -> int:
    """Independent HBM traffic recount (words) from the captured calls."""
    words = 0
    seen_params = set()
    for rec in records:
        for spec_, buf in zip(rec.in_specs, rec.arg_ids):
            shape = block_shape_of(spec_)
            if _is_scalar_param(shape):
                if buf not in seen_params:
                    seen_params.add(buf)
                    words += 1
                continue
            words += len(rec.blocks_of(spec_)) * math.prod(shape)
        for spec_ in rec.out_specs:
            shape = block_shape_of(spec_)
            words += len(rec.blocks_of(spec_)) * math.prod(shape)
    return words


def recount_vmem_counts(records: list, *, block_m: int = TRACE_BLOCK_M
                        ) -> tuple:
    """Independent ``(n_blocks, n_lhs_rows, n_carry_rows,
    n_sweep_scratch)`` recount — the elementwise max over the captured
    kernels' per-grid-step sets.

    The fourth slot counts full-N VMEM scratch: scratch holding
    ``block_m`` lanes over the full output sweep, in any arrangement (the
    fused kernels' intermediates, the resident batch kernel's
    coefficients, the packed sweep's ``(block_m / 128 * N, 128)``
    lane-tile-major copy)."""
    counts = (0, 0, 0, 0)
    for rec in records:
        blocks = lhs = carry = sweep = 0
        n_extents = {tuple(o.shape)[0] for o in rec.out_shapes}
        for spec_ in tuple(rec.in_specs) + tuple(rec.out_specs):
            shape = block_shape_of(spec_)
            if _is_scalar_param(shape):
                continue
            if in_smem(spec_):
                lhs += shape[0]      # the stacked shared LHS rows
            else:
                blocks += 1          # a pipelined VMEM block
        for scratch in rec.scratch_shapes:
            shape = tuple(scratch.shape)
            if math.prod(shape) in {n * block_m for n in n_extents}:
                sweep += 1           # full-N scratch
            else:
                carry += shape[0]    # carry rows
        counts = tuple(max(a, b)
                       for a, b in zip(counts, (blocks, lhs, carry, sweep)))
    return counts
