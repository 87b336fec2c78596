"""Shared helpers for the Pallas TPU kernels.

All kernels in this package follow the paper's interleaved layout adapted to
TPU (DESIGN.md §2): the batch/system index M rides the 128-wide lane axis,
the unknown index N is the sequential sweep axis, and the shared LHS lives in
a single VMEM-resident block whose index_map is constant across the grid —
the TPU analogue of every CUDA warp broadcast-hitting one global LHS copy.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Budgets of the fit checks, in bytes.  Pallas double-buffers every
# blocked operand (inputs AND outputs), so the checks count each pipelined
# block twice and scratch once (``vmem_working_set``).  The VMEM budget is
# the scoped limit Mosaic applies by default on a TPU v5e; the compiled
# kernels request VMEM_LIMIT_BYTES, which leaves the difference for the
# compiler's own internal scratch.  SMEM holds the shared factored LHS as
# a scalar table (1 MiB per core on v5e).
VMEM_BUDGET_BYTES = 16 * 1024 * 1024
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
SMEM_BUDGET_BYTES = 1024 * 1024
LANE = 128          # TPU lane width — one system per lane (paper: one per thread)
SUBLANE = 8         # VREG sublane depth — sweep unroll granularity


def default_interpret() -> bool:
    """Pallas kernels target TPU; on CPU containers validate via interpret."""
    return jax.default_backend() != "tpu"


def canonical_storage_dtype(storage_dtype):
    """Normalise a user-facing ``storage_dtype`` knob to a jnp dtype.

    ``None`` means "store at the operand dtype" (no mixed precision) and
    passes through.  The short alias ``"bf16"`` (and ``"bfloat16"``) maps
    to ``jnp.bfloat16`` — the storage precision of the mixed path: the
    (N, M) streams (per-lane diagonals, RHS) live at this dtype in HBM;
    the shared factor (32-bit SMEM scalars), all carries and accumulation
    stay at least fp32 in-kernel.  Non-floating dtypes are
    rejected (integer storage would silently quantise the factor)."""
    if storage_dtype is None:
        return None
    if storage_dtype in ("bf16", "bfloat16"):
        return jnp.dtype(jnp.bfloat16)
    dt = jnp.dtype(storage_dtype)
    if not jnp.issubdtype(dt, jnp.floating):
        raise ValueError(
            f"storage_dtype must be a floating dtype (or 'bf16'), "
            f"got {storage_dtype!r}")
    return dt


def row(ref, i):
    """Load row i (dynamic) of a 2-D VMEM ref as a (1, width) vector.

    Rows stay 2-D: a (1, BLOCK_M) value occupies one sublane of each lane
    tile, which is the layout Mosaic loads and stores at a dynamic sublane
    offset.  A 1-D (BLOCK_M,) value would need a relayout per step."""
    return ref[pl.ds(i, 1), :]


def store_row(ref, i, val):
    """Store a (1, width) row at row i (dynamic) of a 2-D VMEM ref."""
    ref[pl.ds(i, 1), :] = val


def widen_rows(src_ref, dst_ref, off=0) -> None:
    """Copy a packed storage-dtype block (bf16) into rows ``off:`` of a
    compute-dtype ref, converting on the way.

    A single bf16 row cannot be loaded at a dynamic sublane offset (bf16
    packs 16 rows per (8, 128) tile), so the sweeps never read the packed
    block row by row: the block is widened once, 16 rows per step, and
    the sweep then runs in place on the fp32 copy."""
    rows = src_ref.shape[0]
    step = 16 if rows % 16 == 0 else rows

    def body(t, carry):
        r = pl.multiple_of(t * step, step)
        dst_ref[pl.ds(off + r, step), :] = \
            src_ref[pl.ds(r, step), :].astype(dst_ref.dtype)
        return carry

    jax.lax.fori_loop(0, rows // step, body, 0)


def pad_to_multiple(x: jax.Array, multiple: int, axis: int, *,
                    value: float = 0.0) -> tuple[jax.Array, int]:
    """Pad ``axis`` of ``x`` up to a multiple of ``multiple`` with ``value``.
    Returns (padded, original size along axis)."""
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem:
        pads = [(0, 0)] * x.ndim
        pads[axis] = (0, rem)
        x = jnp.pad(x, pads, constant_values=value)
    return x, size


def pad_lanes(x: jax.Array, block_m: int, *,
              identity: bool = False) -> tuple[jax.Array, int]:
    """Pad the minor (system) axis of an interleaved (N, M) batch to a
    multiple of the lane tile. Returns (padded, original_M).

    ``identity=True`` pads with ones instead of zeros — required for the
    MAIN diagonal of per-system-LHS (batch mode) operands, so the dead
    padded lanes factor as identity rows (1/1) instead of dividing by the
    zero pad (1/0 -> inf/NaN poisoning every dead lane).  The ``sharded``
    backend's mesh padding shares this helper for the same reason.
    """
    return pad_to_multiple(x, block_m, -1, value=1.0 if identity else 0.0)


def shard_lanes(m: int, n_shards: int) -> int:
    """Per-device lane count after the mesh padding of the ``sharded``
    backend: M pads to a multiple of the shard count, then splits evenly.

    This is the lane count the per-device auto-tuner and the sharded
    traffic model reason about — each device's kernels additionally pad
    their local slice to the lane-tile multiple (``pad_lanes``)."""
    return -(-m // n_shards)


def pad_sweep(x: jax.Array, block_n: int, axis: int = 0, *,
              identity: bool = False) -> tuple[jax.Array, int]:
    """Zero-pad the sweep (N) axis to a multiple of the streamed N-chunk.

    Zero padding is exact for the *factored* constant-LHS kernels: a padded
    row computes ``(0 - 0*carry) * 0 = 0``, so padded rows contribute
    nothing to the forward carries and back-substitute to exactly 0 —
    finite under ``JAX_DEBUG_NANS`` (no division happens in the solve
    kernels; the inverses were taken at factor time).

    ``identity=True`` pads with ones instead — required for the MAIN
    diagonal of per-lane (batch-mode) operands, whose fused factorisation
    DOES divide in-kernel: an all-zero padded row would compute
    ``1/(0 - 0) = inf``, while an identity row factors as ``1/1`` and
    back-substitutes to exactly 0 (the sweep-axis analogue of
    ``pad_lanes(identity=True)``)."""
    return pad_to_multiple(x, block_n, axis, value=1.0 if identity else 0.0)


def vmem_working_set(n: int, block_m: int, n_blocks: int, *,
                     block_n: int | None = None, n_scratch: int = 0,
                     n_carry: int = 0, itemsize: int = 4) -> int:
    """Bytes of VMEM one grid step allocates.

    Each of the ``n_blocks`` lane-tiled operand blocks — ``(n, block_m)``
    resident, ``(block_n, block_m)`` streamed — is counted twice, because
    Pallas double-buffers every blocked input and output.  Scratch is
    allocated once: ``n_scratch`` full-N ``(n, block_m)`` arrays (the fused
    kernels' intermediate, the batch factor coefficients) and ``n_carry``
    carry rows.  Everything is priced at ``itemsize`` — callers pass the
    compute itemsize, which bounds the packed storage itemsize from above."""
    rows = n if block_n is None else block_n
    return (2 * n_blocks * rows * block_m + n_scratch * n * block_m
            + n_carry * block_m) * itemsize


def smem_working_set(lhs_rows: int, extent: int, itemsize: int = 4) -> int:
    """Bytes of SMEM the shared factored LHS block takes: ``lhs_rows`` rows
    of ``extent`` scalars (N resident, BLOCK_N streamed).  Counted as two
    buffers with the rows padded to the 8-row tile — an upper bound on
    what Mosaic allocates."""
    if not lhs_rows:
        return 0
    rows = -(-lhs_rows // SUBLANE) * SUBLANE
    return 2 * rows * extent * itemsize


def spec_working_set(spec, n: int, block_m: int, block_n: int | None = None,
                     itemsize: int = 4) -> tuple:
    """``(vmem_bytes, smem_bytes)`` one grid step of ``spec``'s kernels
    allocates, from the spec's own counts (``vmem_counts`` /
    ``sweep_scratch``) — the single fit model of the ops-layer checks and
    of every tuner."""
    n_blocks, n_lhs, n_carry = spec.vmem_counts()
    vmem = vmem_working_set(n, block_m, n_blocks, block_n=block_n,
                            n_scratch=spec.sweep_scratch(), n_carry=n_carry,
                            itemsize=itemsize)
    smem = smem_working_set(n_lhs, n if block_n is None else block_n,
                            itemsize=itemsize)
    return vmem, smem


def spec_fits(spec, n: int, block_m: int, block_n: int | None = None,
              itemsize: int = 4) -> bool:
    vmem, smem = spec_working_set(spec, n, block_m, block_n, itemsize)
    return vmem <= VMEM_BUDGET_BYTES and smem <= SMEM_BUDGET_BYTES


def check_fits(spec, n: int, block_m: int, block_n: int | None = None,
               itemsize: int = 4) -> None:
    """Raise ``ValueError`` when ``spec`` at these blocks busts the VMEM or
    SMEM budget (the message names the knob to turn)."""
    vmem, smem = spec_working_set(spec, n, block_m, block_n, itemsize)
    where = (f"N={n}, BLOCK_M={block_m}" if block_n is None
             else f"N={n}, BLOCK_N={block_n}, BLOCK_M={block_m}")
    if vmem > VMEM_BUDGET_BYTES:
        hint = ("reduce block_m or split N (HBM-streamed variant)"
                if block_n is None else
                "the full-N scratch does not fit — spill to the two-call "
                "streamed pair (fused=False) or reduce block_m"
                if spec.fused else "reduce block_n or block_m")
        raise ValueError(
            f"{spec.name} working set {vmem/2**20:.1f} MiB exceeds VMEM "
            f"budget ({VMEM_BUDGET_BYTES/2**20:.0f} MiB): {where}. {hint}.")
    if smem > SMEM_BUDGET_BYTES:
        raise ValueError(
            f"{spec.name} shared LHS block {smem/2**10:.0f} KiB exceeds SMEM "
            f"budget ({SMEM_BUDGET_BYTES/2**10:.0f} KiB): {where}. Split N "
            f"(HBM-streamed variant).")


# -- streamed (split-N) grid plumbing ---------------------------------------
#
# The streamed kernels run on a 2-D grid ``(M/block_m, N/block_n)``.  The
# LAST grid axis iterates fastest on TPU, so for a fixed lane tile j the
# N-chunks execute sequentially — the sweep state (``dh_prev`` / the penta
# second-order carries) lives in a small VMEM scratch that persists across
# those grid steps.  The forward-sweep kernel walks chunks ascending in N;
# the back-substitution kernel walks them descending (its index_map reverses
# the chunk axis), the TPU analogue of the paper's 2-kernel pipeline.

def _imin(a, b):
    """Branch-free min that works on Python ints AND traced grid indices
    (index maps trace; ``min``/``jnp.minimum`` would concretise or force a
    jnp dependency inside the map)."""
    return (a + b - abs(a - b)) // 2


def chunk_spec(block_n: int, block_m: int, num_n: int, *,
               reverse: bool = False):
    """BlockSpec for an (N, M) operand chunked to (block_n, block_m) on the
    streamed grid (j = lane tile, k = N-chunk; ``num_n`` chunks total)."""
    if reverse:
        return pl.BlockSpec((block_n, block_m),
                            lambda j, k: (num_n - 1 - k, j))
    return pl.BlockSpec((block_n, block_m), lambda j, k: (k, j))


def lhs_spec(rows: int, n: int):
    """BlockSpec for the whole stacked (rows, N) shared LHS, resident in
    SMEM across the lane-tile grid.  The sweep reads one coefficient per
    row and broadcasts it across the lane tile: a scalar read at a dynamic
    index is what SMEM serves (in VMEM it would be a width-1 slice at a
    dynamic lane offset, which Mosaic refuses)."""
    return pl.BlockSpec((rows, n), lambda j: (0, 0),
                        memory_space=pltpu.SMEM)


def param_spec():
    """BlockSpec for a (1, 1) scalar parameter (the uniform eps) in SMEM."""
    return pl.BlockSpec((1, 1), lambda *_: (0, 0), memory_space=pltpu.SMEM)


def chunk_lhs_spec(rows: int, block_n: int, num_n: int, *,
                   reverse: bool = False):
    """BlockSpec for a stacked (rows, N) shared LHS chunked along N, in
    SMEM.  Every lane tile re-walks the same chunks — the single stored
    LHS copy of the paper, streamed instead of resident."""
    if reverse:
        return pl.BlockSpec((rows, block_n),
                            lambda j, k: (0, num_n - 1 - k),
                            memory_space=pltpu.SMEM)
    return pl.BlockSpec((rows, block_n), lambda j, k: (0, k),
                        memory_space=pltpu.SMEM)


# -- fused single-call streamed grid ----------------------------------------
#
# The fused streamed kernels run BOTH sweep passes in one ``pallas_call`` on
# a grid ``(M/block_m, 2*N/block_n)`` whose N-chunk walk ASCENDS for the
# first num_n steps (forward pass) and DESCENDS for the last num_n steps
# (back substitution), with the intermediate (d_hat / g) held in a full-N
# VMEM scratch instead of round-tripping through HBM between two kernels.
# The index maps below clamp each operand to the phase that actually uses
# it, so every HBM block is fetched exactly once per phase that needs it
# (the clamped steps revisit the previous block, which Pallas keeps in VMEM
# — no refetch, and the recount in analysis/capture counts distinct blocks).

def fused_chunk_spec(block_n: int, block_m: int, num_n: int, *, phase: str):
    """BlockSpec for an (N, M) operand on the fused ascend/descend grid.

    ``phase="ascend"`` (forward-pass inputs): chunk ``min(k, num_n-1)`` —
    walks 0..num_n-1, then parks on the last chunk through the descend
    steps (already in VMEM; the descend phase never reads it).
    ``phase="descend"`` (back-substitution output): chunk
    ``min(2*num_n-1-k, num_n-1)`` — parks on chunk num_n-1 through the
    ascend steps (those writes are dead: the first descend step rewrites
    the same block), then walks num_n-1..0."""
    if phase == "ascend":
        return pl.BlockSpec((block_n, block_m),
                            lambda j, k: (_imin(k, num_n - 1), j))
    if phase != "descend":
        raise ValueError(f"phase must be 'ascend' or 'descend', got {phase!r}")
    return pl.BlockSpec((block_n, block_m),
                        lambda j, k: (_imin(2 * num_n - 1 - k, num_n - 1), j))


def fused_lhs_spec(rows: int, block_n: int, num_n: int):
    """BlockSpec for the stacked (rows, N) shared LHS on the fused grid:
    the descend phase MIRRORS the ascend walk (``min(k, 2*num_n-1-k)``
    is 0..num_n-1 then num_n-1..0), so the single stored LHS copy streams
    through SMEM exactly once per phase with no refetch at the turn."""
    return pl.BlockSpec((rows, block_n),
                        lambda j, k: (0, _imin(k, 2 * num_n - 1 - k)),
                        memory_space=pltpu.SMEM)


def block_shape_of(block_spec) -> tuple:
    """The (static) block shape of a ``pl.BlockSpec`` — a version-stable
    accessor for the static-analysis layer (``repro.analysis``), which
    enumerates kernel grids without running them."""
    return tuple(block_spec.block_shape)


def in_smem(block_spec) -> bool:
    """Whether a ``pl.BlockSpec`` places its block in SMEM (the shared LHS
    and scalar parameters) rather than VMEM."""
    return block_spec.memory_space == pltpu.SMEM


def index_map_of(block_spec):
    """The index-map callable of a ``pl.BlockSpec`` (grid indices ->
    block indices).  ``repro.analysis.gridcheck`` enumerates this map over
    the whole grid to prove write coverage and chunk-walk mirroring."""
    return block_spec.index_map


def reset_carry(carry_ref, k) -> None:
    """Zero the carry scratch on the first N-chunk of each lane tile.

    Zero-init makes the boundary rows fall out of the *general* recurrence
    (e.g. ``dh_0 = (d_0 - a_0·0)·inv_0``), so the streamed kernels need no
    first/last-row special cases and no cross-chunk peeking."""
    @pl.when(k == 0)
    def _():
        carry_ref[...] = jnp.zeros_like(carry_ref)
