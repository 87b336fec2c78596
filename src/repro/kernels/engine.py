"""The declarative sweep-kernel engine behind every banded Pallas solver.

The paper's whole kernel family — cuThomasConstantBatch, cuThomasBatch,
cuPentConstantBatch, cuPentUniformBatch, cuPentBatch, plus their transposed
(adjoint) and HBM-streamed (split-N) relatives — is ONE algorithm: a
two-pass sweep where each pass is a short linear recurrence

    out_i = (in_i - sum_j coeff_j(i) * carry_j) * scale(i)

with a carry of order 1 (tridiagonal) or 2 (pentadiagonal), walked either
ascending (forward substitution) or descending (back substitution).  Only
*which coefficient rows feed which carry lag* and *where the stored
inverse-diagonal scale sits* differ between variants — the forward solve
scales the forward pass, the transposed solve scales the backward pass,
the uniform variant reads one coefficient from a (1, 1) parameter block.

This module makes that observation executable (DESIGN.md §2.2):

  * ``SweepSpec`` — the declarative description of one solver variant:
    bandwidth (3|5), layout (``shared`` factored LHS vs ``batch`` per-lane
    fused factorisation), ``transposed``, ``streamed`` (VMEM-resident vs
    HBM-streamed split-N), ``uniform`` (penta shared only), ``packed``
    (resident shared only: 8 lane tiles on the sublanes of each vreg).
  * ``PassSpec`` — one pass of the sweep: the ``(coefficient row,
    carry lag)`` terms in subtraction order plus an optional scale row.
    ``SweepSpec.passes()`` looks both passes up in ``_PASS_TABLE`` — the
    spec tables that replaced four hand-written kernel modules.
  * ``shared_solver(spec)`` / ``batch_solver(spec)`` — the generic kernel
    builders.  They own the grid layout, the ``chunk_spec`` index maps,
    the VMEM carry scratch, the ``reset_carry`` zero-init (which makes the
    boundary rows fall out of the general recurrence — no first/last-row
    special cases anywhere), and emit the ``pl.pallas_call`` pair, each
    call named by its spec (``name=spec.name``; the two calls of a
    streamed pair add ``_fwd`` / ``_bwd``), so traces and Mosaic dumps
    name the kernel that ran.
  * ``REGISTRY`` — every variant the engine generates, by name.  Traffic
    (``SweepSpec.traffic_bytes``) and VMEM accounting
    (``SweepSpec.vmem_counts``) are derived from the spec, so a new
    variant can never silently miss the roofline model or the budget
    check.

Generated bodies are arithmetic-identical (bit-exact) to the hand-written
kernels they replaced: the subtraction order inside each pass and the
zero-carry boundary handling reproduce the old instruction sequences
exactly (``x - 0*c == x`` bitwise for finite ``c``).

Transposed-shared variants run the adjoint sweeps of DESIGN.md §5.1 from
the SAME stored factor: A = L·U means A^T = U^T·L^T, so the transposed
kernels read *shifted* coefficient rows (``c_hat_{i-1}``, ``a_{i+1}``, …)
that the dispatcher pre-shifts on the host (``repro.kernels.ops``).
Transposed-``batch`` needs no kernel of its own — rolling the per-lane
diagonals turns A^T into another batch system, so ``ops``/the solver
backend reuse the forward batch kernels (there is deliberately no
``transposed=True`` batch spec).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import LANE, SUBLANE, VMEM_LIMIT_BYTES, chunk_lhs_spec, \
    chunk_spec, default_interpret, fused_chunk_spec, fused_lhs_spec, \
    lhs_spec, param_spec, _imin, reset_carry, row, store_row, vmem_limit, \
    widen_rows

# Sentinel coefficient source: the uniform-mode eps value, which rides in a
# (1, 1) ARRAY operand (never a Python float baked into the kernel closure,
# so traced Factorization leaves stay jittable — see penta docstrings).
EPS_PARAM = "eps"


@dataclasses.dataclass(frozen=True)
class PassSpec:
    """One pass of a two-pass sweep.

    ``terms`` is a tuple of ``(coeff_src, carry_lag)`` pairs applied in
    SUBTRACTION ORDER (float subtraction is not associative; the order is
    part of the bit-exactness contract with the pre-engine kernels).
    ``coeff_src`` is a row index into the stacked LHS (shared layout) or
    into the per-lane coefficient refs (batch back-substitution), or
    ``EPS_PARAM``.  ``scale`` multiplies the bracketed result (the stored
    inverse diagonal) — ``None`` means the pass is unscaled.
    """

    terms: tuple
    scale: object = None


# (bandwidth, uniform, transposed) -> (forward pass, backward pass).
#
# Shared-layout LHS row conventions (stacked by repro.kernels.ops):
#   tridiag          [a, inv_denom, c_hat]
#   tridiag^T        [c_hat_{i-1}, inv_denom, a_{i+1}]
#   penta            [eps, beta, inv_alpha, gamma, delta]
#   penta uniform    [beta, inv_alpha, gamma, delta]      (+ eps param)
#   penta^T          [delta_{i-2}, gamma_{i-1}, inv_alpha, beta_{i+1},
#                     eps_{i+2}]
#   penta^T uniform  [delta_{i-2}, gamma_{i-1}, inv_alpha, beta_{i+1}]
#                                                         (+ eps param)
# The transposed rows are the SAME stored factor vectors, shifted on the
# host — A^T = U^T L^T from the forward's O(k N) storage, nothing new.
_PASS_TABLE = {
    (3, False, False): (PassSpec(((0, 1),), 1), PassSpec(((2, 1),), None)),
    (3, False, True): (PassSpec(((0, 1),), None), PassSpec(((2, 1),), 1)),
    (5, False, False): (PassSpec(((0, 2), (1, 1)), 2),
                        PassSpec(((3, 1), (4, 2)), None)),
    (5, False, True): (PassSpec(((0, 2), (1, 1)), None),
                       PassSpec(((3, 1), (4, 2)), 2)),
    (5, True, False): (PassSpec(((EPS_PARAM, 2), (0, 1)), 1),
                       PassSpec(((2, 1), (3, 2)), None)),
    (5, True, True): (PassSpec(((0, 2), (1, 1)), None),
                      PassSpec(((3, 1), (EPS_PARAM, 2)), 2)),
}

# Batch-layout back substitution reads the coefficients the fused
# factorisation just produced (c_hat, or gamma/delta), one (N, BLOCK_M)
# per-lane array each.
_BATCH_BWD = {
    1: PassSpec(((0, 1),), None),
    2: PassSpec(((0, 1), (1, 2)), None),
}

# Gated-recurrence passes, by carry order.  A gated linear recurrence
#   h_i = p_i h_{i-1} + q_i          (order 1)
#   h_i = s_i h_{i-1} + t_i h_{i-2} + u_i   (order 2)
# is a banded sweep pass whose multiplicative coefficients are per-token
# GATE OPERANDS — full (N, M) arrays riding the lane axis like the batch
# layout's fused coefficients — instead of rows of a shared stacked LHS.
# Term convention: gate operand index == carry lag - 1 (the lag-1 gate is
# operand 0, the lag-2 gate operand 1), lags ascending, no scale (gated
# recurrences have no stored inverse diagonal).  The sign flip between
# the sweep's ``acc - coeff*carry`` and the recurrence's ``+`` lives in
# the gate accessor (``_gate_coeff``), which negates on read — IEEE
# negation is exact, so ``q - (-p)*h`` is bitwise ``q + p*h``.
_RECUR_TABLE = {
    1: PassSpec(((0, 1),), None),
    2: PassSpec(((0, 1), (1, 2)), None),
}


@dataclasses.dataclass(frozen=True)
class SweepSpec:
    """Declarative description of one banded-solver variant."""

    bandwidth: int            # 3 | 5
    layout: str               # "shared" (one factored LHS) | "batch"
    transposed: bool = False  # solve A^T x = rhs from the same factor
    streamed: bool = False    # HBM-streamed split-N vs VMEM-resident
    uniform: bool = False     # penta shared only: eps as a (1, 1) operand
    fused: bool = False       # streamed only: both passes in ONE kernel
    packed: bool = False      # resident shared only: 8 lane tiles per vreg

    def __post_init__(self):
        if self.bandwidth not in (3, 5):
            raise ValueError(f"bandwidth must be 3 or 5, got {self.bandwidth}")
        if self.layout not in ("shared", "batch"):
            raise ValueError(f"unknown layout {self.layout!r}")
        if self.uniform and (self.bandwidth != 5 or self.layout != "shared"):
            raise ValueError("uniform is a shared-penta concept "
                             "(cuPentUniformBatch)")
        if self.transposed and self.layout == "batch":
            raise ValueError(
                "no transposed batch kernels: rolling the per-lane diagonals "
                "turns A^T into another batch system, so the forward batch "
                "kernels serve the adjoint (repro.solver.pallas)")
        if self.fused and not self.streamed:
            raise ValueError(
                "fused is a streamed concept: the resident kernels already "
                "run both passes in one pallas_call; fused=True fuses the "
                "STREAMED forward/backward pair onto one ascend/descend grid")
        if self.packed and (self.layout != "shared" or self.streamed):
            raise ValueError(
                "packed is a resident shared-layout concept: it carries 8 "
                "lane tiles on the sublanes of a VMEM-resident block swept "
                "against one shared LHS")

    # -- derived structure --------------------------------------------------

    @property
    def order(self) -> int:
        """Carry order of each sweep pass (1st/2nd-order recurrence)."""
        return 1 if self.bandwidth == 3 else 2

    @property
    def lhs_rows(self) -> int:
        """Rows of the stacked shared LHS block (0 for batch layout)."""
        if self.layout != "shared":
            return 0
        if self.bandwidth == 3:
            return 3
        return 4 if self.uniform else 5

    @property
    def n_coefs(self) -> int:
        """Per-lane coefficient arrays the fused factorisation produces
        (c_hat, or gamma+delta) — the batch kernels' scratch/spill."""
        return self.order if self.layout == "batch" else 0

    @property
    def carry_rows(self) -> int:
        """Rows of the (forward) kernel's VMEM carry scratch (0 for the
        packed sweep, whose carries are whole vregs seeded as zeros)."""
        if self.layout == "batch":
            # factorisation carries: (c_hat, d_hat) or
            # (gamma x2, delta x2, g x2) lags
            return 2 if self.order == 1 else 6
        return 0 if self.packed else self.order

    @property
    def mode(self) -> str:
        if self.layout == "batch":
            return "batch"
        return "uniform" if self.uniform else "constant"

    @property
    def name(self) -> str:
        base = "thomas" if self.bandwidth == 3 else "penta"
        name = f"{base}_{self.mode}"
        if self.streamed:
            name += "_streamed"
        if self.fused:
            name += "_fused"
        if self.packed:
            name += "_packed"
        if self.transposed:
            name += "_t"
        return name

    def passes(self) -> tuple:
        """(forward PassSpec, backward PassSpec) for this variant."""
        if self.layout == "batch":
            return None, _BATCH_BWD[self.order]
        return _PASS_TABLE[(self.bandwidth, self.uniform, self.transposed)]

    @property
    def scale_row(self) -> int:
        """Row index of the stored inverse diagonal in the stacked LHS —
        the ONLY row a pass's ``scale`` may legally point at (0 for batch
        layout, where the fused factorisation holds the inverse).

        Uniform stacks drop the eps row, so the inverse sits one row
        lower on the forward side ([beta, inv_alpha, gamma, delta]) but
        keeps row 2 on the transposed side ([delta, gamma, inv_alpha,
        beta] — the dropped row is at the other end of the band)."""
        if self.layout == "batch":
            return 0
        if self.bandwidth == 3:
            return 1
        if self.uniform and not self.transposed:
            return 1
        return 2

    @property
    def resident_name(self) -> str:
        """Name of the VMEM-resident sibling (self when not streamed)."""
        return dataclasses.replace(self, streamed=False, fused=False).name

    @property
    def row_name(self) -> str:
        """Name of the row-sweep sibling (self when not packed)."""
        return dataclasses.replace(self, packed=False).name

    @property
    def packed_name(self) -> str:
        """Name of the packed sibling of a resident shared spec."""
        return dataclasses.replace(self, packed=True).name

    @property
    def unfused_name(self) -> str:
        """Name of the two-call streamed sibling (self when not fused) —
        the spill target when the fused working set exceeds the budget."""
        return dataclasses.replace(self, fused=False).name

    def twin_name(self) -> str | None:
        """Name of the transposed twin spec (None for batch layout, whose
        adjoint reuses the forward kernels on rolled diagonals)."""
        if self.layout == "batch":
            return None
        return dataclasses.replace(self, transposed=not self.transposed).name

    def dummy_args(self, n: int, m: int, dtype=jnp.float32) -> tuple:
        """``(args, eps)`` zero-filled operands shaped for this spec's
        solver entry point — the introspection hook ``repro.analysis``
        uses to drive the kernel builders under abstract interpretation
        (no solve ever runs on them)."""
        if self.layout == "shared":
            args = (jnp.zeros((self.lhs_rows, n), dtype),
                    jnp.zeros((n, m), dtype))
            eps = jnp.zeros((1, 1), dtype) if self.uniform else None
            return args, eps
        return tuple(jnp.zeros((n, m), dtype)
                     for _ in range(self.bandwidth + 1)), None

    # -- derived accounting (no hand-kept tables) ---------------------------

    def storage_words(self, n: int, m: int) -> int:
        """HBM<->VMEM words one solve READS from the stored (N, M)
        streams — the per-lane diagonals and the RHS.  These are the words
        a ``storage_dtype`` override (bf16 in HBM, fp32 in-kernel)
        shrinks; everything else moves at the compute dtype and is
        counted by ``compute_words``."""
        if self.layout == "batch":
            # diagonals + rhs in.
            return (self.bandwidth + 1) * n * m
        return n * m

    def compute_words(self, n: int, m: int) -> int:
        """HBM<->VMEM words moved at the COMPUTE dtype (fp32-accumulated,
        regardless of ``storage_dtype``): the final x; the shared factored
        LHS and the eps parameter, which SMEM holds as 32-bit scalars; and
        — for the two-call streamed pair only — the intermediate (and, for
        batch, the spilled factor coefficients) round-tripped through HBM
        between the forward and backward kernels.  Resident and fused
        variants keep d_hat/g in VMEM, so their only (N, M) compute-dtype
        stream is x."""
        # the two-call streamed pair re-reads the LHS for its backward
        # kernel, the fused/resident variants read it once.
        lhs_passes = 1 if (self.fused or not self.streamed) else 2
        lhs = lhs_passes * self.lhs_rows * n + (1 if self.uniform else 0)
        if not self.streamed or self.fused:
            return n * m + lhs
        if self.layout == "batch":
            # x out + fwd writes intermediate + n_coefs spills which the
            # bwd kernel reads back.
            return (1 + 2 * (1 + self.n_coefs)) * n * m
        # x out + the d_hat/g round trip.
        return 3 * n * m + lhs

    def traffic_words(self, n: int, m: int) -> int:
        """HBM<->VMEM words one solve of an (n, m) RHS moves — the roofline
        memory term the paper's speed-up rests on, derived from the spec's
        stream structure (passes x {operands in, results out, LHS rows})."""
        return self.storage_words(n, m) + self.compute_words(n, m)

    def traffic_bytes(self, n: int, m: int, dtype=jnp.float32,
                      storage_dtype=None) -> int:
        """Bytes moved, itemized PER OPERAND CLASS: stored operands move at
        ``storage_dtype`` (defaults to ``dtype``), intermediates at the
        compute dtype — so the bf16-storage path halves the storage term
        while the spilled intermediates (if any) stay full width."""
        s_item = jnp.dtype(storage_dtype or dtype).itemsize
        c_item = jnp.dtype(dtype).itemsize
        return (self.storage_words(n, m) * s_item
                + self.compute_words(n, m) * c_item)

    def sharded_traffic_words(self, n: int, m: int, n_shards: int) -> int:
        """PER-DEVICE HBM<->VMEM words when the M axis is sharded over
        ``n_shards`` devices and each device runs this spec's kernels on
        its local slice (the sharded x streamed composition).

        The solve needs no collectives, so the per-device traffic is just
        ``traffic_words`` of the local lane count — for the shared layout
        the ``lhs_rows * n`` LHS stream does NOT shrink with the mesh
        (one replicated factor copy per device, the paper's storage idea
        applied per device), while the RHS terms divide by the shard
        count (up to mesh padding)."""
        from .common import shard_lanes
        return self.traffic_words(n, shard_lanes(m, n_shards))

    def vmem_counts(self) -> tuple:
        """(n_blocks, n_lhs_rows, n_carry_rows) for the fit checks
        (``common.spec_working_set``): the lane-tiled blocks Pallas
        pipelines (each double-buffered in VMEM), the shared-LHS rows held
        in SMEM, and the VMEM carry rows that seed (resident) or thread
        (streamed) the sweep carries.  For the streamed batch pair this
        is the FORWARD kernel's (larger) set: diagonals + rhs in,
        intermediate + spilled coefs out.  Full-N scratch is counted
        separately by ``sweep_scratch``."""
        if self.layout == "shared":
            return 2, self.lhs_rows, self.carry_rows
        if self.streamed and not self.fused:
            return self.bandwidth + 1 + 1 + self.n_coefs, 0, self.carry_rows
        return self.bandwidth + 2, 0, self.carry_rows

    def sweep_scratch(self) -> int:
        """Full-length (N, BLOCK_M) VMEM scratch arrays a kernel keeps
        across its whole sweep: the fused kernels' intermediate d_hat/g
        (plus, for batch layout, the factor coefficients) that the two-call
        pair would spill to HBM, the resident batch kernel's factor
        coefficients, and the packed sweep's lane-tile-major copy of its
        block.  0 for every other spec."""
        if self.fused:
            return 1 + self.n_coefs
        if self.layout == "batch" and not self.streamed:
            return self.n_coefs
        return 1 if self.packed else 0

    @property
    def num_pallas_calls(self) -> int:
        """``pl.pallas_call`` count one solve of this spec emits — the
        accounting invariant the capture layer cross-checks.  Streamed
        sweeps are a forward/backward kernel PAIR unless fused; resident
        and fused sweeps run both passes in one kernel."""
        return 2 if (self.streamed and not self.fused) else 1


@dataclasses.dataclass(frozen=True)
class RecurrenceSpec:
    """Declarative description of one gated-linear-recurrence variant.

    The sweep machine's second spec family (DESIGN.md §4): same generic
    pass body (``_solve_pass``), same streamed split-N grid plumbing, same
    registry/accounting/speclint contracts as ``SweepSpec`` — but the
    multiplicative coefficients arrive as per-token (N, M) gate operands
    (one per carry lag) instead of a shared stacked LHS, and a solve is a
    SINGLE pass (a recurrence has no back-substitution partner).

    ``reverse`` runs the recurrence from i = N-1 down to 0
    (h_i = p_i h_{i+1} + q_i) — the suffix-scan shape, NOT an adjoint of
    the forward variant (the adjoint additionally shifts the gates, which
    the dispatcher ``core.recurrence`` does on the host).
    """

    order: int                # 1 | 2 carry lags
    reverse: bool = False     # walk the sweep axis descending
    streamed: bool = False    # HBM-streamed split-N vs VMEM-resident

    #: a single-pass recurrence has nothing to fuse — class attribute so
    #: the analysis layers can branch on ``spec.fused`` uniformly.
    fused = False

    def __post_init__(self):
        if self.order not in (1, 2):
            raise ValueError(f"recurrence order must be 1 or 2, "
                             f"got {self.order}")

    # -- derived structure --------------------------------------------------

    @property
    def layout(self) -> str:
        return "recurrence"

    @property
    def lhs_rows(self) -> int:
        return 0              # no shared stacked LHS — gates are operands

    @property
    def carry_rows(self) -> int:
        return self.order

    @property
    def mode(self) -> str:
        return "recurrence"

    @property
    def name(self) -> str:
        name = f"recur{self.order}"
        if self.streamed:
            name += "_streamed"
        if self.reverse:
            name += "_rev"
        return name

    def passes(self) -> tuple:
        """``(pass,)`` — a recurrence is ONE sweep pass (no partner)."""
        return (_RECUR_TABLE[self.order],)

    @property
    def resident_name(self) -> str:
        return dataclasses.replace(self, streamed=False).name

    def twin_name(self) -> str:
        """Name of the reversed twin (same pass table, mirrored walk)."""
        return dataclasses.replace(self, reverse=not self.reverse).name

    def dummy_args(self, n: int, m: int, dtype=jnp.float32) -> tuple:
        """``(args, eps)`` zero-filled operands shaped for
        ``recurrence_solver``: ``order`` gate arrays then the additive
        operand, all (n, m).  ``eps`` is always None (no uniform mode)."""
        return tuple(jnp.zeros((n, m), dtype)
                     for _ in range(self.order + 1)), None

    # -- derived accounting (no hand-kept tables) ---------------------------

    def storage_words(self, n: int, m: int) -> int:
        """Words read from stored operands: ``order`` gate arrays + the
        additive operand (no shared LHS, no eps)."""
        return (self.order + 1) * n * m

    def compute_words(self, n: int, m: int) -> int:
        """Words moved at the compute dtype: h out (a single pass has no
        inter-kernel intermediate to round-trip)."""
        return n * m

    def traffic_words(self, n: int, m: int) -> int:
        """HBM<->VMEM words one solve moves: ``order`` gate operands + the
        additive operand in, h out — identical for resident and streamed
        (a single pass streams every chunk exactly once; nothing is
        revisited, unlike the two-pass sweeps)."""
        return self.storage_words(n, m) + self.compute_words(n, m)

    def traffic_bytes(self, n: int, m: int, dtype=jnp.float32,
                      storage_dtype=None) -> int:
        s_item = jnp.dtype(storage_dtype or dtype).itemsize
        c_item = jnp.dtype(dtype).itemsize
        return (self.storage_words(n, m) * s_item
                + self.compute_words(n, m) * c_item)

    def sharded_traffic_words(self, n: int, m: int, n_shards: int) -> int:
        """PER-DEVICE words with M sharded: every stream is lane-tiled
        (no replicated shared-LHS term), so everything divides by the
        shard count (up to mesh padding)."""
        from .common import shard_lanes
        return self.traffic_words(n, shard_lanes(m, n_shards))

    def vmem_counts(self) -> tuple:
        """(n_rhs_blocks, n_lhs_vecs, n_carry_rows): gates + operand + h
        are all lane-tiled blocks; no shared LHS vectors; ``order`` carry
        rows seed the resident sweep / thread the streamed chunks."""
        return self.order + 2, 0, self.order

    def sweep_scratch(self) -> int:
        """No fused variant, so never any full-N VMEM sweep scratch."""
        return 0

    @property
    def num_pallas_calls(self) -> int:
        """Always 1: a recurrence solve is a single pass, so even the
        streamed variant is ONE kernel walking its chunks sequentially."""
        return 1


def _all_specs() -> tuple:
    specs = []
    for bw in (3, 5):
        for transposed in (False, True):
            for streamed, fused, packed in ((False, False, False),
                                            (False, False, True),
                                            (True, False, False),
                                            (True, True, False)):
                for uniform in ((False, True) if bw == 5 else (False,)):
                    specs.append(SweepSpec(bw, "shared",
                                           transposed=transposed,
                                           streamed=streamed, fused=fused,
                                           uniform=uniform, packed=packed))
        for streamed, fused in ((False, False), (True, False), (True, True)):
            specs.append(SweepSpec(bw, "batch", streamed=streamed,
                                   fused=fused))
    for order in (1, 2):
        for reverse in (False, True):
            for streamed in (False, True):
                specs.append(RecurrenceSpec(order, reverse=reverse,
                                            streamed=streamed))
    return tuple(specs)


#: Every variant the engine generates, by name — the single source the
#: dispatcher, the traffic model, and the CI parity matrix all enumerate.
REGISTRY: dict = {s.name: s for s in _all_specs()}


def find_spec(bandwidth: int, mode: str, *, streamed: bool = False,
              transposed: bool = False, fused: bool = False) -> SweepSpec:
    """Look up the spec serving (bandwidth, storage mode) — the tridiag
    ``uniform`` mode shares the constant kernel (no eps vector to drop).

    Unknown combinations raise ``ValueError`` naming the valid choices
    (never a bare ``KeyError`` leaking the internal registry key)."""
    if bandwidth not in (3, 5):
        raise ValueError(
            f"no sweep kernels for bandwidth={bandwidth!r}; the engine "
            f"serves bandwidth 3 (tridiagonal) and 5 (pentadiagonal)")
    if mode not in ("constant", "uniform", "batch"):
        raise ValueError(
            f"unknown storage mode {mode!r}; valid modes are 'constant' "
            f"(one shared LHS), 'uniform' (all-equal diagonals) and "
            f"'batch' (per-system LHS copies)")
    if mode == "batch" and transposed:
        raise ValueError(
            "no transposed batch kernels are registered: the adjoint of a "
            "batch solve rolls the per-lane diagonals into another batch "
            "system and reuses the FORWARD batch kernels "
            "(repro.solver.pallas.transpose_solve_stored) — call with "
            "transposed=False on the rolled diagonals")
    if fused and not streamed:
        raise ValueError(
            "fused=True is a streamed refinement (one ascend/descend "
            "pallas_call instead of the forward/backward pair); the "
            "resident kernels are already single-call — pass streamed=True "
            "or drop fused")
    if bandwidth == 3 and mode == "uniform":
        mode = "constant"
    base = "thomas" if bandwidth == 3 else "penta"
    name = f"{base}_{mode}"
    if streamed:
        name += "_streamed"
    if fused:
        name += "_fused"
    if transposed:
        name += "_t"
    try:
        return REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"no registered sweep kernel named {name!r} for "
            f"bandwidth={bandwidth}, mode={mode!r}, streamed={streamed}, "
            f"transposed={transposed}; registered variants: "
            f"{sorted(REGISTRY)}") from None


def find_recurrence_spec(order: int, *, reverse: bool = False,
                         streamed: bool = False) -> RecurrenceSpec:
    """Look up the registered gated-recurrence spec for ``order`` with
    the requested walk direction and residency.  Unknown orders raise
    ``ValueError`` naming the valid choices."""
    if order not in (1, 2):
        raise ValueError(
            f"no recurrence kernels for order={order!r}; the engine "
            f"serves order 1 (h = p*h' + q) and order 2 "
            f"(h = s*h' + t*h'' + u)")
    name = RecurrenceSpec(order, reverse=reverse, streamed=streamed).name
    return REGISTRY[name]


def pass_table() -> dict:
    """A copy of the shared-layout pass tables, keyed by
    ``(bandwidth, uniform, transposed)`` — the introspection hook
    ``repro.analysis.speccheck`` audits (a copy: mutating it cannot
    corrupt the engine)."""
    return dict(_PASS_TABLE)


def batch_backward_table() -> dict:
    """A copy of the batch-layout back-substitution table, keyed by carry
    order — the fused forward factorisation has no PassSpec (its
    coefficient algebra lives in ``_factor_pass``)."""
    return dict(_BATCH_BWD)


def recurrence_table() -> dict:
    """A copy of the gated-recurrence pass table, keyed by carry order —
    the introspection hook ``repro.analysis.speccheck`` audits."""
    return dict(_RECUR_TABLE)


def traffic_table(bandwidth: int, n: int, m: int, dtype=jnp.float32) -> dict:
    """{variant_key: bytes} for every registered sweep spec of
    ``bandwidth`` — keys are the spec names minus the thomas_/penta_
    prefix (``constant``, ``constant_streamed_t``, ``batch_streamed``, …).
    Recurrence specs key their own family; see ``recurrence_traffic_table``."""
    prefix = ("thomas_" if bandwidth == 3 else "penta_")
    return {s.name[len(prefix):]: s.traffic_bytes(n, m, dtype)
            for s in REGISTRY.values()
            if isinstance(s, SweepSpec) and s.bandwidth == bandwidth}


def recurrence_traffic_table(n: int, m: int, dtype=jnp.float32) -> dict:
    """{spec_name: bytes} for every registered recurrence spec."""
    return {s.name: s.traffic_bytes(n, m, dtype)
            for s in REGISTRY.values() if isinstance(s, RecurrenceSpec)}


# ---------------------------------------------------------------------------
# Generic pass bodies
# ---------------------------------------------------------------------------

def _shared_coeff(lhs_ref, eps_ref):
    """Coefficient accessor for the shared layout: scalar per sweep row,
    broadcast across the lane tile (the paper's broadcast-hit LHS copy)."""
    def at(src, i):
        if src == EPS_PARAM:
            return eps_ref[0, 0]
        return lhs_ref[src, i]
    return at


def _shift(off):
    """Index shifter: identity for the (static) zero offset so non-fused
    traces stay instruction-identical; otherwise adds the (possibly
    traced) base row of a fused kernel's full-N VMEM scratch."""
    if isinstance(off, int) and off == 0:
        return lambda i: i
    return lambda i: off + i


def _lane_coeff(refs, off=0):
    """Coefficient accessor for the batch layout: a (BLOCK_M,) vector per
    sweep row, read from per-lane (N, BLOCK_M) refs.  ``off`` rebases the
    row index when the refs are a fused kernel's full-N scratch but the
    pass walks one BLOCK_N chunk of it."""
    at_row = _shift(off)

    def at(src, i):
        return row(refs[src], at_row(i))
    return at


def _sweep_step(acc, carries, pspec: PassSpec, coeff):
    """One row of a pass: ``acc`` minus each term's coefficient times its
    carry lag, in table order, then times the scale.  ``coeff(src)`` gives
    the row's coefficient from source ``src``.  The one home of the sweep
    arithmetic, so the row and packed sweeps cannot drift apart."""
    for src, lag in pspec.terms:
        acc = acc - coeff(src) * carries[lag - 1]
    if pspec.scale is not None:
        acc = acc * coeff(pspec.scale)
    return acc


def _solve_pass(coeff_at, in_ref, out_ref, init, *, pspec: PassSpec,
                order: int, length: int, reverse: bool, unroll: int,
                in_off=0, out_off=0):
    """Run one sweep pass; returns the final carry tuple.

    ``init`` is the carry tuple entering the pass (zeros, or the VMEM
    scratch rows threading a streamed sweep across N-chunks).  ``in_ref``
    and ``out_ref`` may alias (the resident kernels back-substitute in
    place over the intermediate they just wrote).  ``in_off``/``out_off``
    rebase the row index into refs that are LONGER than the pass (a fused
    kernel's full-N intermediate scratch vs its BLOCK_N chunk walk);
    coefficient rows are always chunk-local (``coeff_at`` carries its own
    base when needed)."""
    in_at, out_at = _shift(in_off), _shift(out_off)

    def body(t, carries):
        i = length - 1 - t if reverse else t
        acc = _sweep_step(row(in_ref, in_at(i)), carries, pspec,
                          lambda src: coeff_at(src, i))
        store_row(out_ref, out_at(i), acc)
        return (acc,) + carries[:order - 1]

    return jax.lax.fori_loop(0, length, body, tuple(init), unroll=unroll)


def _packed_rows(n: int, g: int, i):
    """Row ``i`` of packed group ``g`` in a lane-tile-major scratch (lane
    tile j at rows ``j*n .. j*n + n - 1``): one sublane-strided (8, 128)
    window whose sublane k is row i of lane tile ``8*g + k``."""
    return pl.ds(g * SUBLANE * n + i, SUBLANE, stride=n)


def _packed_pass(coeff_at, tiles_ref, init, *, pspec: PassSpec, order: int,
                 n: int, groups: int, reverse: bool, unroll: int):
    """One sweep pass, in place, over a lane-tile-major scratch holding
    ``groups`` packed groups of 8 lane tiles.  Each trip reads row i's
    coefficients once and steps every group: a whole (8, 128) vreg of
    1024 systems per load, op and store, with ``_sweep_step``'s arithmetic.
    ``init`` holds one carry tuple per group."""
    sources = [src for src, _ in pspec.terms]
    if pspec.scale is not None:
        sources.append(pspec.scale)

    def body(t, carries):
        i = n - 1 - t if reverse else t
        coeffs = {src: coeff_at(src, i) for src in sources}
        out = []
        for g, carry in enumerate(carries):
            rows = _packed_rows(n, g, i)
            acc = _sweep_step(tiles_ref[rows, :], carry, pspec,
                              coeffs.__getitem__)
            tiles_ref[rows, :] = acc
            out.append((acc,) + carry[:order - 1])
        return tuple(out)

    return jax.lax.fori_loop(0, n, body, init, unroll=unroll)


def _factor_pass(diag_at, rhs_ref, coef_store, out_ref, init, *, order: int,
                 length: int, unroll: int, out_off=0):
    """Fused factorisation + forward sweep (batch layout: cuThomasBatch /
    cuPentBatch semantics — the per-lane LHS is re-factored every solve).

    Zero-initialised carries make row 0 (and row 1 for penta) fall out of
    the general step: ``a_0``/``b_0`` only ever multiply zero carries, so
    no boundary special-casing — which is also what makes the streamed
    chunking and the identity sweep-padding exact."""
    out_at = _shift(out_off)

    if order == 1:
        def body(i, carry):
            chat_p, dh_p = carry
            a_i = diag_at(0, i)
            inv = 1.0 / (diag_at(1, i) - a_i * chat_p)
            chat = diag_at(2, i) * inv
            coef_store(0, i, chat)
            dh = (row(rhs_ref, i) - a_i * dh_p) * inv
            store_row(out_ref, out_at(i), dh)
            return chat, dh
    else:
        def body(i, carry):
            g1, g2, dl1, dl2, gg1, gg2 = carry
            a_i = diag_at(0, i)
            beta_i = diag_at(1, i) - a_i * g2
            alpha_i = diag_at(2, i) - a_i * dl2 - beta_i * g1
            inv = 1.0 / alpha_i
            gamma_i = (diag_at(3, i) - beta_i * dl1) * inv
            delta_i = diag_at(4, i) * inv
            coef_store(0, i, gamma_i)
            coef_store(1, i, delta_i)
            g_i = (row(rhs_ref, i) - a_i * gg2 - beta_i * gg1) * inv
            store_row(out_ref, out_at(i), g_i)
            return gamma_i, g1, delta_i, dl1, g_i, gg1

    return jax.lax.fori_loop(0, length, body, tuple(init), unroll=unroll)


def _compute_dtype(dtype):
    """In-kernel accumulation dtype: carries, intermediates, and the final
    x stay at least fp32 even when the stored operands arrive bf16 (the
    mixed-precision storage path — cast up on load, never accumulate in
    bf16).  Identity for fp32/fp64 inputs, preserving bit-exactness."""
    return jnp.promote_types(dtype, jnp.float32)


def _compiler_params(interpret: bool, *, streamed: bool,
                     prefetch: bool = False,
                     limit: int = VMEM_LIMIT_BYTES) -> dict:
    """Mosaic knobs for a compiled kernel: the scoped-VMEM limit the fit
    checks budget against (``limit``: ``common.vmem_limit`` of the spec)
    and the grid's dimension semantics.  Resident grids walk independent
    lane tiles (``parallel``).  On the streamed/fused 2-D grids the
    N-chunk axis is ``arbitrary`` (its carry scratch is sequential);
    ``prefetch=True`` marks the lane axis ``parallel`` too.  Interpret
    mode (CPU CI) takes no compiler params at all."""
    if interpret:
        return {}
    if not streamed:
        sem = ("parallel",)
    else:
        sem = ("parallel" if prefetch else "arbitrary", "arbitrary")
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=sem, vmem_limit_bytes=limit)}


def _resolve_interpret(interpret):
    """``None`` resolves to the platform default: compiled on a TPU, the
    Pallas interpreter elsewhere (``common.default_interpret``)."""
    return default_interpret() if interpret is None else interpret


# ---------------------------------------------------------------------------
# Shared-layout kernels (one factored LHS, broadcast to every lane)
# ---------------------------------------------------------------------------
#
# The stacked LHS lives in SMEM (``common.lhs_spec``): each sweep step reads
# its coefficients as scalars and broadcasts them across the lane tile.
# When the RHS arrives at a packed storage dtype (bf16), the kernel first
# widens the block into its compute-dtype output (or the fused kernel's
# intermediate scratch) and sweeps that copy in place.

def _stored_input(in_ref, work_ref, off=0):
    """The ref the first pass reads: ``in_ref`` itself when it is already
    at the compute dtype, else ``work_ref`` after widening ``in_ref`` into
    rows ``off:`` of it."""
    if in_ref.dtype == work_ref.dtype:
        return in_ref, 0
    widen_rows(in_ref, work_ref, off)
    return work_ref, off


def _zero_carries(carry_ref, count: int) -> tuple:
    """Zero the carry scratch and load ``count`` rows of it: the zero seed
    of a resident sweep.  Loaded rather than built as a constant, so the
    seed has the row layout of the values the sweep loop carries (Mosaic
    cannot carry a broadcast constant and a loaded row in one slot)."""
    carry_ref[...] = jnp.zeros_like(carry_ref)
    return tuple(row(carry_ref, j) for j in range(count))


def _shared_resident_kernel(*refs, spec: SweepSpec, n: int, unroll: int):
    """Both passes in one kernel; the output block doubles as intermediate
    storage (forward writes d_hat/g, backward overwrites with x)."""
    if spec.uniform:
        eps_ref, lhs_ref, in_ref, x_ref, carry_ref = refs
    else:
        (lhs_ref, in_ref, x_ref, carry_ref), eps_ref = refs, None
    fwd, bwd = spec.passes()
    at = _shared_coeff(lhs_ref, eps_ref)
    src, _ = _stored_input(in_ref, x_ref)
    zeros = _zero_carries(carry_ref, spec.order)
    _solve_pass(at, src, x_ref, zeros, pspec=fwd, order=spec.order,
                length=n, reverse=False, unroll=unroll)
    _solve_pass(at, x_ref, x_ref, zeros, pspec=bwd, order=spec.order,
                length=n, reverse=True, unroll=unroll)


def _shared_packed_kernel(*refs, spec: SweepSpec, n: int, unroll: int):
    """``_shared_resident_kernel`` with 8 lane tiles on the sublanes.  The
    block is copied into a lane-tile-major scratch (lane tile j at rows
    ``j*n ..``), both passes sweep it in place a whole vreg at a time
    (``_packed_pass``), and it is copied back out.  Every element takes the
    row sweep's arithmetic in the same order, so the two are bit-equal."""
    if spec.uniform:
        eps_ref, lhs_ref, in_ref, x_ref, tiles_ref = refs
    else:
        (lhs_ref, in_ref, x_ref, tiles_ref), eps_ref = refs, None
    fwd, bwd = spec.passes()
    at = _shared_coeff(lhs_ref, eps_ref)
    n_tiles = in_ref.shape[1] // LANE
    # the zero seed is loaded, not a constant, for the reason
    # ``_zero_carries`` gives; the copy below overwrites its vreg
    tiles_ref[pl.ds(0, SUBLANE), :] = jnp.zeros((SUBLANE, LANE),
                                                tiles_ref.dtype)
    zero = tiles_ref[pl.ds(0, SUBLANE), :]
    zeros = ((zero,) * spec.order,) * (n_tiles // SUBLANE)
    for j in range(n_tiles):
        tiles_ref[pl.ds(j * n, n), :] = in_ref[:, pl.ds(j * LANE, LANE)]
    kw = dict(order=spec.order, n=n, groups=n_tiles // SUBLANE,
              unroll=unroll)
    _packed_pass(at, tiles_ref, zeros, pspec=fwd, reverse=False, **kw)
    _packed_pass(at, tiles_ref, zeros, pspec=bwd, reverse=True, **kw)
    for j in range(n_tiles):
        x_ref[:, pl.ds(j * LANE, LANE)] = tiles_ref[pl.ds(j * n, n), :]


def _shared_streamed_kernel(*refs, pspec: PassSpec, order: int, block_n: int,
                            reverse: bool, uniform: bool, unroll: int):
    """One pass over one (BLOCK_N, BLOCK_M) chunk; the carry scratch
    threads the sweep state across the sequential N-chunk grid steps."""
    if uniform:
        eps_ref, lhs_ref, in_ref, out_ref, carry_ref = refs
    else:
        (lhs_ref, in_ref, out_ref, carry_ref), eps_ref = refs, None
    reset_carry(carry_ref, pl.program_id(1))
    init = tuple(row(carry_ref, j) for j in range(order))
    src, _ = _stored_input(in_ref, out_ref)
    final = _solve_pass(_shared_coeff(lhs_ref, eps_ref), src, out_ref,
                        init, pspec=pspec, order=order, length=block_n,
                        reverse=reverse, unroll=unroll)
    for j in range(order):
        store_row(carry_ref, j, final[j])


def _shared_fused_kernel(*refs, spec: SweepSpec, block_n: int, num_n: int,
                         unroll: int):
    """Both streamed passes in ONE kernel on the ascend/descend grid:
    steps k < num_n run the forward pass over ascending chunks, writing
    the intermediate (d_hat / g) into the full-N VMEM scratch ``mid_ref``;
    steps k >= num_n run back substitution over descending chunks, reading
    ``mid_ref`` back — the HBM round trip of the two-call pair, eliminated.
    The carry scratch resets at k == 0 AND k == num_n (``k % num_n``): each
    phase starts from the zero-carry boundary protocol."""
    if spec.uniform:
        eps_ref, lhs_ref, in_ref, x_ref, mid_ref, carry_ref = refs
    else:
        (lhs_ref, in_ref, x_ref, mid_ref, carry_ref), eps_ref = refs, None
    fwd, bwd = spec.passes()
    at = _shared_coeff(lhs_ref, eps_ref)
    k = pl.program_id(1)
    reset_carry(carry_ref, k % num_n)
    init = tuple(row(carry_ref, j) for j in range(spec.order))
    # Base rows into the full-N scratch: the chunk this step ascends into /
    # descends from (clamped like the index maps, so the not-taken branch
    # never addresses out of range).
    off = _imin(k, num_n - 1) * block_n
    doff = _imin(2 * num_n - 1 - k, num_n - 1) * block_n

    @pl.when(k < num_n)
    def _ascend():
        src, in_off = _stored_input(in_ref, mid_ref, off)
        final = _solve_pass(at, src, mid_ref, init, pspec=fwd,
                            order=spec.order, length=block_n, reverse=False,
                            unroll=unroll, in_off=in_off, out_off=off)
        for j in range(spec.order):
            store_row(carry_ref, j, final[j])

    @pl.when(k >= num_n)
    def _descend():
        final = _solve_pass(at, mid_ref, x_ref, init, pspec=bwd,
                            order=spec.order, length=block_n, reverse=True,
                            unroll=unroll, in_off=doff)
        for j in range(spec.order):
            store_row(carry_ref, j, final[j])


@functools.lru_cache(maxsize=None)
def shared_solver(spec: SweepSpec):
    """Compile ``spec`` (shared layout) into its jitted pallas entry point:
    ``solver(lhs, rhs, *, block_m, [block_n,] unroll, interpret, eps)``.

    ``lhs`` is the (rows, N) stack of ``repro.kernels.ops.stack_*_lhs``
    (pre-shifted for transposed specs) at the compute dtype; ``eps`` is
    the (1, 1) uniform parameter operand.  ``rhs`` may arrive at a packed
    storage dtype (bf16); the result is at the fp32-promoted compute
    dtype.  Callers pad: M % block_m == 0, and for streamed specs
    N % block_n == 0.  Packed specs take the RHS at the compute dtype,
    N % 8 == 0 and block_m % 1024 == 0 (``ops`` engages them only then).
    ``interpret=None`` compiles on a TPU and interprets elsewhere."""
    assert spec.layout == "shared"

    if not spec.streamed:
        @functools.partial(jax.jit,
                           static_argnames=("block_m", "unroll", "interpret"))
        def solver(lhs, rhs, *, block_m=128, unroll=1, interpret=None,
                   eps=None):
            interpret = _resolve_interpret(interpret)
            n, m = rhs.shape
            cdt = _compute_dtype(rhs.dtype)
            in_specs = [lhs_spec(spec.lhs_rows, n), _col_spec(n, block_m)]
            args = [lhs, rhs]
            if spec.uniform:
                in_specs.insert(0, param_spec())
                args.insert(0, eps)
            if spec.packed:
                if (rhs.dtype != cdt or n % SUBLANE
                        or block_m % (SUBLANE * LANE)):
                    raise ValueError(
                        f"{spec.name} needs the RHS at the compute dtype, "
                        f"N % {SUBLANE} == 0 and block_m % "
                        f"{SUBLANE * LANE} == 0; got {rhs.dtype}, N={n}, "
                        f"block_m={block_m}")
                kernel = _shared_packed_kernel
                scratch = pltpu.VMEM((block_m // LANE * n, LANE), cdt)
            else:
                kernel = _shared_resident_kernel
                scratch = pltpu.VMEM((spec.order, block_m), cdt)
            return pl.pallas_call(
                functools.partial(kernel, spec=spec, n=n, unroll=unroll),
                grid=(m // block_m,),
                in_specs=in_specs,
                out_specs=_col_spec(n, block_m),
                out_shape=_out_struct((n, m), cdt, *args),
                scratch_shapes=[scratch],
                interpret=interpret,
                name=spec.name,
                **_compiler_params(interpret, streamed=False,
                                   limit=vmem_limit(spec)),
            )(*args)
        return solver

    if spec.fused:
        @functools.partial(jax.jit,
                           static_argnames=("block_m", "block_n", "unroll",
                                            "interpret", "prefetch"))
        def solver(lhs, rhs, *, block_m=128, block_n=512, unroll=1,
                   interpret=None, eps=None, prefetch=False):
            interpret = _resolve_interpret(interpret)
            n, m = rhs.shape
            cdt = _compute_dtype(rhs.dtype)
            num_n = n // block_n
            in_specs = [fused_lhs_spec(spec.lhs_rows, block_n, num_n),
                        fused_chunk_spec(block_n, block_m, num_n,
                                         phase="ascend")]
            args = [lhs, rhs]
            if spec.uniform:
                in_specs.insert(0, param_spec())
                args.insert(0, eps)
            return pl.pallas_call(
                functools.partial(_shared_fused_kernel, spec=spec,
                                  block_n=block_n, num_n=num_n,
                                  unroll=unroll),
                grid=(m // block_m, 2 * num_n),
                in_specs=in_specs,
                out_specs=fused_chunk_spec(block_n, block_m, num_n,
                                           phase="descend"),
                out_shape=_out_struct((n, m), cdt, *args),
                scratch_shapes=[pltpu.VMEM((n, block_m), cdt),
                                pltpu.VMEM((spec.order, block_m), cdt)],
                interpret=interpret,
                name=spec.name,
                **_compiler_params(interpret, streamed=True,
                                   prefetch=prefetch),
            )(*args)
        return solver

    @functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                                 "unroll", "interpret",
                                                 "prefetch"))
    def solver(lhs, rhs, *, block_m=128, block_n=512, unroll=1,
               interpret=None, eps=None, prefetch=False):
        interpret = _resolve_interpret(interpret)
        n, m = rhs.shape
        cdt = _compute_dtype(rhs.dtype)
        num_n = n // block_n
        grid = (m // block_m, num_n)
        carry = [pltpu.VMEM((spec.order, block_m), cdt)]
        fwd, bwd = spec.passes()

        def one_pass(pspec, reverse, operand, name):
            in_specs = [chunk_lhs_spec(spec.lhs_rows, block_n, num_n,
                                       reverse=reverse),
                        chunk_spec(block_n, block_m, num_n, reverse=reverse)]
            args = [lhs, operand]
            if spec.uniform:
                in_specs.insert(0, param_spec())
                args.insert(0, eps)
            return pl.pallas_call(
                functools.partial(_shared_streamed_kernel, pspec=pspec,
                                  order=spec.order, block_n=block_n,
                                  reverse=reverse, uniform=spec.uniform,
                                  unroll=unroll),
                grid=grid,
                in_specs=in_specs,
                out_specs=chunk_spec(block_n, block_m, num_n, reverse=reverse),
                out_shape=_out_struct((n, m), cdt, *args),
                scratch_shapes=carry,
                interpret=interpret,
                name=name,
                **_compiler_params(interpret, streamed=True,
                                   prefetch=prefetch),
            )(*args)

        mid = one_pass(fwd, False, rhs, f"{spec.name}_fwd")  # d_hat / g
        return one_pass(bwd, True, mid, f"{spec.name}_bwd")  # x
    return solver


# ---------------------------------------------------------------------------
# Batch-layout kernels (per-lane LHS, factorisation fused into the solve)
# ---------------------------------------------------------------------------

def _batch_resident_kernel(*refs, spec: SweepSpec, n: int, unroll: int):
    nd = spec.bandwidth
    diag_refs, rhs_ref, x_ref = refs[:nd], refs[nd], refs[nd + 1]
    coef_refs, carry_ref = refs[nd + 2:-1], refs[-1]   # VMEM scratch
    zeros = _zero_carries(carry_ref, spec.carry_rows)
    _factor_pass(_lane_coeff(diag_refs), rhs_ref,
                 lambda r, i, v: store_row(coef_refs[r], i, v),
                 x_ref, zeros, order=spec.order, length=n, unroll=unroll)
    _, bwd = spec.passes()
    _solve_pass(_lane_coeff(coef_refs), x_ref, x_ref, zeros[:spec.order],
                pspec=bwd, order=spec.order, length=n, reverse=True,
                unroll=unroll)


def _batch_streamed_fwd_kernel(*refs, spec: SweepSpec, block_n: int,
                               unroll: int):
    """Fused factorisation over ascending chunks; the intermediate AND the
    factor coefficients (c_hat / gamma+delta) spill to HBM for the
    backward kernel (DESIGN.md §2.2's scratch-spill layout)."""
    nd = spec.bandwidth
    diag_refs, rhs_ref = refs[:nd], refs[nd]
    out_ref = refs[nd + 1]
    coef_refs = refs[nd + 2:nd + 2 + spec.n_coefs]   # HBM-backed outputs
    carry_ref = refs[-1]
    reset_carry(carry_ref, pl.program_id(1))
    init = tuple(row(carry_ref, j) for j in range(spec.carry_rows))
    final = _factor_pass(_lane_coeff(diag_refs), rhs_ref,
                         lambda r, i, v: store_row(coef_refs[r], i, v),
                         out_ref, init, order=spec.order, length=block_n,
                         unroll=unroll)
    for j in range(spec.carry_rows):
        store_row(carry_ref, j, final[j])


def _batch_streamed_bwd_kernel(*refs, spec: SweepSpec, block_n: int,
                               unroll: int):
    """Back substitution over descending chunks, reading the spilled
    coefficients back from HBM."""
    coef_refs = refs[:spec.n_coefs]
    in_ref, x_ref, carry_ref = refs[spec.n_coefs], refs[spec.n_coefs + 1], \
        refs[-1]
    reset_carry(carry_ref, pl.program_id(1))
    _, bwd = spec.passes()
    init = tuple(row(carry_ref, j) for j in range(spec.order))
    final = _solve_pass(_lane_coeff(coef_refs), in_ref, x_ref, init,
                        pspec=bwd, order=spec.order, length=block_n,
                        reverse=True, unroll=unroll)
    for j in range(spec.order):
        store_row(carry_ref, j, final[j])


def _batch_fused_kernel(*refs, spec: SweepSpec, block_n: int, num_n: int,
                        unroll: int):
    """Fused factorisation + back substitution in ONE kernel on the
    ascend/descend grid: the intermediate AND the factor coefficients
    (c_hat / gamma+delta) live in full-N VMEM scratch instead of spilling
    to HBM between the two-call pair's kernels.  The carry scratch resets
    at k == 0 AND k == num_n (``k % num_n``) — the descend phase's
    (smaller) back-substitution carry reuses the leading rows."""
    nd = spec.bandwidth
    diag_refs, rhs_ref, x_ref = refs[:nd], refs[nd], refs[nd + 1]
    mid_ref = refs[nd + 2]
    coef_refs = refs[nd + 3:nd + 3 + spec.n_coefs]   # full-N VMEM scratch
    carry_ref = refs[-1]
    k = pl.program_id(1)
    reset_carry(carry_ref, k % num_n)
    off = _imin(k, num_n - 1) * block_n
    doff = _imin(2 * num_n - 1 - k, num_n - 1) * block_n

    @pl.when(k < num_n)
    def _ascend():
        init = tuple(row(carry_ref, j) for j in range(spec.carry_rows))
        final = _factor_pass(
            _lane_coeff(diag_refs), rhs_ref,
            lambda r, i, v: store_row(coef_refs[r], off + i, v),
            mid_ref, init, order=spec.order, length=block_n,
            unroll=unroll, out_off=off)
        for j in range(spec.carry_rows):
            store_row(carry_ref, j, final[j])

    @pl.when(k >= num_n)
    def _descend():
        _, bwd = spec.passes()
        init = tuple(row(carry_ref, j) for j in range(spec.order))
        final = _solve_pass(_lane_coeff(coef_refs, off=doff), mid_ref, x_ref,
                            init, pspec=bwd, order=spec.order,
                            length=block_n, reverse=True, unroll=unroll,
                            in_off=doff)
        for j in range(spec.order):
            store_row(carry_ref, j, final[j])


def _check_batch_storage(args, interpret: bool) -> None:
    """The batch kernels read every per-lane operand row by row, which a
    packed (bf16) block does not allow on the chip: refuse it there rather
    than widen the operands outside the kernel and run fp32 in silence."""
    cdt = _compute_dtype(args[-1].dtype)
    if not interpret and any(a.dtype != cdt for a in args):
        raise NotImplementedError(
            "storage_dtype for batch (per-system LHS) operands has no "
            "compiled kernel: the batch sweeps load single rows of every "
            "diagonal, which Mosaic cannot do on a packed bf16 block. Store "
            "the diagonals at the compute dtype (storage_dtype=None).")


@functools.lru_cache(maxsize=None)
def batch_solver(spec: SweepSpec):
    """Compile ``spec`` (batch layout) into its jitted pallas entry point:
    ``solver(*diagonals, rhs, *, block_m, [block_n,] unroll, interpret)``.

    Callers pad lanes (identity main diagonal) and, for streamed specs,
    the sweep axis (identity main diagonal there too — the fused
    factorisation divides in-kernel, see ``common.pad_sweep``).
    ``interpret=None`` compiles on a TPU and interprets elsewhere."""
    assert spec.layout == "batch"

    if not spec.streamed:
        @functools.partial(jax.jit,
                           static_argnames=("block_m", "unroll", "interpret"))
        def solver(*args, block_m=128, unroll=1, interpret=None):
            interpret = _resolve_interpret(interpret)
            _check_batch_storage(args, interpret)
            n, m = args[-1].shape
            cdt = _compute_dtype(args[-1].dtype)
            sp = _col_spec(n, block_m)
            return pl.pallas_call(
                functools.partial(_batch_resident_kernel, spec=spec, n=n,
                                  unroll=unroll),
                grid=(m // block_m,),
                in_specs=[sp] * (spec.bandwidth + 1),
                out_specs=sp,
                out_shape=_out_struct((n, m), cdt, *args),
                scratch_shapes=[pltpu.VMEM((n, block_m), cdt)
                                for _ in range(spec.n_coefs)]
                               + [pltpu.VMEM((spec.carry_rows, block_m),
                                             cdt)],
                interpret=interpret,
                name=spec.name,
                **_compiler_params(interpret, streamed=False),
            )(*args)
        return solver

    if spec.fused:
        @functools.partial(jax.jit,
                           static_argnames=("block_m", "block_n", "unroll",
                                            "interpret", "prefetch"))
        def solver(*args, block_m=128, block_n=512, unroll=1, interpret=None,
                   prefetch=False):
            interpret = _resolve_interpret(interpret)
            _check_batch_storage(args, interpret)
            n, m = args[-1].shape
            cdt = _compute_dtype(args[-1].dtype)
            num_n = n // block_n
            asc = fused_chunk_spec(block_n, block_m, num_n, phase="ascend")
            return pl.pallas_call(
                functools.partial(_batch_fused_kernel, spec=spec,
                                  block_n=block_n, num_n=num_n,
                                  unroll=unroll),
                grid=(m // block_m, 2 * num_n),
                in_specs=[asc] * (spec.bandwidth + 1),
                out_specs=fused_chunk_spec(block_n, block_m, num_n,
                                           phase="descend"),
                out_shape=_out_struct((n, m), cdt, *args),
                scratch_shapes=[pltpu.VMEM((n, block_m), cdt)
                                for _ in range(1 + spec.n_coefs)]
                               + [pltpu.VMEM((spec.carry_rows, block_m),
                                             cdt)],
                interpret=interpret,
                name=spec.name,
                **_compiler_params(interpret, streamed=True,
                                   prefetch=prefetch),
            )(*args)
        return solver

    @functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                                 "unroll", "interpret",
                                                 "prefetch"))
    def solver(*args, block_m=128, block_n=512, unroll=1, interpret=None,
               prefetch=False):
        interpret = _resolve_interpret(interpret)
        _check_batch_storage(args, interpret)
        n, m = args[-1].shape
        cdt = _compute_dtype(args[-1].dtype)
        num_n = n // block_n
        grid = (m // block_m, num_n)
        csp = chunk_spec(block_n, block_m, num_n)
        shape = _out_struct((n, m), cdt, *args)
        params = _compiler_params(interpret, streamed=True, prefetch=prefetch)

        outs = pl.pallas_call(
            functools.partial(_batch_streamed_fwd_kernel, spec=spec,
                              block_n=block_n, unroll=unroll),
            grid=grid,
            in_specs=[csp] * (spec.bandwidth + 1),
            out_specs=[csp] * (1 + spec.n_coefs),
            out_shape=[shape] * (1 + spec.n_coefs),
            scratch_shapes=[pltpu.VMEM((spec.carry_rows, block_m), cdt)],
            interpret=interpret,
            name=f"{spec.name}_fwd",
            **params,
        )(*args)
        mid, coefs = outs[0], outs[1:]

        rsp = chunk_spec(block_n, block_m, num_n, reverse=True)
        return pl.pallas_call(
            functools.partial(_batch_streamed_bwd_kernel, spec=spec,
                              block_n=block_n, unroll=unroll),
            grid=grid,
            in_specs=[rsp] * (spec.n_coefs + 1),
            out_specs=rsp,
            out_shape=shape,
            scratch_shapes=[pltpu.VMEM((spec.order, block_m), cdt)],
            interpret=interpret,
            name=f"{spec.name}_bwd",
            **params,
        )(*coefs, mid)
    return solver


# ---------------------------------------------------------------------------
# Recurrence-layout kernels (per-token gate operands, single pass)
# ---------------------------------------------------------------------------

def _gate_coeff(refs):
    """Coefficient accessor for the recurrence layout: a (1, BLOCK_M) gate
    row per sweep step, read NEGATED from per-token (N, BLOCK_M) refs —
    ``_solve_pass`` subtracts its terms, a recurrence adds, and IEEE
    negation is exact, so ``q - (-p)*h`` is bitwise ``q + p*h``."""
    def at(src, i):
        return -row(refs[src], i)
    return at


def _recurrence_resident_kernel(*refs, spec: RecurrenceSpec, n: int,
                                unroll: int):
    """The whole recurrence in one kernel: a single ``_solve_pass`` over
    the resident (N, BLOCK_M) tiles, walked forward or reverse."""
    gate_refs, in_ref, out_ref, carry_ref = (refs[:spec.order], refs[-3],
                                             refs[-2], refs[-1])
    (pspec,) = spec.passes()
    zeros = _zero_carries(carry_ref, spec.order)
    _solve_pass(_gate_coeff(gate_refs), in_ref, out_ref, zeros, pspec=pspec,
                order=spec.order, length=n, reverse=spec.reverse,
                unroll=unroll)


def _recurrence_streamed_kernel(*refs, spec: RecurrenceSpec, block_n: int,
                                unroll: int):
    """One (BLOCK_N, BLOCK_M) chunk of the recurrence; the carry scratch
    threads h across the sequential N-chunk grid steps.  Reverse variants
    get DESCENDING chunks from their index maps, so inside the kernel the
    walk is the same reverse loop the resident kernel runs."""
    gate_refs = refs[:spec.order]
    in_ref, out_ref, carry_ref = refs[spec.order], refs[spec.order + 1], \
        refs[-1]
    (pspec,) = spec.passes()
    reset_carry(carry_ref, pl.program_id(1))
    init = tuple(row(carry_ref, j) for j in range(spec.order))
    final = _solve_pass(_gate_coeff(gate_refs), in_ref, out_ref, init,
                        pspec=pspec, order=spec.order, length=block_n,
                        reverse=spec.reverse, unroll=unroll)
    for j in range(spec.order):
        store_row(carry_ref, j, final[j])


@functools.lru_cache(maxsize=None)
def recurrence_solver(spec: RecurrenceSpec):
    """Compile ``spec`` into its jitted pallas entry point:
    ``solver(*gates, q, *, block_m, [block_n,] unroll, interpret)``.

    ``gates`` are the ``order`` per-token (N, M) gate arrays (lag-1 first)
    and ``q`` the additive operand; all carries start at zero — nonzero
    h0 is folded into the boundary rows of ``q`` by the dispatcher
    (``repro.kernels.ops.recurrence``), which keeps the kernels on the
    same zero-carry protocol as every sweep kernel.  Callers pad:
    M % block_m == 0, and for streamed specs N % block_n == 0 (zero
    padding is exact: padded gate rows multiply a finite carry by 0).
    ``interpret=None`` compiles on a TPU and interprets elsewhere."""
    assert isinstance(spec, RecurrenceSpec)

    if not spec.streamed:
        @functools.partial(jax.jit,
                           static_argnames=("block_m", "unroll", "interpret"))
        def solver(*args, block_m=128, unroll=1, interpret=None):
            interpret = _resolve_interpret(interpret)
            n, m = args[-1].shape
            sp = _col_spec(n, block_m)
            return pl.pallas_call(
                functools.partial(_recurrence_resident_kernel, spec=spec,
                                  n=n, unroll=unroll),
                grid=(m // block_m,),
                in_specs=[sp] * (spec.order + 1),
                out_specs=sp,
                out_shape=_out_struct((n, m), args[-1].dtype, *args),
                scratch_shapes=[pltpu.VMEM((spec.order, block_m),
                                           args[-1].dtype)],
                interpret=interpret,
                name=spec.name,
                **_compiler_params(interpret, streamed=False),
            )(*args)
        return solver

    @functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                                 "unroll", "interpret"))
    def solver(*args, block_m=128, block_n=512, unroll=1, interpret=None):
        interpret = _resolve_interpret(interpret)
        n, m = args[-1].shape
        num_n = n // block_n
        csp = chunk_spec(block_n, block_m, num_n, reverse=spec.reverse)
        return pl.pallas_call(
            functools.partial(_recurrence_streamed_kernel, spec=spec,
                              block_n=block_n, unroll=unroll),
            grid=(m // block_m, num_n),
            in_specs=[csp] * (spec.order + 1),
            out_specs=csp,
            out_shape=_out_struct((n, m), args[-1].dtype, *args),
            scratch_shapes=[pltpu.VMEM((spec.order, block_m),
                                       args[-1].dtype)],
            interpret=interpret,
            name=spec.name,
            **_compiler_params(interpret, streamed=True),
        )(*args)
    return solver


def _out_struct(shape, dtype, *operands):
    """Output type of a kernel: varying over every mesh axis any operand
    varies over, so the kernels type-check inside ``jax.shard_map`` with
    ``check_vma=True`` (the ``sharded`` backend); outside it this is the
    empty set."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _col_spec(n: int, block_m: int):
    return pl.BlockSpec((n, block_m), lambda j: (0, j))
