"""speccheck — structural invariants of the pass tables + accounting audit.

Every check here is over the *declarative* layer: the ``SweepSpec`` /
``PassSpec`` tables that generate the kernels, and the accounting those
specs derive.  Nothing solves anything.

Structural invariants (the bit-exactness contract of DESIGN.md §2.2):

  * every carry lag lies in ``[1, order]`` and each pass touches the full
    lag range (an order-2 sweep that never reads lag 2 is a different —
    wrong — recurrence);
  * every integer coefficient row index addresses a real row of the
    stacked LHS (``< lhs_rows``; batch back-substitution rows ``<
    n_coefs``); the EPS sentinel appears exactly once, and only in
    uniform specs;
  * exactly ONE inverse-diagonal scale across each pass pair, on the
    stored-inverse row (``scale_row``) — forward variants scale the
    forward pass, transposed variants the backward pass (A = L·U vs
    A^T = U^T·L^T);
  * subtraction order is canonical: forward-pass lags strictly
    descending, backward-pass lags strictly ascending (float subtraction
    is not associative — this IS the instruction order of the
    pre-engine kernels the generated bodies are bit-exact against);
  * the transposed twin is the same machine with the scale moved: same
    term tables (same lag sequences for uniform, where eps migrates from
    the forward to the backward pass), scale on the other side;
  * streamed and resident siblings share one pass table (streaming moves
    carries to scratch, never the arithmetic).

Accounting audit: the HBM-traffic and VMEM numbers ``SweepSpec`` derives
are recounted INDEPENDENTLY from the captured kernel builders
(``repro.analysis.capture``) and must agree exactly — a stale constant in
``traffic_words`` / ``vmem_counts`` (or a builder change that silently
adds a stream) fails here, in isolation.
"""

from __future__ import annotations

from repro.kernels import engine
from repro.kernels.common import shard_lanes
from repro.kernels.engine import EPS_PARAM, RecurrenceSpec, SweepSpec

from . import Finding
from .capture import (TRACE_M, TRACE_N, recount_traffic_words,
                      recount_vmem_counts, trace_spec_calls)


def _lags(pspec) -> tuple:
    return tuple(lag for _src, lag in pspec.terms)


def _check_terms(spec: SweepSpec, pspec, which: str, out: list) -> None:
    """Lag bounds, row bounds, EPS placement, subtraction order."""
    sub = f"{spec.name}.{which}"
    max_row = spec.lhs_rows if spec.layout == "shared" else spec.n_coefs
    for src, lag in pspec.terms:
        if not (1 <= lag <= spec.order):
            out.append(Finding("speccheck", sub,
                               f"carry lag {lag} outside [1, {spec.order}] "
                               f"(order-{spec.order} recurrence)"))
        if src == EPS_PARAM:
            if not spec.uniform:
                out.append(Finding("speccheck", sub,
                                   "EPS parameter term in a non-uniform "
                                   "spec (eps rides a (1, 1) operand only "
                                   "for cuPentUniformBatch variants)"))
        elif not (isinstance(src, int) and 0 <= src < max_row):
            out.append(Finding("speccheck", sub,
                               f"coefficient row {src!r} outside the "
                               f"stacked LHS (valid rows: 0..{max_row - 1})"))
    lags = _lags(pspec)
    if sorted(lags) != list(range(1, spec.order + 1)):
        out.append(Finding("speccheck", sub,
                           f"pass lags {lags} do not cover the carry range "
                           f"1..{spec.order} exactly once"))
    want = tuple(sorted(lags, reverse=(which == "fwd")))
    if lags != want:
        out.append(Finding("speccheck", sub,
                           f"subtraction order {lags} violates the "
                           f"canonical order {want} (fwd descending / bwd "
                           f"ascending — the bit-exactness contract)"))
    if pspec.scale is not None and pspec.scale != spec.scale_row:
        out.append(Finding("speccheck", sub,
                           f"scale row {pspec.scale!r} is not the stored "
                           f"inverse-diagonal row {spec.scale_row}"))


def _check_structure(spec: SweepSpec, out: list) -> None:
    fwd, bwd = spec.passes()
    if spec.layout == "batch":
        if fwd is not None:
            out.append(Finding("speccheck", spec.name,
                               "batch layout has a forward PassSpec (the "
                               "fused factorisation owns the forward pass)"))
        if bwd.scale is not None:
            out.append(Finding("speccheck", spec.name,
                               "batch back-substitution is scaled (the "
                               "fused factorisation already divided)"))
        _check_terms(spec, bwd, "bwd", out)
        return

    _check_terms(spec, fwd, "fwd", out)
    _check_terms(spec, bwd, "bwd", out)

    # exactly one inverse-diagonal scale, on the transposed-dependent side
    scaled = [name for name, p in (("fwd", fwd), ("bwd", bwd))
              if p.scale is not None]
    want_side = "bwd" if spec.transposed else "fwd"
    if scaled != [want_side]:
        out.append(Finding(
            "speccheck", spec.name,
            f"inverse-diagonal scale on {scaled or ['neither pass']}, "
            f"expected exactly one on the {want_side} pass "
            f"({'A^T = U^T*L^T scales back-substitution' if spec.transposed else 'A = L*U scales forward substitution'})"))

    # EPS placement: uniform specs read eps in the unscaled outer-band term
    eps_in = [name for name, p in (("fwd", fwd), ("bwd", bwd))
              for src, _lag in p.terms if src == EPS_PARAM]
    if spec.uniform:
        want_eps = ["bwd" if spec.transposed else "fwd"]
        if eps_in != want_eps:
            out.append(Finding("speccheck", spec.name,
                               f"EPS parameter read in {eps_in or 'no'} "
                               f"pass(es), expected exactly once in the "
                               f"{want_eps[0]} pass"))
    elif eps_in:
        out.append(Finding("speccheck", spec.name,
                           "non-uniform spec reads the EPS parameter"))


def _check_recurrence_structure(spec: RecurrenceSpec, out: list) -> None:
    """The gate-operand contract: a recurrence is ONE unscaled pass whose
    multiplicative coefficients are per-token gate operands, wired so the
    lag-k carry reads gate operand k-1 (the operand order the dispatcher
    ``ops.recurrence`` passes) with lags ascending — the subtraction
    order of the generated body, part of the resident==streamed
    bit-exactness contract."""
    passes = spec.passes()
    if len(passes) != 1:
        out.append(Finding("speccheck", spec.name,
                           f"recurrence spec runs {len(passes)} passes — a "
                           f"gated recurrence has no back-substitution "
                           f"partner, it must be a single pass"))
        return
    (pspec,) = passes
    sub = f"{spec.name}.pass"
    if pspec.scale is not None:
        out.append(Finding("speccheck", sub,
                           f"recurrence pass is scaled by {pspec.scale!r} — "
                           f"gated recurrences have no stored inverse "
                           f"diagonal"))
    lags = _lags(pspec)
    if lags != tuple(range(1, spec.order + 1)):
        out.append(Finding("speccheck", sub,
                           f"pass lags {lags} are not the ascending carry "
                           f"range 1..{spec.order} (gate-operand order is "
                           f"part of the bit-exactness contract)"))
    for src, lag in pspec.terms:
        if src == EPS_PARAM:
            out.append(Finding("speccheck", sub,
                               "recurrence pass reads the EPS parameter "
                               "(a uniform-penta concept)"))
        elif src != lag - 1:
            out.append(Finding("speccheck", sub,
                               f"lag-{lag} carry reads gate operand {src!r}, "
                               f"expected operand {lag - 1} — the gate "
                               f"operands are wired to the wrong lags"))


def _check_recurrence_twin(spec: RecurrenceSpec, out: list) -> None:
    """The reversed twin is the same machine walked the other way: same
    pass table, only the walk direction differs."""
    if spec.reverse:
        return
    twin = engine.REGISTRY.get(spec.twin_name())
    if twin is None:
        out.append(Finding("speccheck", spec.name,
                           f"reversed twin {spec.twin_name()!r} is not "
                           f"registered"))
        return
    if spec.passes() != twin.passes():
        out.append(Finding("speccheck", spec.name,
                           f"reversed twin {twin.name} runs a different "
                           f"pass table — reversal only mirrors the walk, "
                           f"it never re-wires the gate terms"))


def _check_twin(spec: SweepSpec, out: list) -> None:
    """Transposed twin = the same machine with the scale moved."""
    if spec.layout == "batch" or spec.transposed:
        return
    twin_name = spec.twin_name()
    twin = engine.REGISTRY.get(twin_name)
    if twin is None:
        out.append(Finding("speccheck", spec.name,
                           f"transposed twin {twin_name!r} is not "
                           f"registered"))
        return
    fwd, bwd = spec.passes()
    tfwd, tbwd = twin.passes()
    if (_lags(fwd), _lags(bwd)) != (_lags(tfwd), _lags(tbwd)):
        out.append(Finding("speccheck", spec.name,
                           f"twin {twin_name} runs different lag sequences "
                           f"({(_lags(tfwd), _lags(tbwd))} vs "
                           f"{(_lags(fwd), _lags(bwd))}) — not the same "
                           f"sweep machine"))
    if not spec.uniform and (fwd.terms, bwd.terms) != (tfwd.terms,
                                                       tbwd.terms):
        out.append(Finding("speccheck", spec.name,
                           f"twin {twin_name} reads different coefficient "
                           f"terms — transposition only shifts rows on the "
                           f"host and moves the scale, it never re-wires "
                           f"the term table"))
    if (fwd.scale, tbwd.scale) != (spec.scale_row, twin.scale_row) or \
            (bwd.scale, tfwd.scale) != (None, None):
        out.append(Finding("speccheck", spec.name,
                           f"scale not moved fwd->bwd between {spec.name} "
                           f"and {twin_name}"))


def _check_streamed_sibling(spec, out: list) -> None:
    if not spec.streamed:
        return
    resident = engine.REGISTRY.get(spec.resident_name)
    if resident is None:
        out.append(Finding("speccheck", spec.name,
                           f"resident sibling {spec.resident_name!r} is "
                           f"not registered"))
        return
    if spec.passes() != resident.passes():
        out.append(Finding("speccheck", spec.name,
                           "streamed variant runs a different pass table "
                           "than its resident sibling (streaming must "
                           "move carries, never arithmetic)"))


def _check_fused_sibling(spec, out: list) -> None:
    """A fused spec is its two-call sibling collapsed into one call: same
    pass table, ONE pallas_call, and strictly fewer modelled HBM words —
    the whole point of fusing is deleting the intermediate round trip."""
    if not getattr(spec, "fused", False):
        return
    sibling = engine.REGISTRY.get(spec.unfused_name)
    if sibling is None:
        out.append(Finding("speccheck", spec.name,
                           f"two-call sibling {spec.unfused_name!r} is not "
                           f"registered (fused specs must keep their spill "
                           f"fallback)"))
        return
    if spec.passes() != sibling.passes():
        out.append(Finding("speccheck", spec.name,
                           "fused variant runs a different pass table than "
                           "its two-call sibling (fusing must move the "
                           "intermediate to scratch, never arithmetic)"))
    if spec.num_pallas_calls != 1:
        out.append(Finding("speccheck", spec.name,
                           f"fused spec claims {spec.num_pallas_calls} "
                           f"pallas_calls — fusing means ONE"))
    got = spec.traffic_words(TRACE_N, TRACE_M)
    sib = sibling.traffic_words(TRACE_N, TRACE_M)
    if got >= sib:
        out.append(Finding("speccheck", spec.name,
                           f"fused traffic ({got} words) is not below the "
                           f"two-call sibling's ({sib}) — the fusion saves "
                           f"nothing"))


def _check_accounting(spec, out: list) -> None:
    """Recount traffic + VMEM from the captured builders; exact match."""
    records = trace_spec_calls(spec)
    want_calls = spec.num_pallas_calls
    if len(records) != want_calls:
        out.append(Finding("speccheck", spec.name,
                           f"builder emitted {len(records)} pallas_call(s), "
                           f"expected {want_calls}"))
        return
    got = recount_traffic_words(records)
    want = spec.traffic_words(TRACE_N, TRACE_M)
    if got != want:
        out.append(Finding(
            "speccheck", spec.name,
            f"HBM traffic drift: builders move {got} words at "
            f"(N={TRACE_N}, M={TRACE_M}) but SweepSpec.traffic_words "
            f"claims {want} — the roofline model no longer matches the "
            f"code"))
    got_vmem = recount_vmem_counts(records)
    want_vmem = tuple(spec.vmem_counts()) + (spec.sweep_scratch(),)
    labels = ("blocks", "lhs_rows", "carry_rows", "sweep_scratch")
    if got_vmem != want_vmem:
        out.append(Finding(
            "speccheck", spec.name,
            f"VMEM residency drift: builders hold {got_vmem} "
            f"({', '.join(labels)}) but SweepSpec.vmem_counts claims "
            f"{want_vmem} — the budget check no longer matches the code"))


def _check_storage_pricing(spec, out: list) -> None:
    """Mixed-precision pricing sweep: ``traffic_bytes`` must price the
    STORED operand words at the storage itemsize and the writes /
    intermediates at the fp32-promoted compute itemsize — the per-operand
    itemsize split the bf16 storage path's halved-bytes claim rests on."""
    import jax.numpy as jnp
    n, m = TRACE_N, TRACE_M
    f32 = spec.traffic_bytes(n, m, jnp.float32)
    bf16 = spec.traffic_bytes(n, m, jnp.float32, jnp.dtype(jnp.bfloat16))
    want = 2 * spec.storage_words(n, m) + 4 * spec.compute_words(n, m)
    if bf16 != want:
        out.append(Finding(
            "speccheck", spec.name,
            f"bf16-storage pricing drift: traffic_bytes says {bf16} but "
            f"storage_words x 2 + compute_words x 4 = {want} — the "
            f"per-operand itemsize split no longer holds"))
    if not bf16 < f32:
        out.append(Finding(
            "speccheck", spec.name,
            f"bf16 storage does not reduce modelled bytes ({bf16} vs "
            f"{f32} at fp32) — the spec stores nothing at the storage "
            f"dtype?"))


def _check_sharded_traffic(spec, out: list) -> None:
    """The per-device model is the single-device model at the local lane
    count — guard the two code paths against diverging."""
    for n_shards in (1, 3):
        got = spec.sharded_traffic_words(TRACE_N, TRACE_M, n_shards)
        want = spec.traffic_words(TRACE_N, shard_lanes(TRACE_M, n_shards))
        if got != want:
            out.append(Finding(
                "speccheck", spec.name,
                f"sharded traffic at {n_shards} shard(s) is {got} words, "
                f"expected the single-device model at the local lane "
                f"count ({want})"))


def run() -> list:
    """All speccheck invariants over every registered spec."""
    out: list = []
    for name in sorted(engine.REGISTRY):
        spec = engine.REGISTRY[name]
        if spec.name != name:
            out.append(Finding("speccheck", name,
                               f"registry key disagrees with spec.name "
                               f"({spec.name!r})"))
        if isinstance(spec, RecurrenceSpec):
            _check_recurrence_structure(spec, out)
            _check_recurrence_twin(spec, out)
        else:
            _check_structure(spec, out)
            _check_twin(spec, out)
        _check_streamed_sibling(spec, out)
        _check_fused_sibling(spec, out)
        _check_accounting(spec, out)
        _check_storage_pricing(spec, out)
        _check_sharded_traffic(spec, out)
    return out
