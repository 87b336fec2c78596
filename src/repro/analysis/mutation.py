"""Mutation self-test: prove the analyzer actually catches defects.

A static checker that never fires is indistinguishable from one that
works.  This module seeds one representative defect per class the
analyzer claims to cover — by patching the REAL pass tables, index-map
builders, carry reset, eps plumbing and traffic model in place — and
asserts the corresponding checker reports it.  Each mutation is applied
inside a context manager and fully reverted; the generated solvers are
``lru_cache``-d *jit wrappers* whose bodies re-read the module globals on
every (un-jitted) re-execution, so the capture layer sees the mutated
world without any cache invalidation.

Defect classes (the known failure modes of this codebase's history and
of the CUDA solvers the paper benchmarks):

  1. **swapped subtraction order** — reversing the forward-pass terms of
     the penta sweep keeps the math "correct" in exact arithmetic but
     breaks the bit-exactness contract; ``speccheck`` flags the
     non-canonical order.
  2. **off-by-one index map** — a ``chunk_spec`` that maps grid point
     ``k`` to block ``k + 1``; Pallas would clamp and silently corrupt.
     ``gridcheck`` flags blocks outside the range and block 0 never
     written.
  3. **dropped reset_carry** — the k == 0 zero-init removed; lane tile
     j+1 inherits tile j's final sweep state.  ``gridcheck``'s mock
     execution flags the cross-lane-tile carry race.
  4. **baked float(eps)** — concretizing the uniform eps operand; breaks
     ``jax.jit(solve)`` with a traced Factorization.  Caught twice:
     ``tracecheck`` (eval_shape with abstract leaves) and the AST lint
     on the mutated source text.
  5. **stale traffic constant** — ``traffic_words`` drifting from what
     the builders actually stream; ``speccheck``'s independent recount
     flags the exact word delta.
  6. **swapped gate lags** — the order-2 recurrence pass wiring the
     lag-1 carry to the second-gate operand and vice versa; parity tests
     at order 1 never see it and symmetric test data can mask it.
     ``speccheck``'s structural check on the gate-operand pass table
     flags the miswired lag.
  7. **forgotten descend mirror** — the fused single-call kernels' output
     index map using the ascend-phase walk for the descend phase too;
     every descend grid point then clamps onto the last chunk (Pallas
     never errors) and the back-substitution silently overwrites one
     block ``num_n`` times.  ``gridcheck``'s fused walk/coverage checks
     flag the missing mirror.
  8. **wrong packed group base** — the packed sweep addressing group g
     at ``g * n`` instead of ``g * 8 * n`` in its lane-tile-major
     scratch: group 0 is right, every later group sweeps lane tiles of
     group 0 a second time and leaves its own unsolved.  A one-group
     test never sees it; ``gridcheck`` runs the packed body against its
     row sibling on two groups and flags the layout.
"""

from __future__ import annotations

import pathlib
import contextlib
import dataclasses

import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import engine, ops

from . import Finding
from . import lint, gridcheck, speccheck, tracecheck


@dataclasses.dataclass(frozen=True)
class MutationResult:
    name: str
    detected: bool
    evidence: tuple  # the matching Finding(s), empty when undetected


# ---------------------------------------------------------------------------
# The seeded defects
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _swapped_subtraction_order():
    key = (5, False, False)
    fwd, bwd = engine._PASS_TABLE[key]
    engine._PASS_TABLE[key] = (
        engine.PassSpec(tuple(reversed(fwd.terms)), fwd.scale), bwd)
    try:
        yield
    finally:
        engine._PASS_TABLE[key] = (fwd, bwd)


@contextlib.contextmanager
def _off_by_one_index_map():
    orig = engine.chunk_spec

    def bad(block_n, block_m, num_n, *, reverse=False):
        if reverse:
            return orig(block_n, block_m, num_n, reverse=True)
        return pl.BlockSpec((block_n, block_m), lambda j, k: (k + 1, j))

    engine.chunk_spec = bad
    try:
        yield
    finally:
        engine.chunk_spec = orig


@contextlib.contextmanager
def _dropped_reset_carry():
    orig = engine.reset_carry
    engine.reset_carry = lambda carry_ref, k: None
    try:
        yield
    finally:
        engine.reset_carry = orig


@contextlib.contextmanager
def _baked_float_eps():
    orig = ops._uniform_eps_param

    def bad(f, dtype):
        eps = jnp.broadcast_to(jnp.asarray(f.eps), f.beta.shape)
        return jnp.full((1, 1), float(eps[2]), dtype)

    ops._uniform_eps_param = bad
    try:
        yield
    finally:
        ops._uniform_eps_param = orig


@contextlib.contextmanager
def _swapped_gate_lags():
    orig = engine._RECUR_TABLE[2]
    engine._RECUR_TABLE[2] = engine.PassSpec(((1, 1), (0, 2)), None)
    try:
        yield
    finally:
        engine._RECUR_TABLE[2] = orig


@contextlib.contextmanager
def _forgotten_descend_mirror():
    orig = engine.fused_chunk_spec

    def bad(block_n, block_m, num_n, *, phase):
        return orig(block_n, block_m, num_n,
                    phase="ascend" if phase == "descend" else phase)

    engine.fused_chunk_spec = bad
    try:
        yield
    finally:
        engine.fused_chunk_spec = orig


@contextlib.contextmanager
def _wrong_packed_group_base():
    orig = engine._packed_rows

    def bad(n, g, i):
        return pl.ds(g * n + i, engine.SUBLANE, stride=n)

    engine._packed_rows = bad
    try:
        yield
    finally:
        engine._packed_rows = orig


@contextlib.contextmanager
def _stale_traffic_constant():
    orig = engine.SweepSpec.traffic_words

    def bad(self, n, m):
        return orig(self, n, m) + n * m

    engine.SweepSpec.traffic_words = bad
    try:
        yield
    finally:
        engine.SweepSpec.traffic_words = orig


# ---------------------------------------------------------------------------
# Per-class detection probes
# ---------------------------------------------------------------------------

def _trace_uniform_penta() -> list:
    """tracecheck restricted to the cells the eps mutation can reach."""
    out: list = []
    for case in tracecheck.contract_cases():
        if case[1] == 5 and case[2] == "uniform":
            out.extend(tracecheck.check_case(*case))
    return out


def _lint_mutated_ops() -> list:
    """AST-lint the eps mutation at the source level: rewrite the real
    ops.py text to the baked-float form and lint the result."""
    src = pathlib.Path(ops.__file__).read_text()
    mutated = src.replace("eps[2].reshape(1, 1).astype(dtype)",
                          "jnp.asarray(float(eps[2]), dtype).reshape(1, 1)")
    if mutated == src:
        return [Finding("mutation", "ops.py",
                        "eps site not found — the source-level mutation "
                        "no longer applies; update mutation.py")]
    findings = lint.lint_source(mutated, "ops.py(mutated)")
    if not findings:
        return []
    return findings


def _float_eps_probe() -> list:
    """Both detection layers for defect class 4 must fire."""
    traced = _trace_uniform_penta()
    linted = _lint_mutated_ops()
    if any(f.checker == "mutation" for f in linted):
        return linted  # the mutation itself is broken — surface that
    if not traced or not linted:
        return []  # one layer missed -> undetected
    return traced + linted


_MUTATIONS = (
    ("swapped-subtraction-order", _swapped_subtraction_order,
     speccheck.run, "subtraction order"),
    ("index-map-off-by-one", _off_by_one_index_map,
     gridcheck.run, "outside the block range"),
    ("dropped-reset-carry", _dropped_reset_carry,
     gridcheck.run, "carry race"),
    ("baked-float-eps", _baked_float_eps,
     _float_eps_probe, ""),
    ("stale-traffic-constant", _stale_traffic_constant,
     speccheck.run, "HBM traffic drift"),
    ("swapped-gate-lags", _swapped_gate_lags,
     speccheck.run, "gate operand"),
    ("forgotten-descend-mirror", _forgotten_descend_mirror,
     gridcheck.run, "mirror"),
    ("wrong-packed-group-base", _wrong_packed_group_base,
     gridcheck.run, "packed layout"),
)


def self_test(verbose: bool = False) -> list:
    """Run every seeded defect; returns one MutationResult per class."""
    import jax

    results = []
    for name, mutate, probe, match in _MUTATIONS:
        # the probes re-trace mutated call paths; a clean trace cached by
        # an earlier run would mask the defect (and a mutated one would
        # leak out), so the cache is dropped on both sides
        jax.clear_caches()
        with mutate():
            findings = probe()
        jax.clear_caches()
        hits = tuple(f for f in findings if match in f.message)
        results.append(MutationResult(name, bool(hits), hits))
        if verbose:
            mark = "caught" if hits else "MISSED"
            print(f"  {name:28s} {mark} "
                  f"({len(hits)} finding(s))")
    return results
