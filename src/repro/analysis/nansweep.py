"""Registry-driven NaN/sanitizer sweep over every generated kernel.

PR 3's dead-lane NaN bug is the motivating defect class: padding the
batch axis with zeros made the fused factorisation divide by the zero
pad, flooding the (sliced-off) padding with inf/NaN — harmless to the
answer, fatal under ``JAX_DEBUG_NANS`` and to the flush-to-zero path.
The guard against regressions used to be a hand-kept list of test files
in CI; this sweep derives the cases from the engine ``REGISTRY`` instead,
so a newly registered variant is sanitizer-covered the day it lands.

Per registered spec, the ops-layer entry point (``ops.entry_point``) runs
under ``jax_debug_nans`` on three shape classes:

  * **ragged** — both axes off the tile multiples (lane AND sweep
    padding active);
  * **dead-lane** — a tiny batch against a large lane tile (the padding
    dominates: most lanes are dead);
  * **aligned** — exact multiples (the identity-padding code paths must
    also stay silent when they are no-ops).

Any non-finite value in an intermediate raises immediately (debug-nans),
and the sliced outputs are additionally checked finite.

A packed spec engages only on whole packed groups (N % 8 == 0, M % 1024
== 0), so it runs each case embedded in the smallest such operand, zero
outside the case as a caller's padding leaves it: its dead lanes and rows
then ride through the packed sweep itself.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import penta_factor, thomas_factor
from repro.kernels import engine, ops
from repro.kernels.common import PACK, SUBLANE

from . import Finding

#: (case name, n, m, block_m, block_n) — block_n only used when streamed.
CASES = (
    ("ragged", 45, 70, 64, 16),
    ("dead-lane", 33, 3, 64, 16),
    ("aligned", 48, 64, 64, 16),
)


def _shared_factor(spec, rng, n):
    if spec.bandwidth == 3:
        a = rng.uniform(-1, 1, n).astype(np.float32)
        c = rng.uniform(-1, 1, n).astype(np.float32)
        b = (np.abs(a) + np.abs(c) + 2.5).astype(np.float32)
        return thomas_factor(*map(jnp.asarray, (a, b, c)))
    if spec.uniform:
        one = np.ones(n, np.float32)
        s = 0.11
        coeffs = (s * one, -4 * s * one, (1 + 6 * s) * one,
                  -4 * s * one, s * one)
    else:
        a, b, d, e = (rng.uniform(-1, 1, n).astype(np.float32)
                      for _ in range(4))
        c = (np.abs(a) + np.abs(b) + np.abs(d) + np.abs(e) + 4.0).astype(
            np.float32)
        coeffs = (a, b, c, d, e)
    return penta_factor(*map(jnp.asarray, coeffs))


def _batch_diags(spec, rng, n, m):
    k = spec.bandwidth - 1
    off = [rng.uniform(-1, 1, (n, m)).astype(np.float32) for _ in range(k)]
    main = (sum(np.abs(o) for o in off) + np.float32(k + 1.0)).astype(
        np.float32)
    return tuple(map(jnp.asarray,
                     (*off[:k // 2], main, *off[k // 2:])))


def _recurrence_gates(spec, rng, n, m):
    """Stable per-token gates: |s| + |t| < 1 bounds every carry, so the
    sweep (and its zero padding) stays finite under debug-nans."""
    scales = (0.9,) if spec.order == 1 else (0.6, 0.3)
    return tuple(jnp.asarray(rng.uniform(-s, s, (n, m)).astype(np.float32))
                 for s in scales)


def _dispatch(spec, rng, n, m, block_m, block_n):
    """One solve of ``spec`` through its ops entry point; returns (n, m)."""
    fn = ops.entry_point(spec)
    rhs = jnp.asarray(rng.uniform(-1, 1, (n, m)).astype(np.float32))
    if getattr(spec, "packed", False):
        full = (-(-n // SUBLANE) * SUBLANE, -(-m // PACK) * PACK)
        rhs = jnp.zeros(full, rhs.dtype).at[:n, :m].set(rhs)
        f = _shared_factor(spec, rng, full[0])
        kwargs = dict(block_m=block_m, interpret=True,
                      transposed=spec.transposed)
        if spec.bandwidth == 5:
            kwargs["uniform"] = spec.uniform
        return fn(f, rhs, **kwargs)[:n, :m]
    bn = block_n if spec.streamed else None
    if spec.layout == "recurrence":
        return fn(*_recurrence_gates(spec, rng, n, m), rhs,
                  reverse=spec.reverse, block_m=block_m, block_n=bn,
                  interpret=True)
    fused = getattr(spec, "fused", False)
    if spec.layout == "batch":
        return fn(*_batch_diags(spec, rng, n, m), rhs, block_m=block_m,
                  block_n=bn, fused=fused, interpret=True)
    f = _shared_factor(spec, rng, n)
    kwargs = dict(block_m=block_m, block_n=bn, fused=fused, interpret=True,
                  transposed=spec.transposed)
    if spec.bandwidth == 5:
        kwargs["uniform"] = spec.uniform
    return fn(f, rhs, **kwargs)


def run() -> list:
    """Every REGISTRY spec x shape class under debug-nans; findings on any
    raised NaN or non-finite output."""
    out: list = []
    debug_nans_was = jax.config.jax_debug_nans
    jax.config.update("jax_debug_nans", True)
    try:
        for name in sorted(engine.REGISTRY):
            spec = engine.REGISTRY[name]
            for case, n, m, block_m, block_n in CASES:
                sub = f"{spec.name}[{case} n={n} m={m}]"
                rng = np.random.default_rng(7)
                try:
                    x = _dispatch(spec, rng, n, m, block_m, block_n)
                except FloatingPointError as exc:
                    out.append(Finding(
                        "nansweep", sub,
                        f"debug-nans tripped in an intermediate: "
                        f"{str(exc).splitlines()[0]} — padding is being "
                        f"fed through a divide (dead-lane NaN class)"))
                    continue
                except Exception as exc:  # noqa: BLE001
                    out.append(Finding("nansweep", sub,
                                       f"dispatch raised "
                                       f"{type(exc).__name__}: {exc}"))
                    continue
                vals = np.asarray(x)
                if vals.shape != (n, m):
                    out.append(Finding("nansweep", sub,
                                       f"output shape {vals.shape}, "
                                       f"expected {(n, m)}"))
                if not np.isfinite(vals).all():
                    out.append(Finding(
                        "nansweep", sub,
                        f"{int((~np.isfinite(vals)).sum())} non-finite "
                        f"value(s) in the sliced output"))
    finally:
        jax.config.update("jax_debug_nans", debug_nans_was)
    return out
