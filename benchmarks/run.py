"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Wall-times are CPU-host times
(this container has no TPU): the *relative* constant-vs-batch trends mirror
the paper's Figs. 2-4 mechanism (less memory traffic per solve); the
absolute roofline story for TPU lives in EXPERIMENTS.md §Roofline and the
analytic kernel-traffic table (bench_kernel_traffic).

Solver benchmarks route through the unified ``repro.solver`` front-end, so
constant-vs-batch × reference-vs-pallas is one sweep (``backends`` table).

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run fig2       # one table
    PYTHONPATH=src python -m benchmarks.run --json     # + BENCH_solvers.json

``--json`` additionally writes ``BENCH_solvers.json`` — a list of
``{name, us_per_call, backend, n, m}`` rows (the ``backends`` sweep, penta
``batch``-mode rows included, the ``grad_solve`` rows timing the
custom_vjp adjoint, and the ``recurrence`` rows timing the sequence-model
substrate) — so the perf trajectory is machine-readable across PRs.
Kernel-backed rows also carry ``model_bytes`` (the spec-derived expected
HBM traffic) plus the ``traffic`` key it was resolved from; the regress
gate re-derives the number from the live registry, so a traffic-model
drift fails CI exactly like a timing regression.  CI runs ``--json`` in
interpret mode on every push, then diffs the rows against the committed
baseline with ``tools/bench_regress.py``, so the perf plumbing cannot
silently rot and a matched row cannot silently get 1.5x slower.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

JSON_PATH = "BENCH_solvers.json"
_ROWS: list = []   # machine-readable mirror of the printed CSV


def _record(name: str, us_per_call: float, *, backend=None, n=None, m=None,
            derived: str = "", traffic: dict | None = None):
    """``traffic`` is the spec-resolver key (bandwidth/mode/streamed/fused/
    storage_dtype, or order/reverse for recurrences); when present the row
    also carries ``model_bytes`` — the expected HBM traffic re-derived by
    ``tools/bench_regress.py`` from the same key, so a drifted traffic
    model fails the bench gate."""
    row = {"name": name, "us_per_call": round(us_per_call, 1),
           "backend": backend, "n": n, "m": m}
    if traffic is not None:
        row["model_bytes"] = _model_bytes(traffic, n, m)
        row["traffic"] = traffic
        derived = (derived + "_" if derived else "") \
            + f"model_bytes={row['model_bytes']}"
    print(f"{name},{us_per_call:.0f},{derived}")
    _ROWS.append(row)


def _model_bytes(traffic: dict, n: int, m: int) -> int:
    """Resolve a row's traffic key through the kernel spec registry."""
    from repro.kernels import ops as kops
    key = dict(traffic)
    if "order" in key:
        return kops.recurrence_hbm_traffic_bytes(key.pop("order"), n, m,
                                                 **key)
    return kops.solver_hbm_traffic_bytes(key.pop("bandwidth"),
                                         key.pop("mode"), n, m, **key)


def _timeit(fn, *args, reps=3, warmup=1):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return min(ts) * 1e6   # us


def _rhs(n, m, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=(n, m)).astype(np.float32))


# ---------------------------------------------------------------------------
# Fig. 2: tridiagonal — cuThomasConstantBatch vs cuThomasBatch (N x M grid)
# ---------------------------------------------------------------------------

def bench_fig2_tridiag():
    from repro.solver import BandedSystem, plan
    sigma = 0.4
    for n in (64, 256, 1024):
        for m in (64, 512, 4096):
            ops = {}
            for mode in ("constant", "batch"):
                p = plan(BandedSystem.tridiag(
                    -sigma, 1 + 2 * sigma, -sigma, n=n, mode=mode,
                    periodic=True, batch=m if mode == "batch" else None),
                    backend="reference")
                d = _rhs(n, m)
                ops[mode] = _timeit(jax.jit(p.solve), d)
            speedup = ops["batch"] / ops["constant"]
            _record(f"fig2_tridiag_N{n}_M{m}", ops["constant"],
                    backend="reference", n=n, m=m,
                    derived=f"speedup_vs_batch={speedup:.2f}x")


# ---------------------------------------------------------------------------
# Fig. 3: pentadiagonal — cuPentConstantBatch vs cuPentBatch
# ---------------------------------------------------------------------------

def bench_fig3_penta():
    from repro.solver import BandedSystem, plan
    s = 0.11
    coef = (s, -4 * s, 1 + 6 * s, -4 * s, s)
    for n in (64, 256, 1024):
        for m in (64, 512, 4096):
            res = {}
            for mode in ("constant", "batch"):
                p = plan(BandedSystem.penta(
                    *coef, n=n, mode=mode, periodic=True,
                    batch=m if mode == "batch" else None),
                    backend="reference")
                d = _rhs(n, m)
                res[mode] = _timeit(jax.jit(p.solve), d)
            _record(f"fig3_penta_N{n}_M{m}", res["constant"],
                    backend="reference", n=n, m=m,
                    derived=f"speedup_vs_batch={res['batch']/res['constant']:.2f}x")


# ---------------------------------------------------------------------------
# Fig. 4: cuPentUniformBatch vs cuPentBatch
# ---------------------------------------------------------------------------

def bench_fig4_uniform():
    from repro.solver import BandedSystem, plan
    s = 0.11
    coef = (s, -4 * s, 1 + 6 * s, -4 * s, s)
    for n, m in ((256, 512), (1024, 512), (256, 4096)):
        res = {}
        for mode in ("uniform", "batch"):
            p = plan(BandedSystem.penta(
                *coef, n=n, mode=mode, periodic=True,
                batch=m if mode == "batch" else None), backend="reference")
            d = _rhs(n, m)
            res[mode] = _timeit(jax.jit(p.solve), d)
        _record(f"fig4_uniform_N{n}_M{m}", res["uniform"],
                backend="reference", n=n, m=m,
                derived=f"speedup_vs_batch={res['batch']/res['uniform']:.2f}x")


# ---------------------------------------------------------------------------
# Storage table (§III.A / §IV.A claims: ~75% and ~83% reductions)
# ---------------------------------------------------------------------------

def bench_memory_table():
    from repro.solver import BandedSystem, plan
    n, m = 1024, 65536

    def total(system):
        return plan(system, backend="reference").storage_bytes(
            rhs_batch=m)["total_bytes"]

    tc = total(BandedSystem.tridiag(1., 4., 1., n=n, mode="constant"))
    tb = total(BandedSystem.tridiag(1., 4., 1., n=n, mode="batch", batch=m))
    print(f"mem_tridiag_N{n}_M{m},0,reduction={100*(1-tc/tb):.1f}%_paper75%")
    pen = (1., -4., 7., -4., 1.)
    pc = total(BandedSystem.penta(*pen, n=n, mode="constant"))
    pb = total(BandedSystem.penta(*pen, n=n, mode="batch", batch=m))
    pu = total(BandedSystem.penta(*pen, n=n, mode="uniform"))
    print(f"mem_penta_N{n}_M{m},0,reduction={100*(1-pc/pb):.1f}%_paper83%")
    print(f"mem_penta_uniform_N{n}_M{m},0,reduction={100*(1-pu/pb):.1f}%")


# ---------------------------------------------------------------------------
# Kernel HBM-traffic table (the TPU roofline story for the Pallas kernels)
# ---------------------------------------------------------------------------

def bench_kernel_traffic():
    from repro.kernels.fused_cn import hbm_traffic_bytes as fused_t
    from repro.kernels.fused_cn_penta import hbm_traffic_bytes as fusedp_t
    from repro.kernels.penta import hbm_traffic_bytes as pen_t
    from repro.kernels.thomas import hbm_traffic_bytes as tri_t
    n, m = 1024, 65536
    t = tri_t(n, m)
    print(f"traffic_tridiag_N{n}_M{m},0,batch/constant="
          f"{t['batch']/t['constant']:.2f}x")
    print(f"traffic_tridiag_streamed_N{n}_M{m},0,streamed/constant="
          f"{t['constant_streamed']/t['constant']:.2f}x_still_"
          f"{t['batch']/t['constant_streamed']:.2f}x_under_batch")
    print(f"traffic_tridiag_batch_streamed_N{n}_M{m},0,streamed/resident="
          f"{t['batch_streamed']/t['batch']:.2f}x_spilled_chat")
    p = pen_t(n, m)
    print(f"traffic_penta_N{n}_M{m},0,batch/constant="
          f"{p['batch']/p['constant']:.2f}x")
    print(f"traffic_penta_streamed_N{n}_M{m},0,streamed/constant="
          f"{p['constant_streamed']/p['constant']:.2f}x_still_"
          f"{p['batch']/p['constant_streamed']:.2f}x_under_batch")
    print(f"traffic_penta_batch_streamed_N{n}_M{m},0,streamed/resident="
          f"{p['batch_streamed']/p['batch']:.2f}x_spilled_gamma_delta")
    fz = fused_t(n, m)
    print(f"traffic_fused_cn_N{n}_M{m},0,unfused/fused="
          f"{fz['unfused_pipeline']/fz['fused']:.2f}x")
    fp = fusedp_t(n, m)
    print(f"traffic_fused_cn_penta_N{n}_M{m},0,unfused/fused="
          f"{fp['unfused_pipeline']/fp['fused']:.2f}x")
    # memory-roofline seconds per CN step on v5e (819 GB/s)
    for name, b in [("constant_pipeline", fz["unfused_pipeline"]),
                    ("fused", fz["fused"]),
                    ("penta_fused", fp["fused"])]:
        print(f"roofline_cn_step_{name},{b/819e9*1e6:.1f},hbm_bound_us")


# ---------------------------------------------------------------------------
# Pallas kernels (interpret mode) vs pure-jnp reference — correctness + time
# ---------------------------------------------------------------------------

def bench_pallas_kernels():
    from repro.core import thomas_factor
    from repro.kernels import thomas_constant
    n, m = 256, 1024
    rng = np.random.default_rng(0)
    a = rng.uniform(-1, 1, n).astype(np.float32)
    c = rng.uniform(-1, 1, n).astype(np.float32)
    b = (np.abs(a) + np.abs(c) + 2.5).astype(np.float32)
    f = thomas_factor(jnp.asarray(a), jnp.asarray(b), jnp.asarray(c))
    d = _rhs(n, m)
    t = _timeit(lambda dd: thomas_constant(f, dd), d, reps=2)
    _record(f"pallas_thomas_constant_interp_N{n}_M{m}", t, backend="pallas",
            n=n, m=m, derived="interpret_mode")


# ---------------------------------------------------------------------------
# Backend axis: constant-vs-batch x reference-vs-pallas through repro.solver
# ---------------------------------------------------------------------------

def bench_backends():
    """One sweep over the repro.solver registry: the benchmark surface later
    PRs extend when they plug new backends in. (Pallas rows are interpret
    mode off-TPU — compare trends, not absolutes.)"""
    from repro.solver import BandedSystem, plan
    sigma = 0.4
    n, m = 256, 512
    d = _rhs(n, m)
    for mode in ("constant", "batch"):
        for backend in ("reference", "pallas"):
            p = plan(BandedSystem.tridiag(
                -sigma, 1 + 2 * sigma, -sigma, n=n, mode=mode,
                batch=m if mode == "batch" else None), backend=backend)
            t = _timeit(jax.jit(p.solve), d, reps=2)
            _record(f"solver_tridiag_{mode}_{backend}_N{n}_M{m}", t,
                    backend=backend, n=n, m=m, derived=f"mode={mode}",
                    traffic={"bandwidth": 3, "mode": mode}
                    if backend == "pallas" else None)
    s = 0.11
    for mode in ("constant", "batch"):
        for backend in ("reference", "pallas"):
            p = plan(BandedSystem.penta(
                s, -4 * s, 1 + 6 * s, -4 * s, s, n=n, mode=mode,
                batch=m if mode == "batch" else None), backend=backend)
            t = _timeit(jax.jit(p.solve), d, reps=2)
            _record(f"solver_penta_{mode}_{backend}_N{n}_M{m}", t,
                    backend=backend, n=n, m=m, derived=f"mode={mode}",
                    traffic={"bandwidth": 5, "mode": mode}
                    if backend == "pallas" else None)
    bench_backends_streamed()


def bench_backends_streamed():
    """Large-N rows in the regime the HBM-streamed split-N kernels unlock:
    at N=16384 the resident pallas working set exceeds the VMEM budget at
    EVERY block_m candidate (even 128 needs 16 MiB), so before PR 3
    ``auto`` could only fall back to reference here.  ``auto`` now
    resolves to pallas with a streamed ``block_n`` AND the fused
    single-call sweeps (the full-N scratch fits at block_m=128) — both
    asserted below, so neither the fallback nor the two-call spill can
    silently return.  The ``bf16s`` rows store factor + streamed RHS at
    bf16 in HBM (carries stay fp32), halving the stored-operand bytes."""
    from repro.solver import BandedSystem, plan
    n, m = 16384, 1024
    d = _rhs(n, m)
    sigma = 0.4
    tri = BandedSystem.tridiag(-sigma, 1 + 2 * sigma, -sigma, n=n)
    s = 0.11
    pen = BandedSystem.penta(s, -4 * s, 1 + 6 * s, -4 * s, s, n=n)
    for kind, bw, system in (("tridiag", 3, tri), ("penta", 5, pen)):
        for backend in ("reference", "auto"):
            p = plan(system, backend=backend)
            if backend == "auto":
                assert p.backend == "pallas", "streamed auto-select regressed"
                block_n = p.impl.block_n
                assert block_n is not None, "expected the streamed kernels"
                assert p.impl.fact.meta.opt("fused") is True, \
                    "auto no longer selects the fused single-call sweeps"
                label = "pallas"
                derived = f"fused_block_n={block_n}"
                traffic = {"bandwidth": bw, "mode": "constant",
                           "streamed": True, "fused": True}
            else:
                label, derived, traffic = backend, "mode=constant", None
            t = _timeit(jax.jit(p.solve), d, reps=2)
            _record(f"solver_{kind}_constant_{label}_fused_streamed_N{n}_M{m}"
                    if backend == "auto" else
                    f"solver_{kind}_constant_{label}_N{n}_M{m}", t,
                    backend=label, n=n, m=m, derived=derived, traffic=traffic)
        # mixed-precision storage on the same fused streamed point
        p = plan(system, backend="pallas", storage_dtype="bf16")
        assert p.impl.fact.meta.opt("storage_dtype") == "bfloat16"
        t = _timeit(jax.jit(p.solve), d, reps=2)
        _record(f"solver_{kind}_constant_pallas_bf16s_streamed_N{n}_M{m}", t,
                backend="pallas", n=n, m=m,
                derived=f"storage=bf16_fused={p.impl.fact.meta.opt('fused')}",
                traffic={"bandwidth": bw, "mode": "constant",
                         "streamed": True,
                         "fused": bool(p.impl.fact.meta.opt("fused")),
                         "storage_dtype": "bf16"})
    bench_batch_streamed()


def bench_batch_streamed():
    """mode="batch" past the old VMEM wall: before the sweep engine the
    per-lane diagonal blocks could not stream, so ``auto`` fell back to
    reference at this N.  The engine's batch-streamed pair (fused-factor
    scratch spilled to HBM between the passes) keeps pallas in play —
    asserted, so the fallback cannot silently return."""
    from repro.solver import BandedSystem, plan
    n, m = 16384, 1024
    d = _rhs(n, m)
    sigma = 0.4
    system = BandedSystem.tridiag(-sigma, 1 + 2 * sigma, -sigma, n=n,
                                  mode="batch", batch=m)
    for backend in ("reference", "auto"):
        p = plan(system, backend=backend)
        if backend == "auto":
            assert p.backend == "pallas", "batch streamed auto-select regressed"
            block_n = p.impl.block_n
            assert block_n is not None, "expected the batch streamed kernels"
            # the batch fused working set (two full-N sweep scratches)
            # exceeds the VMEM budget here: the tuner must SPILL to the
            # two-call pair, not reject the solve
            assert p.impl.fact.meta.opt("fused") is False, \
                "batch fused spill rule regressed"
            label, derived = "pallas", f"batch_streamed_block_n={block_n}"
            traffic = {"bandwidth": 3, "mode": "batch", "streamed": True}
        else:
            label, derived, traffic = backend, "mode=batch", None
        t = _timeit(jax.jit(p.solve), d, reps=2)
        _record(f"solver_tridiag_batch_{label}_streamed_N{n}_M{m}"
                if backend == "auto" else
                f"solver_tridiag_batch_{label}_N{n}_M{m}", t,
                backend=label, n=n, m=m, derived=derived, traffic=traffic)
    bench_sharded()


def bench_sharded():
    """The sharded x streamed composition: the ``sharded`` backend running
    the engine's Pallas kernels per device inside shard_map (vs the old
    per-shard reference sweeps, kept as the ``kernels="reference"`` row).
    The engine dispatch is asserted so the composition cannot silently
    degrade back to reference sweeps."""
    from repro.solver import BandedSystem, plan
    sigma = 0.4
    n, m = 256, 512
    d = _rhs(n, m)
    system = BandedSystem.tridiag(-sigma, 1 + 2 * sigma, -sigma, n=n)
    for kernels in ("reference", "auto"):
        p = plan(system, backend="sharded", kernels=kernels)
        if kernels == "auto":
            assert p.impl.kernels == "pallas", "sharded kernel dispatch regressed"
        label = p.impl.kernels
        t = _timeit(jax.jit(p.solve), d, reps=2)
        _record(f"solver_tridiag_constant_sharded_{label}_N{n}_M{m}", t,
                backend="sharded", n=n, m=m,
                derived=f"shards={p.impl.n_shards}_kernels={label}")
    # large-N: streamed split-N chunks per shard (block_n frozen in meta)
    n = 16384
    d = _rhs(n, m)
    p = plan(BandedSystem.tridiag(-sigma, 1 + 2 * sigma, -sigma, n=n),
             backend="sharded")
    assert p.impl.kernels == "pallas", "sharded kernel dispatch regressed"
    assert p.impl.block_n is not None, "expected streamed kernels per shard"
    t = _timeit(jax.jit(p.solve), d, reps=2)
    _record(f"solver_tridiag_constant_sharded_streamed_N{n}_M{m}", t,
            backend="sharded", n=n, m=m,
            derived=f"shards={p.impl.n_shards}_block_n={p.impl.block_n}")


# ---------------------------------------------------------------------------
# Differentiable solves: the custom_vjp adjoint (transposed solve reusing
# the forward factorization) through the pure factorize/solve API
# ---------------------------------------------------------------------------

def bench_grad_solve():
    """Time jax.grad through ``solve`` — the adjoint is one transposed
    banded solve on the SAME stored factor, so grad should cost ~2x the
    forward solve, not a refactor + dense VJP."""
    from repro.solver import BandedSystem, factorize, solve
    n, m = 256, 512
    d = _rhs(n, m)
    sigma = 0.4
    systems = {
        "tridiag": BandedSystem.tridiag(-sigma, 1 + 2 * sigma, -sigma, n=n,
                                        periodic=True),
        "penta": BandedSystem.penta(0.11, -0.44, 1.66, -0.44, 0.11, n=n,
                                    periodic=True),
    }
    for kind, system in systems.items():
        fact = factorize(system, backend="reference")
        fwd = _timeit(jax.jit(lambda r: solve(fact, r)), d, reps=2)
        g = jax.jit(jax.grad(lambda r: jnp.sum(solve(fact, r) ** 2)))
        t = _timeit(g, d, reps=2)
        _record(f"grad_solve_{kind}_reference_N{n}_M{m}", t,
                backend="reference", n=n, m=m,
                derived=f"grad/fwd={t / fwd:.2f}x_adjoint_reuses_factor")
    bench_grad_solve_streamed()


def bench_grad_solve_streamed():
    """grad through a LARGE-N streamed solve: the adjoint runs the sweep
    engine's streamed TRANSPOSED Pallas kernels on the same stored factor
    (no reference fallback — asserted via the auto-tuned streamed plan)."""
    from repro.solver import BandedSystem, factorize, solve
    n, m = 16384, 1024
    d = _rhs(n, m)
    sigma = 0.4
    system = BandedSystem.tridiag(-sigma, 1 + 2 * sigma, -sigma, n=n)
    fact = factorize(system, backend="auto")
    assert fact.backend == "pallas", "streamed auto-select regressed"
    assert fact.meta.opt("block_n") is not None, "expected streamed kernels"
    fwd = _timeit(jax.jit(lambda r: solve(fact, r)), d, reps=2)
    g = jax.jit(jax.grad(lambda r: jnp.sum(solve(fact, r) ** 2)))
    t = _timeit(g, d, reps=2)
    _record(f"grad_solve_streamed_tridiag_pallas_N{n}_M{m}", t,
            backend="pallas", n=n, m=m,
            derived=f"grad/fwd={t / fwd:.2f}x_adjoint_on_streamed_pallas")


# ---------------------------------------------------------------------------
# Gated linear recurrences: XLA scan vs the engine's Pallas kernels
# ---------------------------------------------------------------------------

def bench_recurrence():
    """The sequence-model substrate (``repro.core.recurrence``): first- and
    second-order gated recurrences, XLA scan vs the sweep engine's Pallas
    recurrence kernels (interpret mode off-TPU — compare trends, not
    absolutes), plus a forced streamed row.  The auto policy is asserted
    so the models' kernel path cannot silently degrade back to scan."""
    from repro.core.recurrence import (_resolve, linear_recurrence,
                                       linear_recurrence2)
    from repro.kernels import recurrence_hbm_traffic_bytes
    assert _resolve("auto", jnp.float32) == "pallas", "auto policy regressed"
    n, m = 1024, 512
    rng = np.random.default_rng(0)
    p = jnp.asarray(rng.uniform(-0.9, 0.9, (n, m)).astype(np.float32))
    s = jnp.asarray(rng.uniform(-0.6, 0.6, (n, m)).astype(np.float32))
    t2 = jnp.asarray(rng.uniform(-0.3, 0.3, (n, m)).astype(np.float32))
    q = _rhs(n, m)
    for method in ("scan", "pallas"):
        t = _timeit(jax.jit(
            lambda d: linear_recurrence(p, d, method=method)), q, reps=2)
        _record(f"recurrence_order1_{method}_N{n}_M{m}", t, backend=method,
                n=n, m=m, traffic={"order": 1} if method == "pallas"
                else None,
                derived=f"hbm_bytes={recurrence_hbm_traffic_bytes(1, n, m)}")
        t = _timeit(jax.jit(
            lambda d: linear_recurrence2(s, t2, d, method=method)), q, reps=2)
        _record(f"recurrence_order2_{method}_N{n}_M{m}", t, backend=method,
                n=n, m=m, traffic={"order": 2} if method == "pallas"
                else None,
                derived=f"hbm_bytes={recurrence_hbm_traffic_bytes(2, n, m)}")
    # forced streamed kernel: same arithmetic, chunked sweep residency
    t = _timeit(jax.jit(
        lambda d: linear_recurrence(p, d, method="pallas", block_n=256)),
        q, reps=2)
    _record(f"recurrence_order1_pallas_streamed_N{n}_M{m}", t,
            backend="pallas", n=n, m=m,
            traffic={"order": 1, "streamed": True},
            derived="block_n=256")


# ---------------------------------------------------------------------------
# Dry-run roofline summary (reads artifacts if present)
# ---------------------------------------------------------------------------

def bench_dryrun_summary():
    import glob
    import json
    import os
    rows = []
    for p in sorted(glob.glob("artifacts/dryrun/*.json")):
        if "__pod2" in p or "__" not in os.path.basename(p):
            continue
        d = json.load(open(p))
        if d.get("status") != "ok":
            continue
        rl = d["roofline"]
        rows.append((d["arch"], d["shape"], rl["dominant"],
                     rl["bound_s"], d.get("roofline_fraction", 0)))
    if not rows:
        print("dryrun_summary,0,no_artifacts_run_python_-m_repro.launch.dryrun_--all")
        return
    for arch, shape, dom, bound, frac in rows:
        print(f"dryrun_{arch}_{shape},{bound*1e6:.0f},"
              f"dominant={dom}_rooflinefrac={frac:.3f}")


TABLES = {
    "fig2": bench_fig2_tridiag,
    "fig3": bench_fig3_penta,
    "fig4": bench_fig4_uniform,
    # bench_backends_streamed / bench_batch_streamed / bench_sharded chain
    # off "backends", and bench_grad_solve_streamed off "grad" — not
    # registered separately, so selecting several tables never records
    # duplicate rows.
    "backends": bench_backends,
    "grad": bench_grad_solve,
    "recurrence": bench_recurrence,
    "memory": bench_memory_table,
    "traffic": bench_kernel_traffic,
    "pallas": bench_pallas_kernels,
    "dryrun": bench_dryrun_summary,
}


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    argv = sys.argv[1:]
    write_json = "--json" in argv
    which = [a for a in argv if not a.startswith("--")]
    if not which:
        # --json alone: the solver tables that carry (backend, n, m) rows.
        which = (["backends", "grad", "recurrence"] if write_json
                 else list(TABLES))
    print("name,us_per_call,derived")
    for k in which:
        TABLES[k]()
    if write_json:
        with open(JSON_PATH, "w") as fh:
            json.dump(_ROWS, fh, indent=2)
        print(f"# wrote {len(_ROWS)} rows to {JSON_PATH}", file=sys.stderr)


if __name__ == "__main__":
    main()
