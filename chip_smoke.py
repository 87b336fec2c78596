"""Drive the solver stack's main path once on a TPU and check the results.

    python chip_smoke.py              # one chip: phases 1-4
    python chip_smoke.py --chips 4    # four chips: the sharded backend only

Every phase goes through the entry points a user calls (``factorize`` /
``solve`` / ``jax.grad``, the PDE steppers, the model's train step), on
``backend="pallas"`` — never ``"auto"``, which may fall back to the
reference sweeps.  Each solver phase compiles its solve ahead of time,
checks that the compiled program holds the expected number of Pallas
kernels (``tpu_custom_call``), times one steady call, and compares a
subset of columns with a float64 NumPy/SciPy solve on the host.

Phases on one chip:
  1. the paper's storage-table size, N=1024, M=65536 fp32: tridiag and
     penta x constant/uniform/batch x Dirichlet/periodic (periodic x batch
     has no kernel), on the resident kernels; plus ``jax.grad`` through
     one constant solve (the transposed kernels);
  2. large N, N=16384, M=8192: constant tridiag and penta (fused streamed
     kernels), batch tridiag (two-call streamed pair; and the fused kernel
     at N=8192, the largest N whose batch full-N scratch fits the VMEM
     budget), and one ``storage_dtype="bf16"`` solve;
  3. time stepping, 10 scan steps at N=1024, M=65536: ``DiffusionCN``
     (``pallas`` and ``fused``) and ``HyperdiffusionCN``;
  4. mamba2-130m at full width (24 layers, d_model 768): three train steps
     at batch 8 x seq 2048, and one SSD layer's Pallas recurrence against
     its ``method="scan"`` output.

With ``--chips 4`` the script runs the ``sharded`` backend over a 4-chip
mesh (constant tridiag and batch penta at N=1024, M=4x65536 and N=16384,
M=4x8192) and compares it with the single-chip ``pallas`` solve of the
same columns.

Every phase prints one line.  The last line of standard output is one JSON
object naming the device; it is printed only when every phase passed.
Without a TPU the script exits with status 2 and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: Columns of each solve compared with the float64 host reference.
N_CHECK_COLS = 64
FP32_TOL = 1e-4
BF16_TOL = 1e-2
#: The sharded backend runs the single-chip kernels on each chip's
#: columns: its result may differ from single-chip ``pallas`` by no more
#: than last-ulp effects of a differently fused factorisation.
SHARDED_TOL = 1e-6


class PhaseError(RuntimeError):
    """A phase produced a wrong result or the wrong compiled program."""


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise PhaseError(msg)


# ---------------------------------------------------------------------------
# Host references (float64, independent of the code under test)
# ---------------------------------------------------------------------------

def dense_matrix(diags, periodic: bool) -> np.ndarray:
    """Dense (N, N) float64 matrix with ``diags[k]`` on offset k - half;
    periodic systems wrap the band around the corners."""
    half = len(diags) // 2
    n = len(diags[0])
    a = np.zeros((n, n))
    rows = np.arange(n)
    for k, d in enumerate(diags):
        cols = rows + k - half
        keep = slice(None) if periodic else (cols >= 0) & (cols < n)
        a[rows[keep], cols[keep] % n] = np.asarray(d, np.float64)[keep]
    return a


def ref_solve(diags, periodic: bool, rhs: np.ndarray) -> np.ndarray:
    """float64 solve of the banded system for the columns of ``rhs``."""
    from scipy.linalg import solve_banded
    rhs = np.asarray(rhs, np.float64)
    if periodic:
        return np.linalg.solve(dense_matrix(diags, True), rhs)
    half = len(diags) // 2
    n = len(diags[0])
    ab = np.zeros((2 * half + 1, n))
    for k, d in enumerate(diags):
        off = k - half                      # A[i, i + off] = d[i]
        d = np.asarray(d, np.float64)
        if off >= 0:
            ab[half - off, off:] = d[:n - off]
        else:
            ab[half - off, :n + off] = d[-off:]
    return solve_banded((half, half), ab, rhs)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


# ---------------------------------------------------------------------------
# Compile, count kernels, time one steady call
# ---------------------------------------------------------------------------

def pallas_calls(compiled) -> int:
    """Pallas kernels in a compiled TPU program."""
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def compile_and_run(fn, *args, **jit_kw):
    """(out, compile_s, steady_s, n_pallas_calls) for ``jit(fn)(*args)``."""
    import jax
    t0 = time.perf_counter()
    compiled = jax.jit(fn, **jit_kw).lower(*args).compile()
    compile_s = time.perf_counter() - t0
    jax.block_until_ready(compiled(*args))          # first call: warm-up
    t0 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, compile_s, time.perf_counter() - t0, pallas_calls(compiled)


def report(phase: str, **fields) -> None:
    parts = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {parts}", flush=True)


# ---------------------------------------------------------------------------
# Systems made from a seed
# ---------------------------------------------------------------------------

def make_diags(rng, bandwidth: int, n: int, mode: str) -> list:
    """Diagonally dominant diagonals, ordered sub-most first: random
    vectors, or scalars repeated along N for the uniform mode."""
    k = bandwidth - 1
    size = 1 if mode == "uniform" else n
    off = [rng.uniform(-1.0, 1.0, size) for _ in range(k)]
    main = sum(np.abs(o) for o in off) + 1.0 + rng.uniform(0.0, 1.0, size)
    diags = off[:k // 2] + [main] + off[k // 2:]
    return [np.broadcast_to(d, (n,)).astype(np.float32) for d in diags]


def make_system(diags, periodic: bool, mode: str, m: int):
    from repro.solver import BandedSystem
    ctor = BandedSystem.tridiag if len(diags) == 3 else BandedSystem.penta
    return ctor(*diags, periodic=periodic, mode=mode,
                batch=m if mode == "batch" else None)


def expected_calls(fact, *, transposed: bool = False) -> int:
    """Pallas kernels one solve of ``fact`` should compile to, from the
    registered spec its tuned options select."""
    from repro.kernels.engine import find_spec
    meta = fact.meta
    spec = find_spec(meta.bandwidth, meta.mode,
                     streamed=meta.opt("block_n") is not None,
                     fused=bool(meta.opt("fused")),
                     transposed=transposed and meta.mode != "batch")
    return spec.num_pallas_calls


def solve_phase(phase: str, key, diags, periodic: bool, mode: str, m: int,
                *, streamed: bool, fused: bool = False, storage_dtype=None,
                tol: float = FP32_TOL) -> None:
    """Factorize, solve one (N, M) batch on ``pallas``, and check the
    tuner's kernel choice (``streamed``/``fused``), the compiled kernel
    count and the error on a column subset."""
    import jax
    import jax.numpy as jnp
    from repro.solver import factorize, solve
    n = len(diags[0])
    system = make_system(diags, periodic, mode, m)
    fact = factorize(system, backend="pallas", storage_dtype=storage_dtype)
    rhs = jax.random.normal(key, (n, m), jnp.float32)
    x, compile_s, solve_s, calls = compile_and_run(solve, fact, rhs)
    cols = np.linspace(0, m - 1, N_CHECK_COLS).astype(int)
    ref = ref_solve(diags, periodic, np.asarray(rhs[:, cols]))
    err = rel_err(x[:, cols], ref)
    want_calls = expected_calls(fact)
    opts = {k: fact.meta.opt(k) for k in ("block_m", "block_n", "fused")}
    report(phase, system=system.describe(), M=m,
           storage=storage_dtype or "fp32", **opts,
           compile_s=f"{compile_s:.3f}", solve_s=f"{solve_s:.6f}",
           max_rel_err=f"{err:.3e}", pallas_calls=f"{calls}/{want_calls}")
    check(calls == want_calls,
          f"{phase}: {calls} Pallas kernels compiled, expected {want_calls}")
    check(err <= tol, f"{phase}: max relative error {err:.3e} > {tol}")
    check((opts["block_n"] is not None) == streamed
          and bool(opts["fused"]) == fused,
          f"{phase}: tuner chose {opts}, expected streamed={streamed} "
          f"fused={fused}")


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_storage_table(rng, key, n: int = 1024, m: int = 65536) -> None:
    """Phase 1: every storage mode x boundary at the paper's table size,
    on the resident kernels, plus grad through a constant solve."""
    import jax
    import jax.numpy as jnp
    from repro.solver import factorize, solve
    for bandwidth in (3, 5):
        for mode in ("constant", "uniform", "batch"):
            for periodic in (False, True):
                if periodic and mode == "batch":
                    continue        # no kernel for periodic x batch
                key, sub = jax.random.split(key)
                solve_phase("1", sub, make_diags(rng, bandwidth, n, mode),
                            periodic, mode, m, streamed=False)

    # grad through one constant solve: the transposed resident kernels
    diags = make_diags(rng, 3, n, "constant")
    fact = factorize(make_system(diags, False, "constant", m),
                     backend="pallas")
    k1, k2 = jax.random.split(key)
    rhs = jax.random.normal(k1, (n, m), jnp.float32)
    w = jax.random.normal(k2, (n, m), jnp.float32)

    def loss(f, r, weights):
        return jnp.sum(solve(f, r) * weights)

    (val, g), compile_s, solve_s, calls = compile_and_run(
        jax.value_and_grad(loss, argnums=1), fact, rhs, w)
    cols = np.linspace(0, m - 1, N_CHECK_COLS).astype(int)
    a = dense_matrix(diags, False)
    err = rel_err(g[:, cols], np.linalg.solve(a.T, np.asarray(w[:, cols],
                                                             np.float64)))
    want_calls = expected_calls(fact) + expected_calls(fact, transposed=True)
    report("1", system="grad tridiag/dirichlet/constant", N=n, M=m,
           compile_s=f"{compile_s:.3f}", solve_s=f"{solve_s:.6f}",
           max_rel_err=f"{err:.3e}", pallas_calls=f"{calls}/{want_calls}")
    check(calls == want_calls,
          f"grad: {calls} Pallas kernels compiled, expected {want_calls}")
    check(bool(np.isfinite(float(val))), "grad: loss is not finite")
    check(err <= FP32_TOL, f"grad: max relative error {err:.3e}")


def phase_large_n(rng, key, n: int = 16384, m: int = 8192,
                  n_batch_fused: int = 8192) -> None:
    """Phase 2: the streamed kernels past the resident VMEM wall."""
    import jax
    keys = jax.random.split(key, 5)
    solve_phase("2", keys[0], make_diags(rng, 3, n, "constant"), False,
                "constant", m, streamed=True, fused=True)
    solve_phase("2", keys[1], make_diags(rng, 5, n, "constant"), False,
                "constant", m, streamed=True, fused=True)
    solve_phase("2", keys[2], make_diags(rng, 3, n, "batch"), False,
                "batch", m, streamed=True, fused=False)
    solve_phase("2", keys[3], make_diags(rng, 3, n_batch_fused, "batch"),
                False, "batch", m, streamed=True, fused=True)
    solve_phase("2", keys[4], make_diags(rng, 3, n, "constant"), False,
                "constant", m, streamed=True, fused=True,
                storage_dtype="bf16", tol=BF16_TOL)


def phase_time_stepping(key, n: int = 1024, m: int = 65536,
                        steps: int = 10) -> None:
    """Phase 3: CN time stepping, factor once, scan the solve."""
    import jax
    import jax.numpy as jnp
    from scipy.linalg import lu_factor, lu_solve
    from repro.pde import DiffusionCN, HyperdiffusionCN
    cases = [
        (DiffusionCN(n=n, dt=1e-5, backend="pallas"), 1),
        (DiffusionCN(n=n, dt=1e-5, backend="fused"), 1),
        (HyperdiffusionCN(n=n, dt=1e-12, backend="pallas"), 1),
    ]
    field0 = jax.random.normal(key, (n, m), jnp.float32)
    cols = np.linspace(0, m - 1, N_CHECK_COLS).astype(int)
    for stepper, want_calls in cases:
        s = stepper.sigma
        if isinstance(stepper, DiffusionCN):
            diags, weights = [-s, 1 + 2 * s, -s], [s, 1 - 2 * s, s]
        else:
            diags = [s, -4 * s, 1 + 6 * s, -4 * s, s]
            weights = [-s, 4 * s, 1 - 6 * s, 4 * s, -s]
        out, compile_s, run_s, calls = compile_and_run(
            lambda f, st=stepper: st.run(f, steps), field0)
        lu = lu_factor(dense_matrix([np.full(n, d) for d in diags], True))
        ref = np.asarray(field0[:, cols], np.float64)
        r = len(weights) // 2
        for _ in range(steps):
            rhs = sum(w * np.roll(ref, -(k - r), axis=0)
                      for k, w in enumerate(weights))
            ref = lu_solve(lu, rhs)
        err = rel_err(out[:, cols], ref)
        report("3", stepper=type(stepper).__name__, backend=stepper.backend,
               N=n, M=m, steps=steps, sigma=f"{s:.3f}",
               compile_s=f"{compile_s:.3f}", run_s=f"{run_s:.6f}",
               max_rel_err=f"{err:.3e}", pallas_calls=f"{calls}/{want_calls}")
        check(calls == want_calls,
              f"{stepper}: {calls} Pallas kernels compiled, expected "
              f"{want_calls}")
        check(err <= FP32_TOL, f"{stepper}: max relative error {err:.3e}")


def phase_model(seed: int, batch: int = 8, seq: int = 2048,
                steps: int = 3) -> None:
    """Phase 4: mamba2-130m train steps at full width; one SSD layer's
    Pallas recurrence against the scan."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.data import SyntheticLM
    from repro.launch.mesh import make_local_mesh
    from repro.models import build_model
    from repro.models.ssm import ssd_chunked
    from repro.sharding import LogicalRules, ShardingCtx
    from repro.train import AdamW, make_train_step, warmup_cosine

    cfg = get_config("mamba2-130m")
    model = build_model(cfg)
    sctx = ShardingCtx(mesh=make_local_mesh(), rules=LogicalRules.default())
    opt = AdamW(lr=warmup_cosine(3e-4, 1, steps), opt_dtype=jnp.float32)
    params = model.init(jax.random.PRNGKey(seed))
    opt_state = opt.init(params)
    data = SyntheticLM(vocab=cfg.vocab, seq_len=seq, global_batch=batch,
                       seed=seed)
    t0 = time.perf_counter()
    step_fn = jax.jit(make_train_step(model, sctx, opt),
                      donate_argnums=(0, 1)).lower(
        params, opt_state, data.batch_at(0), jnp.int32(0)).compile()
    compile_s = time.perf_counter() - t0
    calls = pallas_calls(step_fn)
    losses, times = [], []
    for step in range(steps):
        b = data.batch_at(step)
        t0 = time.perf_counter()
        params, opt_state, metrics = step_fn(params, opt_state, b,
                                             jnp.int32(step))
        losses.append(float(metrics["loss"]))      # waits for the step
        times.append(time.perf_counter() - t0)
    report("4", model=cfg.name, layers=cfg.n_layers, d_model=cfg.d_model,
           batch=batch, seq=seq, compile_s=f"{compile_s:.3f}",
           step_s=f"{times[-1]:.6f}",
           losses="/".join(f"{x:.4f}" for x in losses),
           pallas_calls=calls)
    check(all(np.isfinite(losses)), f"train losses not finite: {losses}")
    # forward recurrence and its adjoint, at least
    check(calls >= 2, f"train step compiled {calls} Pallas kernels")

    # one SSD layer at the model's width: Pallas recurrence vs the scan
    h, p, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    k = jax.random.split(jax.random.PRNGKey(seed + 1), 5)
    xh = jax.random.normal(k[0], (batch, seq, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(k[1], (batch, seq, h)))
    a_log = jax.random.normal(k[2], (h,)) * 0.5
    bm = jax.random.normal(k[3], (batch, seq, n), jnp.float32)
    cm = jax.random.normal(k[4], (batch, seq, n), jnp.float32)
    (y, state), compile_s, run_s, calls = compile_and_run(
        lambda *a: ssd_chunked(*a, cfg.ssm_chunk, method="pallas"),
        xh, dt, a_log, bm, cm)
    y_ref, state_ref = jax.jit(
        lambda *a: ssd_chunked(*a, cfg.ssm_chunk, method="scan"))(
            xh, dt, a_log, bm, cm)
    err = max(rel_err(y, y_ref), rel_err(state, state_ref))
    report("4", layer="ssd", batch=batch, seq=seq, heads=h, head_dim=p,
           state=n, chunk=cfg.ssm_chunk, compile_s=f"{compile_s:.3f}",
           run_s=f"{run_s:.6f}", max_rel_err_vs_scan=f"{err:.3e}",
           pallas_calls=f"{calls}/1")
    check(calls == 1, f"SSD layer compiled {calls} Pallas kernels")
    check(err <= FP32_TOL, f"SSD Pallas vs scan: {err:.3e}")


#: (bandwidth, mode, N, M per chip) of the ``--chips 4`` phase.
SHARDED_CASES = ((3, "constant", 1024, 65536), (5, "batch", 1024, 65536),
                 (3, "constant", 16384, 8192), (5, "batch", 16384, 8192))


def phase_sharded(rng, key, cases=SHARDED_CASES, n_chips: int = 4) -> None:
    """``--chips 4``: the sharded backend against single-chip pallas."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.solver import factorize, solve
    devices = jax.devices()[:n_chips]
    mesh = Mesh(np.array(devices), ("batch",))
    cols_sharding = NamedSharding(mesh, P(None, "batch"))
    replicated = NamedSharding(mesh, P())
    for bandwidth, mode, n, m_chip in cases:
        m = n_chips * m_chip
        diags = make_diags(rng, bandwidth, n, mode)
        key, sub = jax.random.split(key)
        rhs = jax.jit(lambda k: jax.random.normal(k, (n, m), jnp.float32),
                      out_shardings=cols_sharding)(sub)
        system = make_system(diags, False, mode, m)

        def build(system=system):
            return factorize(system, backend="sharded", mesh=mesh,
                             kernels="pallas")

        # per-system LHS copies live with their systems: built under jit,
        # each chip materialises only its own columns
        out_shardings = jax.tree_util.tree_map(
            lambda v: cols_sharding if v.ndim == 2 else replicated,
            jax.eval_shape(build))
        fact = jax.jit(build, out_shardings=out_shardings)()
        x, compile_s, solve_s, calls = compile_and_run(solve, fact, rhs)
        want_calls = expected_calls(fact)
        # the single-chip pallas solve of each chip's columns
        one = factorize(make_system(diags, False, mode, m_chip),
                        backend="pallas")
        worst = 0.0
        for c in range(n_chips):
            part = slice(c * m_chip, (c + 1) * m_chip)
            x1 = jax.jit(solve)(one, jax.device_put(rhs[:, part],
                                                    devices[0]))
            xc = jax.device_put(x[:, part], devices[0])
            worst = max(worst, float(jnp.max(jnp.abs(xc - x1))
                                     / jnp.max(jnp.abs(x1))))
            del x1, xc
        cols = np.linspace(0, m - 1, N_CHECK_COLS).astype(int)
        err = rel_err(x[:, cols], ref_solve(diags, False,
                                            np.asarray(rhs[:, cols])))
        report("sharded", system=f"{'tridiag' if bandwidth == 3 else 'penta'}"
               f"/dirichlet/{mode}", N=n, M=m, chips=n_chips,
               block_m=fact.meta.opt("block_m"),
               block_n=fact.meta.opt("block_n"),
               fused=fact.meta.opt("fused"), compile_s=f"{compile_s:.3f}",
               solve_s=f"{solve_s:.6f}", max_rel_err=f"{err:.3e}",
               max_rel_diff_vs_single_chip=f"{worst:.3e}",
               pallas_calls=f"{calls}/{want_calls}")
        check(fact.meta.opt("kernels") == "pallas",
              "sharded backend did not run the engine kernels")
        check(calls == want_calls,
              f"sharded: {calls} Pallas kernels compiled, expected "
              f"{want_calls}")
        check(err <= FP32_TOL, f"sharded: max relative error {err:.3e}")
        check(worst <= SHARDED_TOL, f"sharded differs from single-chip "
                                    f"pallas by {worst:.3e} (relative)")
        del x, rhs, fact, one


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded backend over four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2

    from repro.launch.compile_cache import use_compile_cache
    report("setup", device_kind=devices[0].device_kind,
           devices=len(devices), jax=jax.__version__,
           compile_cache=use_compile_cache())
    rng = np.random.default_rng(args.seed)
    key = jax.random.PRNGKey(args.seed)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    if args.chips == 4:
        phase_sharded(rng, k4)
    else:
        phase_storage_table(rng, k1)
        phase_large_n(rng, k2)
        phase_time_stepping(k3)
        phase_model(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
