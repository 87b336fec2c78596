"""One ``repro.solver.solve`` of a fixed (N, M) right-hand side.

The configuration gives N, M and the bandwidth; the traffic gives the
storage ``mode`` (``constant``/``uniform``: one LHS shared by every
system; ``batch``: one stored copy per system), the ``boundary``, the
``backend`` and any further ``options`` of ``factorize``.  Diagonally
dominant diagonals and the right-hand side are drawn from the seed and
made once at set-up; every call solves the same right-hand side (chained
solves would decay toward zero).  On the ``sharded`` backend the systems
are split by columns over the cell's chips.

Checked: ``max_rel_err``, the last call's solution, on ``check_cols``
columns drawn from the seed (one per stratum of M, so every chip's share
is covered), against a float64 host solve.  The control is the same solve
in bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import cells
import control as ctl
import counts
import reference as ref

#: The sharded solve splits independent systems by columns and exchanges
#: nothing between chips, so no exchange can be left out.
FAULTS = cells.ARRAY_FAULTS


def build(config: dict, traffic: dict, seed: int, devices: list, *,
          control: bool = False) -> cells.Cell:
    if config["dtype"] != "float32":
        raise ValueError(f"unsupported dtype {config['dtype']!r}")
    n, m, bandwidth = config["n"], config["m"], config["bandwidth"]
    mode = traffic["mode"]
    periodic = traffic["boundary"] == "periodic"
    mesh, sharding = cells.column_mesh(devices)
    diags = ref.make_diags(ref.host_rng(seed, 0), bandwidth, n, mode)
    rhs = cells.normal(jax.random.PRNGKey(ref.jax_seed(seed)), (n, m),
                       sharding)
    observe = cells.columns_reader(
        ref.check_columns(seed, m, traffic["check_cols"]))
    want = ref.ref_solve(diags, periodic, observe(rhs))

    if control:
        call = jax.jit(ctl.banded_solve(diags, periodic))
        memory = cells.memory_of(call, rhs)
    else:
        from repro.solver import solve
        fact = _factorize(diags, periodic, mode, m, traffic, mesh, sharding)
        solve_jit = jax.jit(solve)

        def call(r):
            return solve_jit(fact, r)

        memory = cells.memory_of(solve_jit, fact, rhs)

    def check(out, calls: int) -> dict:
        return {"max_rel_err": ref.rel_err(observe(out), want)}

    words = counts.solve_words(bandwidth, mode, n, m)
    return cells.Cell(call=call, state=rhs, chained=False,
                      counts={"step_bytes": 4 * words,
                              "kernel_bytes": 4 * words},
                      check=check, memory=memory)


def _factorize(diags, periodic: bool, mode: str, m: int, traffic: dict, mesh,
               cols_sharding):
    """The factorization, built on the device in one jitted call; per-system
    LHS copies are laid out with their columns."""
    from repro.solver import BandedSystem, factorize

    ctor = BandedSystem.tridiag if len(diags) == 3 else BandedSystem.penta
    diags = [jnp.asarray(d) for d in diags]
    backend = traffic["backend"]
    options = dict(traffic.get("options", {}))
    if backend == "sharded":
        options["mesh"] = mesh

    def build_factor():
        system = ctor(*diags, periodic=periodic, mode=mode,
                      batch=m if mode == "batch" else None)
        return factorize(system, backend=backend, **options)

    replicated = NamedSharding(mesh, P())
    out_shardings = jax.tree_util.tree_map(
        lambda v: cols_sharding if v.ndim == 2 else replicated,
        jax.eval_shape(build_factor))
    return jax.jit(build_factor, out_shardings=out_shardings)()
