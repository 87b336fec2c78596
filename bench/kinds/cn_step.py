"""Crank-Nicolson time steps of an (N, M) field through ``repro.pde``.

One timed call makes ``steps_per_call`` steps of the configuration's
stepper (``stepper``, a class of ``repro.pde``, with ``stepper_options``)
on the traffic's ``backend``; the next call starts from the result.  The
stepper factorizes once, as a stepping user does, and the field is donated
to the call.

Checked: ``max_rel_err``, the field after every step the run made, on
``check_cols`` columns drawn from the seed, against float64 host steps of
the operator the configuration states (``stencil``, ``dx_power``, ``dt``).
The control is the same step in bfloat16.
"""

from __future__ import annotations

import jax

import cells
import control as ctl
import counts
import reference as ref

FAULTS = cells.ARRAY_FAULTS


def build(config: dict, traffic: dict, seed: int, devices: list, *,
          control: bool = False) -> cells.Cell:
    if config["dtype"] != "float32":
        raise ValueError(f"unsupported dtype {config['dtype']!r}")
    n, m, dt = config["n"], config["m"], config["dt"]
    stencil, dx_power = config["stencil"], config["dx_power"]
    steps = traffic["steps_per_call"]
    _, sharding = cells.column_mesh(devices)
    field0 = cells.normal(jax.random.PRNGKey(ref.jax_seed(seed)), (n, m),
                          sharding)
    observe = cells.columns_reader(
        ref.check_columns(seed, m, traffic["check_cols"]))
    start = observe(field0)

    if control:
        step = ctl.cn_step(stencil, dx_power, n, dt)
    else:
        import repro.pde
        stepper = getattr(repro.pde, config["stepper"])(
            n=n, dt=dt, backend=traffic["backend"],
            **config.get("stepper_options", {}))
        step = stepper.step_fn()[1]

    def advance(field):
        for _ in range(steps):
            field = step(field)
        return field

    def check(out, calls: int) -> dict:
        want = ref.ref_cn_steps(stencil, dx_power, n, dt, start,
                                calls * steps)
        return {"max_rel_err": ref.rel_err(observe(out), want)}

    call = jax.jit(advance, donate_argnums=0)
    words = steps * counts.cn_step_words(n, m)
    return cells.Cell(call=call, state=field0, chained=True,
                      counts={"step_bytes": 4 * words,
                              "kernel_bytes": 4 * words},
                      check=check, memory=cells.memory_of(call, field0))
