"""The comparison that decides ``correct`` fails the bfloat16 control and
every fault the cells can have, driven through the rest of a run on the
CPU at a tiny size.

Each kind (``kinds/<kind>.py``) declares the faults its cells can have,
``FAULTS``, and each is planted in the timed call's output where it is
produced; for the (N, M) arrays of ``cn_step`` and ``solve`` these are
``cells.ARRAY_FAULTS``: the state returned unchanged, half of the batch
left out, and one answer of every system altered."""

import jax.numpy as jnp
import numpy as np
import pytest

import cells
import control
import reference as ref
import run
from test_rehearsal import CELLS, ENTRIES, SEED, TINY, traffic


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    result, checks = run.execute(workload, SEED, 0.3, False,
                                 require_tpu=False, sizes=TINY[workload],
                                 control=True, entry=ENTRIES[workload])
    assert result["correct"] is False, checks


FAULT_CASES = [pytest.param(workload, name, id=f"{workload}-{name}")
               for workload in CELLS for name in cells.faults(
                   traffic(workload))]


@pytest.mark.parametrize("workload,fault", FAULT_CASES)
def test_fault_is_not_correct(workload, fault):
    wrap = cells.faults(traffic(workload))[fault]
    result, checks = run.execute(workload, SEED, 0.2, False,
                                 require_tpu=False, sizes=TINY[workload],
                                 wrap_call=wrap, entry=ENTRIES[workload])
    assert result["correct"] is False, (fault, checks)


# The controls' algorithms, run in float32, agree with the float64
# references; in bfloat16 they are what the comparison must fail.

@pytest.mark.parametrize("periodic", [False, True])
@pytest.mark.parametrize("bandwidth", [3, 5])
def test_control_solve_is_the_reference_in_lower_precision(bandwidth,
                                                           periodic):
    n, m = 64, 8
    diags = ref.make_diags(ref.host_rng(SEED, 0), bandwidth, n, "batch")
    rhs = np.random.default_rng(0).standard_normal((n, m))
    want = ref.ref_solve(diags, periodic, rhs)
    f32 = control.banded_solve(diags, periodic, jnp.float32)(jnp.asarray(rhs))
    assert ref.rel_err(f32, want) < 1e-5
    bf16 = control.banded_solve(diags, periodic)(jnp.asarray(rhs))
    assert 1e-4 < ref.rel_err(bf16, want) < 1e-1


@pytest.mark.parametrize("stencil,dx_power,dt", [([-1, 2, -1], 2, 1e-3),
                                                 ([1, -4, 6, -4, 1], 4, 1e-7)])
def test_control_cn_step_is_the_reference_in_lower_precision(stencil,
                                                             dx_power, dt):
    n, m = 64, 8
    field = np.random.default_rng(1).standard_normal((n, m))
    want = ref.ref_cn_steps(stencil, dx_power, n, dt, field, 1)
    step = control.cn_step(stencil, dx_power, n, dt, jnp.float32)
    assert ref.rel_err(step(jnp.asarray(field, jnp.float32)), want) < 1e-5
    bf16 = control.cn_step(stencil, dx_power, n, dt)(jnp.asarray(field))
    assert ref.rel_err(bf16, want) > 1e-4
