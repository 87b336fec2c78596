"""The comparison that decides ``correct`` fails the bfloat16 control and
every fault the cells can have, driven through the rest of a run on the
CPU at a tiny size.

Faults, each planted in the timed call's output where it is produced:
the state returned unchanged, half of the batch left out, and one answer
of every system altered.  The cells exchange nothing between chips (the
sharded solve splits independent systems), so no exchange can be left
out."""

import jax.numpy as jnp
import numpy as np
import pytest

import control
import reference as ref
import run
from test_rehearsal import CELLS, ENTRIES, SEED, TINY


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    result, checks = run.execute(workload, SEED, 0.3, False,
                                 require_tpu=False, sizes=TINY[workload],
                                 control=True, entry=ENTRIES[workload])
    assert result["correct"] is False, checks


# The CN cells donate their field to the call, so a fault that still reads
# its input runs the call on a copy.

def unchanged(call):
    def broken(state):
        return state
    return broken


def half_batch(call):
    def broken(state):
        out = call(jnp.copy(state))
        half = out.shape[1] // 2
        return out.at[:, half:].set(state[:, half:])
    return broken


def altered_answer(call):
    def broken(state):
        out = call(jnp.copy(state))
        return out.at[out.shape[0] // 2].add(0.1)
    return broken


@pytest.mark.parametrize("fault", [unchanged, half_batch, altered_answer])
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(workload, fault):
    result, checks = run.execute(workload, SEED, 0.2, False,
                                 require_tpu=False, sizes=TINY[workload],
                                 wrap_call=fault, entry=ENTRIES[workload])
    assert result["correct"] is False, (fault.__name__, checks)


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
