"""Boundary behaviour of the VMEM budget checks and padding helpers
(``repro.kernels.common``) — exactly-at-budget must pass, one byte over
must raise, and aligned padding must be an identity (no copy)."""

from types import SimpleNamespace

import numpy as np
import pytest
import jax.numpy as jnp

from repro.kernels.common import (SMEM_BUDGET_BYTES, VMEM_BUDGET_BYTES,
                                  check_fits, pad_lanes, pad_sweep,
                                  pad_to_multiple, shard_lanes,
                                  smem_working_set, vmem_working_set)


# ---------------------------------------------------------------------------
# Budget boundaries (itemsize=1 makes the working set exactly countable)
# ---------------------------------------------------------------------------

def _spec(blocks, lhs_rows=0, carry=0, scratch=0, streamed=False):
    """A duck-typed spec with the given counts (check_fits reads only
    vmem_counts / sweep_scratch / name / fused)."""
    return SimpleNamespace(vmem_counts=lambda: (blocks, lhs_rows, carry),
                           sweep_scratch=lambda: scratch, name="probe",
                           fused=False, streamed=streamed)


def test_resident_budget_exactly_at():
    # ws = 2 * 1 * (B / 2) * 1 == VMEM_BUDGET_BYTES: blocks count twice
    n = VMEM_BUDGET_BYTES // 2
    assert vmem_working_set(n, 1, 1, itemsize=1) == VMEM_BUDGET_BYTES
    check_fits(_spec(1), n, 1, itemsize=1)


def test_resident_budget_one_byte_over():
    n = VMEM_BUDGET_BYTES // 2
    with pytest.raises(ValueError, match="exceeds VMEM budget"):
        check_fits(_spec(1, carry=1), n, 1, itemsize=1)


def test_streamed_budget_exactly_at():
    # block_n = block_m = 1 -> ws = 2 * n_blocks + n_carry
    n_blocks = (VMEM_BUDGET_BYTES - 4) // 2
    assert vmem_working_set(64, 1, n_blocks, block_n=1, n_carry=4,
                            itemsize=1) == VMEM_BUDGET_BYTES
    check_fits(_spec(n_blocks, carry=4, streamed=True), 64, 1, 1,
               itemsize=1)


def test_streamed_budget_one_byte_over():
    n_blocks = (VMEM_BUDGET_BYTES - 4) // 2
    with pytest.raises(ValueError, match="exceeds VMEM"):
        check_fits(_spec(n_blocks, carry=5, streamed=True), 64, 1, 1,
                   itemsize=1)


def test_budget_scales_with_itemsize():
    # the float64 working set is twice the float32 one — the checks must
    # use the caller's itemsize, not assume 4 bytes
    assert vmem_working_set(8, 16, 2, n_carry=3, itemsize=8) == \
        2 * vmem_working_set(8, 16, 2, n_carry=3, itemsize=4)


def test_smem_budget_bounds_the_shared_lhs():
    # rows pad to the 8-row tile and count twice: a 5-row LHS at N=16384
    # is exactly the 1 MiB budget, one more column is over
    assert smem_working_set(5, 16384) == SMEM_BUDGET_BYTES
    check_fits(_spec(0, lhs_rows=5), 16384, 128, itemsize=4)
    with pytest.raises(ValueError, match="exceeds SMEM"):
        check_fits(_spec(0, lhs_rows=5), 16385, 128, itemsize=4)


# ---------------------------------------------------------------------------
# Padding identities
# ---------------------------------------------------------------------------

def test_pad_to_multiple_aligned_is_identity():
    x = jnp.ones((6, 8))
    padded, size = pad_to_multiple(x, 4, axis=1)
    assert padded is x and size == 8


def test_pad_sweep_aligned_is_identity():
    x = jnp.ones((16, 5))
    padded, size = pad_sweep(x, 8, axis=0)
    assert padded is x and size == 16


def test_pad_lanes_aligned_is_identity():
    x = jnp.ones((5, 64))
    padded, m = pad_lanes(x, 64)
    assert padded is x and m == 64


def test_pad_lanes_identity_value():
    x = jnp.ones((2, 3))
    padded, m = pad_lanes(x, 8, identity=True)
    assert m == 3 and padded.shape == (2, 8)
    assert np.array_equal(np.asarray(padded[:, 3:]), np.ones((2, 5)))
    zero_padded, _ = pad_lanes(x, 8)
    assert np.array_equal(np.asarray(zero_padded[:, 3:]), np.zeros((2, 5)))


def test_pad_sweep_rounds_up():
    x = jnp.ones((9, 2))
    padded, size = pad_sweep(x, 8, axis=0)
    assert padded.shape == (16, 2) and size == 9


# ---------------------------------------------------------------------------
# shard_lanes edges
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,n_shards,want", [
    (0, 4, 0),        # empty batch shards to empty slices
    (7, 1, 7),        # single device: no padding at all
    (8, 4, 2),        # exact split
    (9, 4, 3),        # one straggler pads the whole row up
    (1, 8, 1),        # more devices than systems: one lane each
    (128, 128, 1),
])
def test_shard_lanes(m, n_shards, want):
    assert shard_lanes(m, n_shards) == want
