"""The algorithmic byte counts against hand counts, and the peak table."""

import pytest

import counts


def test_shared_lhs_solve_is_rhs_in_and_solution_out():
    # N=8, M=3: 24 RHS words read, 24 solution words written
    assert counts.solve_words(3, "constant", 8, 3) == 48
    assert counts.solve_words(5, "uniform", 8, 3) == 48


def test_copied_lhs_solve_reads_every_stored_diagonal():
    # tridiag, N=8, M=3: 3 diagonals x 24 + 24 RHS + 24 solution
    assert counts.solve_words(3, "batch", 8, 3) == 3 * 24 + 24 + 24
    # penta: 5 diagonals x 24 + 24 + 24
    assert counts.solve_words(5, "batch", 8, 3) == 5 * 24 + 48


def test_cn_step_is_field_in_and_field_out():
    assert counts.cn_step_words(8, 3) == 48


def test_peaks_of_the_v5e():
    p = counts.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes"] == 16e9


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
