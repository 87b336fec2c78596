"""Algorithmic byte counts and the table of peaks.

A roofline divides the same work whatever implements it, so these count
what a call must read and write once, from its shapes alone.  They are
not the program's own traffic model, which counts what the current
kernels move and changes with them.
"""

from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"


def solve_words(bandwidth: int, mode: str, n: int, m: int) -> int:
    """Words one banded solve of M systems of size N must move.

    Shared LHS (``constant``/``uniform``): the RHS in and the solution out,
    2·N·M; the factor, a few N-vectors, is left out.  Per-system LHS
    (``batch``): each system's ``bandwidth`` stored diagonals as well,
    (bandwidth + 2)·N·M."""
    if mode == "batch":
        return (bandwidth + 2) * n * m
    return 2 * n * m


def cn_step_words(n: int, m: int) -> int:
    """Words one Crank-Nicolson step must move: the field in and the field
    out, 2·N·M.  An ideal step builds its RHS as the solve reads it."""
    return 2 * n * m


def peaks(device_kind: str) -> dict:
    """The peak rates of ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; add its published numbers")
    return table[device_kind]
