"""A new cell, and a new kind of call with its own faults, enter the
benchmark by new files and ``BENCHMARK.json`` entries alone.

A copy of ``bench/`` and ``BENCHMARK.json`` is given two cells: one of the
existing ``solve`` kind (a new traffic mix, its limits and its rehearsal
size), and one of a toy kind whose state is a pytree (a dict of two
arrays), with its configuration, control and faults.  The copy's own
tests, unedited, then rehearse both on the CPU and see the control and
every fault of each kind fail the comparison.  No copied file changes.
"""

import hashlib
import json
import os
import pathlib
import shutil
import subprocess
import sys

import run

SOLVE_CELL = "tridiag-constant-lhs"
TOY_CELL = "toy-pytree"

#: A kind whose state is a pytree: ``x``, an (N, M) field, and ``v``, one
#: value per system.  One call is ``steps_per_call`` averaging updates,
#: which keep ``mean(x) + v`` of each system and settle there; the check
#: replays every call of the run on the host in float64.
TOY_KIND = '''"""Toy updates of a pytree state {"x": (N, M), "v": (M,)}."""

import jax
import jax.numpy as jnp
import numpy as np

import cells
import reference as ref


def _update(state, dtype):
    x, v = state["x"].astype(dtype), state["v"].astype(dtype)
    half = dtype(0.5)
    return {"x": half * (x + v[None, :]),
            "v": half * (v + x.mean(axis=0))}


def _keep_half(call):
    """Half of the systems left out, in both leaves."""
    def broken(state):
        out = call(state)
        half = out["v"].shape[0] // 2
        return {"x": out["x"].at[:, half:].set(state["x"][:, half:]),
                "v": out["v"].at[half:].set(state["v"][half:])}
    return broken


def _drop_leaf(call):
    """One leaf of the state returned unchanged."""
    def broken(state):
        return {"x": call(state)["x"], "v": state["v"]}
    return broken


FAULTS = {"unchanged": cells.unchanged, "half_batch": _keep_half,
          "dropped_leaf": _drop_leaf}


def build(config, traffic, seed, devices, *, control=False):
    n, m, steps = config["n"], config["m"], traffic["steps_per_call"]
    key = jax.random.PRNGKey(ref.jax_seed(seed))
    state = jax.jit(lambda k: {
        "x": jax.random.normal(k, (n, m), jnp.float32),
        "v": jax.random.normal(jax.random.fold_in(k, 1), (m,),
                               jnp.float32)})(key)
    start = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64),
                                   state)
    dtype = jnp.bfloat16 if control else jnp.float32

    def advance(s):
        for _ in range(steps):
            s = _update(s, dtype)
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), s)

    def check(out, calls):
        want = start
        for _ in range(calls * steps):
            want = _update(want, np.float64)
        return {"max_rel_err": max(
            ref.rel_err(np.asarray(out[k], np.float64), want[k])
            for k in want)}

    call = jax.jit(advance)
    return cells.Cell(call=call, state=state, chained=True,
                      counts={"step_bytes": 8 * (n + 1) * m * steps},
                      check=check, memory=cells.memory_of(call, state))
'''

#: path under bench/ -> content of every file the two cells bring
NEW_FILES = {
    "kinds/toy_pytree.py": TOY_KIND,
    "configs/toy-pytree.json": {"n": 64, "m": 512, "dtype": "float32"},
    "traffic/toy-step.json": {"kind": "toy_pytree", "steps_per_call": 2},
    f"limits/{TOY_CELL}.json": {"max_rel_err": {"limit": 1e-4}},
    f"rehearsal/{TOY_CELL}.json": {"sizes": {"m": 128}},
    "traffic/constant-lhs-solve.json": {
        "kind": "solve", "backend": "pallas", "mode": "constant",
        "boundary": "dirichlet", "check_cols": 64},
    f"limits/{SOLVE_CELL}.json": {"max_rel_err": {"limit": 1e-4}},
    f"rehearsal/{SOLVE_CELL}.json": {"sizes": {"m": 256}},
}
NEW_ENTRIES = {
    "configs": [{"name": "toy-pytree", "source": "a toy update of two arrays",
                 "file": "bench/configs/toy-pytree.json", "reduced": [],
                 "why": "a state that is a pytree"}],
    "workloads": [
        {"name": SOLVE_CELL, "config": "tridiag-n1024",
         "traffic": "constant-lhs-solve", "chips": 1,
         "why": "one Dirichlet solve with one LHS shared by every system"},
        {"name": TOY_CELL, "config": "toy-pytree", "traffic": "toy-step",
         "chips": 1, "why": "a kind whose state is a pytree"}],
}


def _digests(root: pathlib.Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).digest()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_cell_and_a_kind_enter_by_new_files_alone(tmp_path):
    bench = tmp_path / "bench"
    shutil.copytree(run.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(run.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    copied = _digests(tmp_path)

    for rel, content in NEW_FILES.items():
        path = bench / rel
        assert not path.exists(), rel
        path.write_text(content if isinstance(content, str)
                        else json.dumps(content))
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    for key, entries in NEW_ENTRIES.items():
        spec[key] += entries
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))

    cells = [SOLVE_CELL, TOY_CELL]
    selected = (
        [f"test_rehearsal.py::test_rehearse_cell[{c}]" for c in cells]
        + ["test_rehearsal.py::test_every_cell_has_a_tiny_size",
           "test_rehearsal.py::test_same_seed_same_inputs"]
        + [f"test_control_and_faults.py::test_control_is_not_correct[{c}]"
           for c in cells]
        + [f"test_control_and_faults.py::test_fault_is_not_correct[{c}-{f}]"
           for c, faults in [(SOLVE_CELL, ["unchanged", "half_batch",
                                           "altered_answer"]),
                             (TOY_CELL, ["unchanged", "half_batch",
                                         "dropped_leaf"])]
           for f in faults])
    env = dict(os.environ, PYTHONPATH=str(run.ROOT / "src"))
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", *selected],
        cwd=bench / "tests", env=env, capture_output=True, text=True,
        timeout=600)
    assert proc.returncode == 0, proc.stdout[-4000:] + proc.stderr[-2000:]
    assert f"{len(selected)} passed" in proc.stdout, proc.stdout[-2000:]

    after = _digests(tmp_path)
    edited = {rel for rel, digest in copied.items()
              if rel != "BENCHMARK.json" and after.get(rel) != digest}
    assert not edited
    old = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    new = json.loads((tmp_path / "BENCHMARK.json").read_text())
    for key, value in old.items():
        added = NEW_ENTRIES.get(key, [])
        assert new[key] == (value + added if added else value), key
