"""A CPU rehearsal of every cell at a tiny size, in interpret mode: set-up,
the closed loop, the reference comparison and the result line.  The
measuring path itself refuses to run without a TPU."""

import json
import pathlib
import subprocess
import sys

import numpy as np
import pytest

import run

SEED = 2 ** 31 + 977           # larger than 32 signed bits hold

#: Each cell's CPU rehearsal, ``rehearsal/<cell>.json``: the ``sizes`` a CPU
#: test run can hold and, for a cell staged in bench/ (configuration,
#: traffic, limits) but not yet in BENCHMARK.json because it is not yet
#: proven on the chip, its ``entry``, so that its path stays tested.
REHEARSALS = {path.stem: json.loads(path.read_text())
              for path in sorted((run.BENCH / "rehearsal").glob("*.json"))}
TINY = {name: r["sizes"] for name, r in REHEARSALS.items()}
ENTRIES = {name: r["entry"] for name, r in REHEARSALS.items()
           if "entry" in r}
ENTRIES.update((w["name"], w) for w in json.loads(
    (run.ROOT / "BENCHMARK.json").read_text())["workloads"])
CELLS = sorted(ENTRIES)


def traffic(workload: str) -> dict:
    return run.load_cell(workload, ENTRIES[workload])["traffic"]


def test_every_cell_has_a_tiny_size():
    assert set(CELLS) <= set(TINY)


@pytest.mark.parametrize("workload", CELLS)
def test_rehearse_cell(workload):
    result, checks = run.execute(workload, SEED, 0.3, False,
                                 require_tpu=False, sizes=TINY[workload],
                                 entry=ENTRIES[workload])
    assert result["correct"] is True, checks
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "checks"
    for c in checks.values():
        assert c["value"] <= c["limit"]
    # host-clock numbers only; no device number comes from a CPU run
    assert set(result["metrics"]) <= {"setup_s", "step_ms", "step_p95_ms"}
    assert "step_ms" in result["metrics"]
    # every call of the window is timed
    assert result["diagnostics"]["call_ms"]["calls"] == result["attempted"]
    assert result["diagnostics"]["compiled_memory_bytes"]["total"] > 0
    assert result["device"]["platform"] == "cpu"
    json.dumps(result)


def test_same_seed_same_inputs():
    # the same seed gives the same data: the step count differs between
    # runs, so compare the inputs the cell was built from, leaf by leaf,
    # for one cell of each kind
    import jax

    import cells
    device = jax.devices()[:1]
    first = {}
    for workload in CELLS:
        first.setdefault(traffic(workload)["kind"], workload)
    for workload in first.values():
        spec = run.load_cell(workload, ENTRIES[workload])
        config = dict(spec["config"], **TINY[workload])

        def leaves(seed):
            cell = cells.build(config, spec["traffic"], seed, device)
            return [np.asarray(x)
                    for x in jax.tree_util.tree_leaves(cell.state)]

        a, b, c = leaves(SEED), leaves(SEED), leaves(SEED + 1)
        assert len(a) == len(b) == len(c) > 0, workload
        assert all((x == y).all() for x, y in zip(a, b)), workload
        assert not all((x == z).all() for x, z in zip(a, c)), workload


def test_a_number_the_cell_does_not_give_fails(monkeypatch):
    load = run.load_cell

    def with_extra_limit(workload, entry=None):
        spec = load(workload, entry)
        spec["limits"] = dict(spec["limits"], not_given={"limit": 1.0})
        return spec

    monkeypatch.setattr(run, "load_cell", with_extra_limit)
    result, checks = run.execute(CELLS[0], SEED, 0.2, False,
                                 require_tpu=False, sizes=TINY[CELLS[0]],
                                 entry=ENTRIES[CELLS[0]])
    assert result["correct"] is False
    assert checks["not_given"]["value"] is None


def test_no_tpu_no_result():
    script = pathlib.Path(run.__file__)
    proc = subprocess.run(
        [sys.executable, str(script), "--workload", "diffusion-shared-lhs",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 2
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr
