"""A CPU rehearsal of every cell at a tiny size, in interpret mode: set-up,
the closed loop, the reference comparison and the result line.  The
measuring path itself refuses to run without a TPU."""

import json
import pathlib
import subprocess
import sys

import pytest

import run

#: Sizes a CPU test run can hold; the widths of the cells are their own.
TINY = {
    "diffusion-shared-lhs": {"m": 256},
    "hyperdiffusion-shared-lhs": {"m": 256},
    "tridiag-copied-lhs": {"m": 256},
    "tridiag-n16384-sharded-x4": {"m": 512, "n": 512},
}
SEED = 2 ** 31 + 977           # larger than 32 signed bits hold


#: Cells staged in bench/ (configuration, traffic, limits) but not yet in
#: BENCHMARK.json, because they are not yet proven on the chip: rehearsed
#: all the same, so that their path stays tested.
STAGED = [
    {"name": "tridiag-copied-lhs", "config": "tridiag-n1024",
     "traffic": "copied-lhs-solve", "chips": 1},
    {"name": "tridiag-n16384-sharded-x4", "config": "tridiag-n16384-x4",
     "traffic": "sharded-solve", "chips": 4},
]
ENTRIES = {w["name"]: w for w in STAGED}
ENTRIES.update((w["name"], w) for w in json.loads(
    (run.ROOT / "BENCHMARK.json").read_text())["workloads"])
CELLS = sorted(ENTRIES)


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
    # runs, so compare the inputs the cell was built from
    import cells
    spec = run.load_cell(CELLS[0], ENTRIES[CELLS[0]])
    config = dict(spec["config"], **TINY[CELLS[0]])
    import jax
    device = jax.devices()[:1]
    a = cells.build(config, spec["traffic"], SEED, device)
    b = cells.build(config, spec["traffic"], SEED, device)
    c = cells.build(config, spec["traffic"], SEED + 1, device)
    assert (a.state == b.state).all()
    assert not (a.state == c.state).all()


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
