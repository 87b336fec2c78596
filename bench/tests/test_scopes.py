"""The stdlib trace reader and the reduction of a trace by the program's
layer scopes, on traces recorded on the chip (``data/``)."""

import json
import pathlib

import pytest

import counts
import devtrace
import run
import scopes
import xplane

DATA = pathlib.Path(__file__).resolve().parent / "data"
#: recorded before the program named its layers
UNSCOPED_TRACE = DATA / "cn_step_1s.xplane.pb"
#: recorded with the layer scopes, one per cell, with what the run printed
SCOPED = ["diffusion_scoped_1s", "hyperdiffusion_scoped_1s"]


@pytest.mark.parametrize("stem", ["cn_step_1s"] + SCOPED)
def test_reader_agrees_with_profile_data_on_every_event(stem):
    from jax.profiler import ProfileData
    path = DATA / f"{stem}.xplane.pb"
    mine = xplane.read(path)
    theirs = ProfileData.from_file(str(path))
    count = 0
    for plane in theirs.planes:
        for line in plane.lines:
            events = mine[plane.name][line.name]
            want = [(e.start_ns, e.duration_ns, e.name) for e in line.events]
            assert [(e.start_ns, e.duration_ns, e.name)
                    for e in events] == want
            count += len(want)
    assert count > 700


def test_op_metadata_of_the_unscoped_trace():
    """Each operation's ``tf_op`` and compiler bytes, as the trace was
    recorded (diffusion-shared-lhs before the scopes)."""
    ops = {devtrace.short_name(e.name): e.stats
           for e in xplane.read(UNSCOPED_TRACE)["/device:TPU:0"]["XLA Ops"]}
    tf_op = {name: stats.get("tf_op") for name, stats in ops.items()}
    roll = "jit(advance)/jit(_roll_static)/slice:"
    assert tf_op["%fusion.4 fusion"] == roll
    assert tf_op["%fusion.5 fusion"] == roll
    assert tf_op["%fusion.1 fusion"] == "jit(advance)/add:"
    assert tf_op["%multiply_subtract_fusion fusion"] == "jit(advance)/sub:"
    assert tf_op["%fusion.3 fusion"] == "jit(advance)/mul:"
    assert tf_op["%solver.1 custom-call"] == \
        "jit(advance)/jit(solver)/pallas_call:"
    assert tf_op["%copy-done copy-done"] is None
    assert ops["%fusion.1 fusion"]["bytes_accessed"] == 8589934592
    assert ops["%solver.1 custom-call"]["bytes_accessed"] == 0


@pytest.mark.parametrize("tf_op,layer", [
    ("jit(advance)/repro.stencil/jit(_roll_static)/slice:", "repro.stencil"),
    ("jit(advance)/repro.corner_correction/sub:", "repro.corner_correction"),
    ("jit(advance)/repro.kernel.thomas_constant/jit(solver)/"
     "thomas_constant/pallas_call:", "repro.kernel.thomas_constant"),
    ("jit(f)/repro.kernel.thomas_constant/repro.pad_cast/pad:",
     "repro.pad_cast"),
    ("jit(advance)/add:", "unscoped"),
    (None, "unscoped"),
])
def test_layer_is_the_innermost_repro_scope(tf_op, layer):
    assert scopes.layer_of(tf_op) == layer


@pytest.fixture(scope="module")
def unscoped():
    return scopes.summarize(UNSCOPED_TRACE, device_ids=[0])


def test_a_trace_without_scopes_is_all_unscoped(unscoped):
    table = unscoped.table(16)
    assert set(table) == {"unscoped"}
    base = devtrace.summarize(UNSCOPED_TRACE, device_ids=[0])
    total = base.kernel_s()[0] + base.other_s()[0]
    assert unscoped.layer_s("unscoped")[0] == pytest.approx(total, rel=1e-12)
    assert table["unscoped"]["ms"] == pytest.approx(total * 1e3 / 16,
                                                    rel=1e-12)


@pytest.mark.parametrize("name", ["stencil_ms", "corner_correction_ms"])
def test_new_metrics_are_silent_without_scopes(unscoped, name):
    """The parent program names no layers: the readers give None."""
    r = run.Run(setup_s=0.0, calls=16, window_s=1.0, call_s=[],
                counts={}, memory_peak_bytes=0, trace=unscoped)
    assert run.read_metric(name, r) is None


def test_scoped_summary_keeps_every_devtrace_reading(unscoped):
    base = devtrace.summarize(UNSCOPED_TRACE, device_ids=[0])
    assert unscoped.kernel_s() == base.kernel_s()
    assert unscoped.other_s() == base.other_s()
    assert unscoped.busy_s == base.busy_s
    assert unscoped.breakdown() == base.breakdown()


def test_report_of_a_kept_trace(capsys):
    assert scopes.main([str(UNSCOPED_TRACE), "--calls", "16",
                        "--device", "0"]) == 0
    out = json.loads(capsys.readouterr().out)
    recorded = json.loads((DATA / "cn_step_1s.json").read_text())["metrics"]
    assert out["metrics"]["kernel_ms"] == pytest.approx(
        recorded["kernel_ms"], rel=1e-9)
    assert out["metrics"]["xla_pass_ms"] == pytest.approx(
        recorded["xla_pass_ms"], rel=1e-9)
    assert out["metrics"]["stencil_ms"] is None


@pytest.fixture(scope="module", params=SCOPED)
def scoped(request):
    """(ScopedSummary, what the run and the report printed)."""
    recorded = json.loads((DATA / f"{request.param}.json").read_text())
    summary = scopes.summarize(DATA / f"{request.param}.xplane.pb",
                               device_ids=[0])
    return summary, recorded


def _run(summary, recorded):
    return run.Run(setup_s=0.0, calls=recorded["calls"],
                   window_s=recorded["window_s"], call_s=[], counts={},
                   memory_peak_bytes=0, trace=summary)


@pytest.mark.parametrize("name", ["stencil_ms", "corner_correction_ms"])
def test_new_metrics_reproduce_the_printed_values(scoped, name):
    summary, recorded = scoped
    assert run.read_metric(name, _run(summary, recorded)) == pytest.approx(
        recorded["scopes_report"]["metrics"][name], rel=1e-12)


@pytest.mark.parametrize("name", ["kernel_ms", "xla_pass_ms",
                                  "device_idle_pct"])
def test_existing_metrics_read_what_the_run_printed(scoped, name):
    summary, recorded = scoped
    assert run.read_metric(name, _run(summary, recorded)) == pytest.approx(
        recorded["metrics"][name], rel=1e-12)


def test_layers_partition_the_kernel_and_xla_pass_time(scoped):
    summary, recorded = scoped
    table = summary.table(recorded["calls"])
    metrics = recorded["metrics"]
    kernels = sum(v["ms"] for k, v in table.items()
                  if k.startswith("repro.kernel."))
    passes = sum(v["ms"] for k, v in table.items()
                 if not k.startswith("repro.kernel."))
    assert kernels == pytest.approx(metrics["kernel_ms"], rel=1e-12)
    assert passes == pytest.approx(metrics["xla_pass_ms"], rel=1e-12)
    assert {k for k in table if not k.startswith("repro.kernel.")} <= {
        scopes.STENCIL, scopes.CORNER_CORRECTION, "repro.pad_cast",
        scopes.UNSCOPED}


def test_every_op_is_scoped_but_async_copies_of_constants(scoped):
    summary, recorded = scoped
    table = summary.table(recorded["calls"])
    assert table["unscoped"]["ms"] < 0.2
    assert all(name.split()[-1] in ("copy-start", "copy-done")
               for name in table["unscoped"]["ops"])
    assert table[scopes.STENCIL]["ms"] > 0
    assert table[scopes.CORNER_CORRECTION]["ms"] > 0


#: every per-layer metric the benchmark names
PER_LAYER = [m["name"] for m in json.loads(
    (run.ROOT / "BENCHMARK.json").read_text())["per_layer"]]
#: the metrics of the program's layers, which only the scoped reduction reads
LAYER_METRICS = {"stencil_ms", "corner_correction_ms"}


@pytest.mark.parametrize("name", PER_LAYER)
@pytest.mark.parametrize("path", sorted(DATA.glob("*.xplane.pb")),
                         ids=lambda p: p.name.split(".")[0])
def test_run_reads_every_metric_as_devtrace_did(path, name):
    """``run.py`` reduces a trace with ``scopes.summarize``: every metric
    that ``devtrace.summarize`` gave reads bit-equal through it, and the
    layer metrics read what ``scopes.py``'s report of the trace gave (None
    on the trace recorded before the program named its layers)."""
    recorded = json.loads(path.with_name(
        path.name.replace(".xplane.pb", ".json")).read_text())
    words = counts.cn_step_words(1024, 524288)  # every stored trace's cell

    def read(summary):
        r = run.Run(setup_s=0.0, calls=recorded["calls"],
                    window_s=recorded["window_s"], call_s=[],
                    counts={"step_bytes": 4 * words,
                            "kernel_bytes": 4 * words},
                    memory_peak_bytes=0,
                    peaks=counts.peaks("TPU v5 lite"), trace=summary)
        return run.read_metric(name, r)

    new = read(scopes.summarize(path, device_ids=[0]))
    if name in LAYER_METRICS:
        reported = recorded.get("scopes_report", {"metrics": {}})["metrics"]
        assert new == pytest.approx(reported.get(name), rel=1e-12)
    else:
        old = read(devtrace.summarize(path, device_ids=[0]))
        assert old is not None and new == old
