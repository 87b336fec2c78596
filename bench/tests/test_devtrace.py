"""The trace reduction on a small trace recorded on the chip: one second
of the diffusion-shared-lhs cell on one TPU v5e (``data/``), reduced to
the numbers that run printed."""

import json
import pathlib

import pytest

import devtrace
import run

DATA = pathlib.Path(__file__).resolve().parent / "data"
TRACE = DATA / "cn_step_1s.xplane.pb"
RECORDED = json.loads((DATA / "cn_step_1s.json").read_text())


@pytest.fixture(scope="module")
def summary():
    return devtrace.summarize(TRACE, device_ids=[0])


def test_window_and_busy_time(summary):
    assert summary.window_s == pytest.approx(RECORDED["window_s"], rel=1e-12)
    assert summary.busy_s == pytest.approx(RECORDED["busy_s"], rel=1e-12)
    assert len(summary.devices) == 1


@pytest.mark.parametrize("name", sorted(RECORDED["metrics"]))
def test_per_layer_metric(summary, name):
    r = run.Run(setup_s=0.0, calls=RECORDED["calls"],
                window_s=RECORDED["window_s"], call_s=[],
                counts={"step_bytes": RECORDED["step_bytes"],
                        "kernel_bytes": RECORDED["kernel_bytes"]},
                memory_peak_bytes=0,
                peaks={"hbm_bytes_per_s": RECORDED["hbm_bytes_per_s"]},
                trace=summary)
    assert run.read_metric(name, r) == pytest.approx(
        RECORDED["metrics"][name], rel=1e-9)


def test_one_kernel_and_the_xla_passes(summary):
    kernels = {n for s, e, n, k in summary.devices[0].ops if k}
    assert kernels == {"%solver.1 custom-call"}
    ops = [n for n, _ in summary.breakdown()["device_ops"]]
    assert ops[0] == "%solver.1 custom-call"
    assert "%multiply_subtract_fusion fusion" in ops


def test_shares_stay_under_100(summary):
    assert 0 < RECORDED["metrics"]["kernel_roofline_pct"] < 100
    assert 0 < RECORDED["metrics"]["step_roofline_pct"] < 100


def test_idle_gaps_are_labelled_by_host_spans(summary):
    labels = {label for label, _ in summary.idle_gaps()}
    assert labels <= set(devtrace.GAP_LABELS) | {"outside bench spans"}
    assert "bench.block_until_ready" in labels


def test_a_kernel_is_a_tpu_custom_call():
    assert devtrace.is_kernel('%solver.1 = f32[8,128]{1,0} custom-call('
                              'f32[8,128]{1,0} %x), '
                              'custom_call_target="tpu_custom_call"')
    assert not devtrace.is_kernel('%fusion.4 = f32[8,128]{1,0} fusion('
                                  'f32[8,128]{1,0} %x), kind=kLoop')
    assert devtrace.short_name('%fusion.4 = (f32[1,8]{1,0}, f32[1,8]{1,0}) '
                               'fusion(f32[8,8]{1,0} %x), kind=kLoop') \
        == "%fusion.4 fusion"
