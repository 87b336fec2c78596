"""Run one benchmark cell once on the chips it names, and print one JSON line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the traffic's kind
(``bench/kinds/<kind>.py``) builds the timed call from them and the seed.
Set-up makes the data on the device, factorizes once and warms the call
up; then a closed loop of calls, each ended by ``block_until_ready`` and
timed alone, runs for ``--seconds``.  After the window the output of the
timed path is compared with the float64 host reference on columns drawn
from the seed: every number that ``bench/limits/<cell>.json`` names is
checked against its limit.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window with each
device operation's layer (``scopes.py``); the result's ``diagnostics``
then carry the time of every layer under ``scopes``.  Each metric is read
by ``bench/metrics/<name>.py``.  Without a TPU, or with fewer chips
than the cell asks for, the run exits with status 2 and prints no result.

``--control`` puts the kind's control, the reference in bfloat16, in the
program's place (the comparison has to fail it); the benchmark's own runs
never pass it.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse                                                  # noqa: E402
import contextlib                                                # noqa: E402
import dataclasses                                               # noqa: E402
import importlib.util                                            # noqa: E402
import json                                                      # noqa: E402
import math                                                      # noqa: E402
import pathlib                                                   # noqa: E402
import shutil                                                    # noqa: E402
import sys                                                       # noqa: E402
import tempfile                                                  # noqa: E402
from typing import Any                                           # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

#: JAX's persistent compilation cache: a fixed directory in the checkout,
#: so that only a checkout's first run of a cell compiles.
CACHE_DIR = ROOT / ".jax_cache"
#: Calls before the window: the first compiles, the second runs warm.
WARMUP_CALLS = 2


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    setup_s: float
    calls: int
    window_s: float
    call_s: list                    # each call, dispatch to ready, seconds
    counts: dict                    # the cell's algorithmic counts per call
    memory_peak_bytes: int
    peaks: dict | None = None
    trace: Any = None               # scopes.ScopedSummary of the window


def load_cell(workload: str, entry: dict | None = None) -> dict:
    """The workload's entry, configuration, traffic, limits and metrics.

    ``entry`` stands in for the workload's entry in ``BENCHMARK.json``:
    the CPU rehearsal passes it for a cell staged in ``bench/`` before it
    is proven on the chip."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if entry is None and workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {', '.join(sorted(cells))}")
    cell = entry or cells[workload]

    def data(kind: str, name: str) -> dict:
        return json.loads((BENCH / kind / f"{name}.json").read_text())

    def applies(metric: dict) -> bool:
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell,
        "config": data("configs", cell["config"]),
        "traffic": data("traffic", cell["traffic"]),
        "limits": data("limits", workload),
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
    }


def read_metric(name: str, run: Run):
    """``bench/metrics/<name>.py``'s ``read(run)``: a number or None."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(run)


def _use_compile_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class _CompileCounter:
    """Counts backend compilations while ``active``."""

    def __init__(self):
        import jax
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if self.active and event.endswith("backend_compile_duration"):
            self.count += 1


def _peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _window(cell, state, seconds: float):
    """The closed loop: (last output, window seconds, seconds of each call
    from its dispatch to its ``block_until_ready``)."""
    import jax
    annotate = jax.profiler.TraceAnnotation
    clock = time.perf_counter
    call_s = []
    out = None
    with annotate("bench.window"):
        t_start = clock()
        deadline = t_start + seconds
        while True:
            if not cell.chained:
                out = None          # free the last result before the next
            t_call = clock()
            with annotate("bench.dispatch"):
                out = cell.call(state)
            with annotate("bench.block_until_ready"):
                jax.block_until_ready(out)
            now = clock()
            call_s.append(now - t_call)
            if cell.chained:
                state = out
            if now >= deadline:
                break
    return out, now - t_start, call_s


def _call_summary(call_s: list) -> dict:
    """Order statistics of the window's call times, in ms."""
    ms = sorted(x * 1e3 for x in call_s)
    at = lambda q: ms[min(len(ms) - 1, int(q * len(ms)))]      # noqa: E731
    return {"calls": len(ms), "min": ms[0], "median": at(0.5),
            "p90": at(0.9), "p95": at(0.95), "p99": at(0.99), "max": ms[-1],
            "slowest": [[i, x * 1e3] for i, x in sorted(
                enumerate(call_s), key=lambda ix: -ix[1])[:10]]}


def _compiled_memory(cell) -> dict | None:
    """The timed call's compiled memory analysis, for the diagnostics; a
    backend that cannot give it leaves its error there instead."""
    try:
        return cell.memory()
    except Exception as e:                      # noqa: BLE001
        return {"error": repr(e)[:300]}


def execute(workload: str, seed: int, seconds: float, trace: bool, *,
            require_tpu: bool = True, control: bool = False,
            sizes: dict | None = None, wrap_call=None,
            trace_dir: str | None = None, entry: dict | None = None):
    """One run of ``workload``: (result line, checks).

    ``sizes`` overrides configuration keys, ``wrap_call`` wraps the timed
    call (a fault of the cell's kind, ``cells.faults``) and ``entry``
    stands in for the cell's entry (``load_cell``); all three exist for
    the CPU rehearsal and its fault tests, which also pass
    ``require_tpu=False``.  The rehearsal takes ``sizes`` from the cell's
    ``rehearsal/<cell>.json``, which may shrink any key, a model
    configuration's widths among them, so that a CPU test run can hold
    the cell; a chip run never reads that file, and its command line has
    no way to pass ``sizes``."""
    import jax

    import cells
    import counts

    spec = load_cell(workload, entry)
    chips = spec["cell"]["chips"]
    if require_tpu:
        _use_compile_cache()
    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    peaks = counts.peaks(devices[0].device_kind) if require_tpu else None
    used = devices[:chips]
    phases = {"backend_s": time.perf_counter() - _T0}

    config = dict(spec["config"], **(sizes or {}))
    cell = cells.build(config, spec["traffic"], seed, used, control=control)
    if wrap_call is not None:
        cell.call = wrap_call(cell.call)
    phases["cell_built_s"] = time.perf_counter() - _T0

    state = cell.state
    for _ in range(WARMUP_CALLS):
        out = jax.block_until_ready(cell.call(state))
        if cell.chained:
            state = out
    del out
    setup_s = time.perf_counter() - _T0

    counter = _CompileCounter()
    warm_calls = WARMUP_CALLS
    log_dir = None
    if trace:
        log_dir = tempfile.mkdtemp(prefix="bench_trace_", dir=trace_dir)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0         # the bench.* spans suffice
        jax.profiler.start_trace(log_dir, profiler_options=options)
        # the first call under a fresh profiler returns late; keep it out
        # of the window
        out = jax.block_until_ready(cell.call(state))
        state = out if cell.chained else state
        warm_calls += 1
        del out
    counter.active = True
    try:
        out, window_s, call_s = _window(cell, state, seconds)
    finally:
        counter.active = False
        if trace:
            jax.profiler.stop_trace()
    del state
    calls = len(call_s)
    memory_peak = _peak_bytes(used)

    run = Run(setup_s=setup_s, calls=calls, window_s=window_s,
              call_s=call_s, counts=cell.counts,
              memory_peak_bytes=memory_peak, peaks=peaks)
    if trace:
        import devtrace
        import scopes
        run.trace = scopes.summarize(devtrace.find_xplane(log_dir),
                                     device_ids=[d.id for d in used])
        if trace_dir is None:
            shutil.rmtree(log_dir, ignore_errors=True)

    # the check: every number the limits name, from the timed path's own
    # output against the float64 host reference; a number the cell does
    # not give fails
    numbers = cell.check(out, warm_calls + calls)
    del out
    checks = {name: {"value": numbers.get(name), "limit": lim["limit"]}
              for name, lim in spec["limits"].items()}
    correct = bool(checks) and all(
        c["value"] is not None and math.isfinite(c["value"])
        and c["value"] <= c["limit"] for c in checks.values())

    group = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec[group]:
        value = read_metric(m["name"], run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": calls,
              "failed": 0 if correct else 1, "metrics": metrics,
              "device": device}
    if trace:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown()
    result["diagnostics"] = {
        "calls_in_warmup": warm_calls, "compiles_in_window": counter.count,
        "setup_phases_s": phases, "call_ms": _call_summary(call_s),
        "memory_stats": used[0].memory_stats() or {},
        "compiled_memory_bytes": _compiled_memory(cell),
        "numbers": numbers}
    if trace:
        result["diagnostics"]["scopes"] = run.trace.table(calls)
    result["checks"] = checks
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="run the bfloat16 reference in the program's place")
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="keep the profiler trace under DIR")
    args = ap.parse_args(argv)
    if args.keep_trace:
        pathlib.Path(args.keep_trace).mkdir(parents=True, exist_ok=True)
    try:
        result, checks = execute(args.workload, args.seed, args.seconds,
                                 bool(args.trace), control=args.control,
                                 trace_dir=args.keep_trace)
    except NoChip as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 2
    with contextlib.suppress(BrokenPipeError):
        for name, c in checks.items():
            print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
                  file=sys.stderr, flush=True)
        print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
