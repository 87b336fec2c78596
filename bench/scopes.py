"""Attribute the device time of a profiler trace to the program's layers.

    python bench/scopes.py <file.xplane.pb> --calls <n>    # a kept trace

The program names its layers with ``jax.named_scope``: ``repro.stencil``,
``repro.corner_correction``, ``repro.kernel.<name>`` and ``repro.pad_cast``
(``src/repro/scopes.py``).  The compiler keeps the name stack as each
device operation's ``tf_op``, on the operation's metadata in the trace
(``xplane.py``).  An operation's layer is the innermost ``repro.*``
segment of its ``tf_op``; an operation under none is ``unscoped``.

Operations are those of ``devtrace``: the ``XLA Ops`` line of each device
plane, clipped to the ``bench.window`` span.  So the layers' times add up
to the kernel time plus the XLA-pass time of ``devtrace.Summary``.  Bytes
are the compiler's ``bytes_accessed`` of each operation (0 for a Pallas
kernel, whose custom call the compiler cannot see into), prorated where
the window clips an operation.  An async copy's start and done events
last a few ns while the copy runs beside them, so the GB/s of a layer
made of them (``unscoped``, today) means nothing.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

import devtrace
import xplane

PREFIX = "repro."
UNSCOPED = "unscoped"
STENCIL = "repro.stencil"
CORNER_CORRECTION = "repro.corner_correction"


def layer_of(tf_op: str | None) -> str:
    """The innermost ``repro.*`` segment of a ``tf_op`` name stack
    (``jit(advance)/repro.stencil/add:``), or ``unscoped``."""
    segments = [s.split(":")[0] for s in (tf_op or "").split("/")]
    scoped = [s for s in segments if s.startswith(PREFIX)]
    return scoped[-1] if scoped else UNSCOPED


@dataclasses.dataclass
class ScopedSummary(devtrace.Summary):
    #: per device, as ``devices``: (start_ns, end_ns, name, layer, bytes)
    #: of each operation in the window
    layers: list = dataclasses.field(default_factory=list)

    def layer_s(self, layer: str) -> list:
        """Seconds each device spent in ``layer``."""
        return [sum(e - s for s, e, _, lay, _ in ops if lay == layer) * 1e-9
                for ops in self.layers]

    def table(self, calls: int) -> dict:
        """{layer: device ms, compiler bytes and GB/s per call, and the
        operations} on the device that spent most in each layer."""
        out = {}
        for layer in sorted({op[3] for ops in self.layers for op in ops}):
            mine = [[op for op in ops if op[3] == layer]
                    for ops in self.layers]
            ops = max(mine, key=lambda o: sum(op[1] - op[0] for op in o))
            ns = sum(e - s for s, e, _, _, _ in ops)
            nbytes = sum(op[4] for op in ops)
            out[layer] = {
                "ms": ns * 1e-6 / calls,
                "bytes": nbytes / calls,
                "gb_per_s": nbytes / ns if ns and nbytes else None,
                "ops": sorted({op[2] for op in ops}),
            }
        return out


def summarize(path, device_ids=None) -> ScopedSummary:
    """``devtrace.summarize`` of ``path``, with each operation's layer."""
    base = devtrace.summarize(path, device_ids=device_ids)
    planes = xplane.read(path)
    ws, we = base.window_ns
    layers = []
    for device in base.devices:
        ops = []
        for ev in planes[device.plane].get(devtrace.OPS_LINE, []):
            s, e = ev.start_ns, ev.start_ns + ev.duration_ns
            if e <= ws or s >= we:
                continue
            cs, ce = max(s, ws), min(e, we)
            share = (ce - cs) / (e - s) if e > s else 1.0
            ops.append((cs, ce, devtrace.short_name(ev.name),
                        layer_of(ev.stats.get("tf_op")),
                        ev.stats.get("bytes_accessed", 0) * share))
        layers.append(ops)
    fields = {f.name: getattr(base, f.name)
              for f in dataclasses.fields(devtrace.Summary)}
    return ScopedSummary(**fields, layers=layers)


def _per_call_ms(per_device: list, calls: int):
    """Ms per call on the device that spent most; None for none."""
    if not calls or not per_device or max(per_device) <= 0:
        return None
    return max(per_device) / calls * 1e3


def layer_ms(run, layer: str):
    """Device ms per call that ``run``'s trace (a ``ScopedSummary``) spent
    in ``layer``, on the device that spent most; None where nothing ran in
    ``layer``."""
    return _per_call_ms(run.trace.layer_s(layer), run.calls)


def report(path, calls: int, device_ids=None) -> dict:
    """The layer metrics and table of a kept trace of ``calls`` calls."""
    summary = summarize(path, device_ids=device_ids)
    return {
        "calls": calls,
        "metrics": {
            "stencil_ms": _per_call_ms(summary.layer_s(STENCIL), calls),
            "corner_correction_ms": _per_call_ms(
                summary.layer_s(CORNER_CORRECTION), calls),
            "kernel_ms": _per_call_ms(summary.kernel_s(), calls),
            "xla_pass_ms": _per_call_ms(summary.other_s(), calls)},
        "scopes": summary.table(calls),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("trace", help="an .xplane.pb, or a directory holding one")
    ap.add_argument("--calls", type=int, required=True,
                    help="timed calls in the traced window (the run's "
                         "'attempted')")
    ap.add_argument("--device", type=int, action="append",
                    help="device id to read (default: every device with "
                         "an operation in the window)")
    args = ap.parse_args(argv)
    path = args.trace
    if not path.endswith(".xplane.pb"):
        path = devtrace.find_xplane(path)
    print(json.dumps(report(path, args.calls, args.device), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
