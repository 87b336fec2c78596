"""Reduce a profiler trace (``.xplane.pb``) of the measured window.

    python bench/devtrace.py dump <file.xplane.pb>     # look at one by hand

Device planes are ``/device:TPU:<id>``; each has a line of XLA operations
(``XLA Ops``) whose events are the operations the chip ran, with a start
and a duration in nanoseconds.  Each event is named by its HLO
instruction; an operation is a Pallas kernel when that instruction is a
custom call to ``tpu_custom_call`` (the Mosaic kernels).  The host plane
carries the benchmark's own spans (``bench.window``, ``bench.dispatch``,
``bench.block_until_ready``), written with
``jax.profiler.TraceAnnotation`` on the same clock.

Everything is clipped to the ``bench.window`` span.  Per device: the
summed time of kernel and of other operations, and the union of all
operation intervals (busy time).  Idle gaps between busy intervals are
labelled by the benchmark span the host was in at the gap's midpoint.
"""

from __future__ import annotations

import dataclasses
import pathlib
import re
import sys

OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
BENCH_SPAN = "bench."
WINDOW_SPAN = "bench.window"
#: Host spans an idle gap can be attributed to, innermost first.
GAP_LABELS = ("bench.dispatch", "bench.block_until_ready", "bench.window")
TOP = 10
OP_NAME = re.compile(r"^(%[\w.\-]+) = (?:\([^=]*?\)|\S+) ([\w\-]+)\(")


def find_xplane(log_dir) -> pathlib.Path:
    found = sorted(pathlib.Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _stats(event) -> dict:
    try:
        return dict(event.stats)
    except (TypeError, ValueError):
        return {}


def is_kernel(name: str) -> bool:
    """A Pallas (Mosaic) kernel: a ``tpu_custom_call`` custom call."""
    return 'custom_call_target="tpu_custom_call"' in name


def short_name(name: str) -> str:
    """``%fusion.4 fusion`` for an HLO operation's trace name
    ``%fusion.4 = (f32[...], ...) fusion(...), kind=...``."""
    match = OP_NAME.match(name)
    return f"{match.group(1)} {match.group(2)}" if match else name[:120]


@dataclasses.dataclass
class Device:
    plane: str
    ops: list            # (start_ns, end_ns, name, kernel), in the window


@dataclasses.dataclass
class Summary:
    window_ns: tuple     # (start, end) of bench.window
    devices: list        # Device
    spans: list          # (start_ns, end_ns, name) of bench.* host spans

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    def kernel_s(self) -> list:
        return [sum(e - s for s, e, _, k in d.ops if k) * 1e-9
                for d in self.devices]

    def other_s(self) -> list:
        return [sum(e - s for s, e, _, k in d.ops if not k) * 1e-9
                for d in self.devices]

    def busy_intervals(self, device: Device) -> list:
        merged = []
        for s, e, _, _ in sorted(device.ops):
            if merged and s <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], e)
            else:
                merged.append([s, e])
        return merged

    @property
    def busy_s(self) -> float:
        """Union of operation intervals, averaged over the devices."""
        if not self.devices:
            return 0.0
        total = sum(e - s for d in self.devices
                    for s, e in self.busy_intervals(d))
        return total * 1e-9 / len(self.devices)

    def idle_gaps(self) -> list:
        """(label, seconds) of every idle interval in the window, per
        device, labelled by the host span at its midpoint."""
        ws, we = self.window_ns
        gaps = []
        for d in self.devices:
            edge = ws
            for s, e in self.busy_intervals(d) + [[we, we]]:
                if s > edge:
                    gaps.append((self._label((edge + s) / 2),
                                 (s - edge) * 1e-9))
                edge = max(edge, e)
        return gaps

    def _label(self, t: float) -> str:
        covering = {n for s, e, n in self.spans if s <= t <= e}
        for name in GAP_LABELS:
            if name in covering:
                return name
        return "outside bench spans"

    def breakdown(self) -> dict:
        totals = {}
        for d in self.devices:
            for s, e, name, _ in d.ops:
                totals[name] = totals.get(name, 0) + (e - s)
        n = max(len(self.devices), 1)
        ops = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(self.idle_gaps(), key=lambda g: -g[1])[:TOP]
        return {"device_ops": [[name, ns * 1e-9 / n] for name, ns in ops],
                "idle_gaps": [[label, s] for label, s in gaps]}


def summarize(path, device_ids=None) -> Summary:
    """The window's device operations and host spans from ``path``.

    ``device_ids`` selects the device planes of the chips a cell used;
    by default every TPU plane with an operation in the window."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    spans, devices = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(BENCH_SPAN):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name))
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, "
                         f"found {len(windows)}")
    ws, we = windows[0]
    for plane in data.planes:
        match = DEVICE_PLANE.match(plane.name)
        if not match:
            continue
        if device_ids is not None and int(match.group(1)) not in device_ids:
            continue
        ops = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if e <= ws or s >= we:
                    continue
                ops.append((max(s, ws), min(e, we), short_name(ev.name),
                            is_kernel(ev.name)))
        if ops or device_ids is not None:
            devices.append(Device(plane=plane.name, ops=ops))
    return Summary(window_ns=(ws, we), devices=devices, spans=spans)


def dump(path, per_line: int = 8) -> None:
    """Print every plane and line of a trace, with a few events each."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for ev in events[:per_line]:
                print(f"    {ev.name!r} start={ev.start_ns} "
                      f"dur={ev.duration_ns} stats={_stats(ev)}")


if __name__ == "__main__":
    if len(sys.argv) != 3 or sys.argv[1] != "dump":
        sys.exit(__doc__)
    dump(sys.argv[2])
