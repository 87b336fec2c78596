"""Read a profiler trace (``.xplane.pb``) with the standard library alone.

``jax.profiler.ProfileData`` gives each event its name and times, but not
the stats kept on the event's metadata, where the compiler records each
device operation's ``tf_op`` (the JAX name stack), ``bytes_accessed``,
``flops`` and ``source``.  This decodes the protobuf wire format of
``XSpace`` directly, by the field numbers of ``xplane.proto``
(``tsl/profiler/protobuf``):

    XSpace          planes=1
    XPlane          name=2 lines=3 event_metadata=4 stat_metadata=5
    XLine           name=2 timestamp_ns=3 events=4
    XEvent          metadata_id=1 offset_ps=2 duration_ps=3 stats=4
    XEventMetadata  id=1 name=2 stats=5
    XStatMetadata   id=1 name=2
    XStat           metadata_id=1 double=2 uint64=3 int64=4 str=5 bytes=6
                    ref=7 (the name of another stat metadata)

Times are whole nanoseconds, as ``ProfileData`` gives them.
"""

from __future__ import annotations

import dataclasses
import pathlib
import struct


@dataclasses.dataclass
class Event:
    start_ns: int
    duration_ns: int
    name: str
    stats: dict          # stat name -> value; the event's own over its metadata's


def _varint(buf: bytes, i: int) -> tuple:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf: bytes):
    """(field number, wire type, value) of each field of a message: an int
    for a varint, bytes for a length-delimited or fixed-width field."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield number, wire, value


def _int64(value: int) -> int:
    return value - (1 << 64) if value >= 1 << 63 else value


def _stat(buf: bytes, stat_names: dict) -> tuple:
    name, value = None, None
    for number, _, v in _fields(buf):
        if number == 1:
            name = stat_names.get(v, str(v))
        elif number == 2:
            value = struct.unpack("<d", v)[0]
        elif number == 3:
            value = v
        elif number == 4:
            value = _int64(v)
        elif number == 5:
            value = v.decode("utf-8", "replace")
        elif number == 6:
            value = v
        elif number == 7:
            value = stat_names.get(v, str(v))
    return name, value


def _plane(buf: bytes) -> tuple:
    """(name, {line name: [Event]}) of one XPlane."""
    fields = list(_fields(buf))
    stat_names = {}
    for number, _, v in fields:
        if number == 5:
            entry = dict((n, x) for n, _, x in _fields(v))
            meta = dict((n, x) for n, _, x in _fields(entry.get(2, b"")))
            stat_names[meta.get(1, 0)] = meta.get(2, b"").decode()
    metadata = {}
    for number, _, v in fields:
        if number == 4:
            entry = dict((n, x) for n, _, x in _fields(v))
            name, stats = "", {}
            for n, _, x in _fields(entry.get(2, b"")):
                if n == 2:
                    name = x.decode("utf-8", "replace")
                elif n == 5:
                    key, value = _stat(x, stat_names)
                    stats[key] = value
            metadata[entry.get(1, 0)] = (name, stats)
    plane_name, lines = "", {}
    for number, _, v in fields:
        if number == 2:
            plane_name = v.decode()
        elif number == 3:
            line_name, events = _line(v, metadata, stat_names)
            lines.setdefault(line_name, []).extend(events)
    return plane_name, lines


def _line(buf: bytes, metadata: dict, stat_names: dict) -> tuple:
    """(name, [Event]) of one XLine."""
    name, timestamp_ns, events = "", 0, []
    for number, _, v in _fields(buf):
        if number == 2:
            name = v.decode()
        elif number == 3:
            timestamp_ns = _int64(v)
        elif number == 4:
            events.append(v)
    return name, [_event(v, timestamp_ns, metadata, stat_names)
                  for v in events]


def _event(buf: bytes, timestamp_ns: int, metadata: dict,
           stat_names: dict) -> Event:
    meta_id, offset_ps, duration_ps, own = 0, 0, 0, {}
    for number, _, v in _fields(buf):
        if number == 1:
            meta_id = v
        elif number == 2:
            offset_ps = _int64(v)
        elif number == 3:
            duration_ps = _int64(v)
        elif number == 4:
            key, value = _stat(v, stat_names)
            own[key] = value
    name, stats = metadata.get(meta_id, ("", {}))
    return Event(start_ns=timestamp_ns + offset_ps // 1000,
                 duration_ns=duration_ps // 1000, name=name,
                 stats={**stats, **own})


def read(path) -> dict:
    """{plane name: {line name: [Event]}} of the trace at ``path``."""
    space = pathlib.Path(path).read_bytes()
    planes = {}
    for number, _, v in _fields(space):
        if number == 1:
            name, lines = _plane(v)
            planes[name] = lines
    return planes
