"""Minimal XSpace (xplane.pb) reader — no tensorflow/tensorboard needed.

THE decoder of a device trace's per-operation stats: the operator's
tables (``module_profiler``: ``engine.module_profile()``, ``ds_serve
--profile-steps``) and the benchmark's component reader
(``benchmarks/chip/readers_scopes.py``) both read an ``.xplane.pb``
through :func:`read_xspace`.  ``jax.profiler.ProfileData`` reads the
same file faster (0.4 s against 7 s for the chat cell's 27 MB, 345,000
events) and is what the benchmark's interval arithmetic uses, but it
shows an event's OWN stats only — on a v5e ``device_offset_ps``,
``device_duration_ps``, ``Time Scale Multiplier`` — and not the stats
of the event's METADATA, where the TPU runtime puts everything that
names an operation: ``tf_op`` (the instruction's ``op_name`` path),
``hlo_category``, ``program_id``, ``flops``, ``bytes_accessed``,
``source`` (the probe of PR 60, PERF.md section 7).

jax.profiler.trace writes TPU op-level timing as an XSpace protobuf
(tsl/profiler/protobuf/xplane.proto). The tensorboard profile plugin
that normally reads it drags in tensorflow + a protobuf-version
minefield, so this module hand-decodes the handful of fields its
readers consume (field numbers verified against tsl xplane_pb2):

    XSpace.planes = 1
    XPlane.name = 2, .lines = 3, .event_metadata = 4 (map),
          .stat_metadata = 5 (map)
    XLine.name = 2, .events = 4
    XEvent.metadata_id = 1, .duration_ps = 3, .stats = 4
    XEventMetadata.id = 1, .name = 2, .stats = 5
    XStat.metadata_id = 1, double=2, uint64=3, int64=4, str=5, bytes=6,
          ref=7
    XStatMetadata.id = 1, .name = 2

Wire format is standard protobuf: this is a ~100-line varint/length-
delimited walker, not a general proto library.
"""

import dataclasses
from typing import Any, Dict, List


def _varint(buf, i):
    shift = 0
    val = 0
    while True:
        b = buf[i]
        i += 1
        val |= (b & 0x7F) << shift
        if not b & 0x80:
            return val, i
        shift += 7


def _fields(buf):
    """Yield (field_number, wire_type, value) over a message buffer.
    value: int for varint/fixed, memoryview for length-delimited."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        fno, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 2:
            ln, i = _varint(buf, i)
            v = buf[i:i + ln]
            i += ln
        elif wt == 5:
            v = int.from_bytes(buf[i:i + 4], "little")
            i += 4
        elif wt == 1:
            v = int.from_bytes(buf[i:i + 8], "little")
            i += 8
        else:
            raise ValueError(f"unsupported wire type {wt}")
        yield fno, wt, v


@dataclasses.dataclass
class Event:
    metadata_id: int
    duration_ps: int
    stats: Dict[str, Any]


@dataclasses.dataclass
class Line:
    name: str
    events: List[Event]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]
    event_names: Dict[int, str]   # metadata_id -> op name
    event_stats: Dict[int, Dict[str, Any]]   # metadata-level stats


def _stat(buf, stat_names):
    mid = 0
    val = None
    for fno, wt, v in _fields(buf):
        if fno == 1:
            mid = v
        elif fno == 2 and wt == 1:   # double
            import struct
            val = struct.unpack("<d", v.to_bytes(8, "little"))[0]
        elif fno in (3, 4, 7):
            val = v
        elif fno == 5:
            val = bytes(v).decode("utf-8", "replace")
        elif fno == 6:
            val = bytes(v)
    return stat_names.get(mid, f"stat{mid}"), val


def _event(buf, stat_names):
    mid = dur = 0
    stats = {}
    for fno, wt, v in _fields(buf):
        if fno == 1:
            mid = v
        elif fno == 3:
            dur = v
        elif fno == 4:
            k, sv = _stat(bytes(v), stat_names)
            stats[k] = sv
    return Event(mid, dur, stats)


def _map_entry(buf):
    """proto map<k, v> entry: key=1, value=2 (message)."""
    key = None
    val = None
    for fno, wt, v in _fields(buf):
        if fno == 1:
            key = v
        elif fno == 2:
            val = bytes(v)
    return key, val


def _named_id(buf):
    """(id=1, name=2) prefix shared by XEventMetadata/XStatMetadata;
    also returns raw submessages of field 5 (metadata-level stats)."""
    mid = 0
    name = ""
    stat_bufs = []
    for fno, wt, v in _fields(buf):
        if fno == 1:
            mid = v
        elif fno == 2:
            name = bytes(v).decode("utf-8", "replace")
        elif fno == 5 and wt == 2:
            stat_bufs.append(bytes(v))
    return mid, name, stat_bufs


def _plane(buf):
    name = ""
    line_bufs = []
    em_bufs = []
    sm_bufs = []
    for fno, wt, v in _fields(buf):
        if fno == 2:
            name = bytes(v).decode("utf-8", "replace")
        elif fno == 3:
            line_bufs.append(bytes(v))
        elif fno == 4:
            em_bufs.append(bytes(v))
        elif fno == 5:
            sm_bufs.append(bytes(v))

    stat_names = {}
    for b in sm_bufs:
        _, vb = _map_entry(b)
        if vb is not None:
            mid, sname, _ = _named_id(vb)
            stat_names[mid] = sname

    event_names = {}
    event_stats = {}
    for b in em_bufs:
        _, vb = _map_entry(b)
        if vb is not None:
            mid, ename, stat_bufs = _named_id(vb)
            event_names[mid] = ename
            if stat_bufs:
                event_stats[mid] = dict(
                    _stat(sb, stat_names) for sb in stat_bufs)

    lines = []
    for lb in line_bufs:
        lname = ""
        ev_bufs = []
        for fno, wt, v in _fields(lb):
            if fno == 2:
                lname = bytes(v).decode("utf-8", "replace")
            elif fno == 4:
                ev_bufs.append(bytes(v))
        lines.append(Line(lname, [_event(eb, stat_names)
                                  for eb in ev_bufs]))
    return Plane(name, lines, event_names, event_stats)


def read_xspace(path):
    """Parse an .xplane.pb file -> list of Plane."""
    with open(path, "rb") as f:
        buf = f.read()
    planes = []
    for fno, wt, v in _fields(buf):
        if fno == 1:
            planes.append(_plane(bytes(v)))
    return planes


def device_plane(planes):
    """The TPU (or first device) plane with op events."""
    for p in planes:
        if p.name.startswith("/device:TPU") and any(
                l.name == "XLA Ops" for l in p.lines):
            return p
    for p in planes:
        if any(l.name == "XLA Ops" for l in p.lines):
            return p
    return None
