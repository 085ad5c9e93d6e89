"""A recorded device trace, written by hand: the few protobuf fields of
an ``.xplane.pb`` that ``deepspeed_tpu/profiling/xplane.py`` decodes
(a CPU backend records no operation-level trace to keep).  The layout
is a v5e's, as the probe of PR 60 found it: an event holds its
metadata's id and its duration; the METADATA holds the operation's HLO
text as its name and, as stats, ``tf_op`` (the ``op_name`` path),
``program_id``, ``hlo_category``, ``flops``, ``raw_bytes_accessed``.
"""


def _uv(n):
    out = b""
    while True:
        b7 = n & 0x7F
        n >>= 7
        if n:
            out += bytes([b7 | 0x80])
        else:
            return out + bytes([b7])


def _tag(fno, wt):
    return _uv(fno << 3 | wt)


def _ld(fno, payload):
    return _tag(fno, 2) + _uv(len(payload)) + payload


def _vi(fno, val):
    return _tag(fno, 0) + _uv(val)


def _stat(mid, value):
    body = _vi(1, mid)
    if isinstance(value, str):
        return body + _ld(5, value.encode())
    return body + _vi(4, value)


def _map_entry(field, key, name, stats=b""):
    # map entries: key=1 varint, value=2 msg (id=1, name=2, stats=5)
    val = _vi(1, key) + _ld(2, name.encode()) + stats
    return _ld(field, _vi(1, key) + _ld(2, val))


def plane_bytes(name, metadata, lines):
    """One XPlane.  ``metadata``: {id: (event name, {stat: str | int})};
    ``lines``: {line name: [(metadata id, duration_ps), ...]}."""
    stat_ids = {}
    for _, stats in metadata.values():
        for stat in stats:
            stat_ids.setdefault(stat, len(stat_ids) + 1)
    body = _ld(2, name.encode())
    for line, events in lines.items():
        body += _ld(3, _ld(2, line.encode()) + b"".join(
            _ld(4, _vi(1, mid) + _vi(3, ps)) for mid, ps in events))
    for mid, (ev_name, stats) in metadata.items():
        body += _map_entry(4, mid, ev_name, b"".join(
            _ld(5, _stat(stat_ids[k], v)) for k, v in stats.items()))
    for stat, sid in stat_ids.items():
        body += _map_entry(5, sid, stat)
    return _ld(1, body)


def write_xspace(path, planes):
    """``planes``: [(plane name, metadata, lines)] -> the file at
    ``path``."""
    with open(path, "wb") as f:
        for plane in planes:
            f.write(plane_bytes(*plane))
    return str(path)
