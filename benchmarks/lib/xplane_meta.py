"""What `jax.profiler.ProfileData` does not hand out of an `.xplane.pb`:
the attributes the profiler keeps once per KIND of event (its
`XEventMetadata`), not on each event. For a device's operations that is
where the HLO `op_name` lives, and with it the `jax.named_scope` path
the program gave the operation.

The file is a protocol buffer (tsl/profiler/protobuf/xplane.proto); the
few fields needed are read off the wire format directly, with no schema
and nothing but the standard library:

    XSpace.planes = 1
    XPlane: name = 2, event_metadata = 4 (map: key = 1, value = 2),
            stat_metadata = 5 (map: key = 1, value = 2)
    XEventMetadata: id = 1, name = 2, display_name = 4, stats = 5
    XStatMetadata: id = 1, name = 2
    XStat: metadata_id = 1, str_value = 5, bytes_value = 6, ref_value = 7
           (a `ref_value` names a stat metadata whose NAME is the string)
"""

from __future__ import annotations

VARINT, FIXED64, BYTES, FIXED32 = 0, 1, 2, 5


def _varint(buf, i: int) -> tuple:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """(field number, wire type, value) of one message's top level;
    a length-delimited value is a `memoryview` of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, kind = key >> 3, key & 7
        if kind == VARINT:
            value, i = _varint(buf, i)
        elif kind == BYTES:
            size, i = _varint(buf, i)
            value = buf[i:i + size]
            i += size
        elif kind == FIXED64:
            value, i = buf[i:i + 8], i + 8
        elif kind == FIXED32:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {kind} at byte {i}")
        yield number, kind, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entries(plane, number: int):
    for f, kind, value in fields(plane):
        if f == number and kind == BYTES:
            entry = {n: v for n, _, v in fields(value)}
            if 2 in entry:
                yield entry.get(1, 0), entry[2]


def plane_strings(plane) -> dict:
    """{event name: the strings of that kind of event's attributes,
    `key=value` joined by spaces} of one XPlane's bytes."""
    stat_names = {}
    for key, meta in _map_entries(plane, 5):
        for f, kind, value in fields(meta):
            if f == 2 and kind == BYTES:
                stat_names[key] = _text(value)
    out = {}
    for _, meta in _map_entries(plane, 4):
        name, parts = "", []
        for f, kind, value in fields(meta):
            if f == 2 and kind == BYTES:
                name = _text(value)
            elif f == 5 and kind == BYTES:
                stat = {n: (k, v) for n, k, v in fields(value)}
                key = stat_names.get(stat.get(1, (0, 0))[1], "?")
                if 5 in stat:
                    parts.append(f"{key}={_text(stat[5][1])}")
                elif 6 in stat:
                    continue            # serialised protos: not text
                elif 7 in stat:
                    parts.append(
                        f"{key}={stat_names.get(stat[7][1], '?')}")
        if name and parts:
            out[name] = " ".join(parts)
    return out


def load(path: str, wanted=lambda plane_name: True) -> dict:
    """{plane name: `plane_strings`} of the planes whose name passes
    `wanted`."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, kind, plane in fields(space):
        if number != 1 or kind != BYTES:
            continue
        name = next((_text(v) for f, k, v in fields(plane)
                     if f == 2 and k == BYTES), "")
        if wanted(name):
            out[name] = plane_strings(plane)
    return out
