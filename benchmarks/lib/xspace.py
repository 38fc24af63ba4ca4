"""What ``lib/xplane.py`` leaves out of a ``*.xplane.pb``, read from the file
itself: each device operation's scope, and the program's own host spans.

JAX's ``ProfileData`` hands out an event's name, times and its own stats, but
not the stats of the event's METADATA, and that is where the TPU runtime puts
an operation's ``tf_op``: the ``jax.named_scope`` path of the instruction
(``jit(_step_impl)/attention/kv_cache_update/scatter``; a fusion carries its
root's). So this file reads the protobuf's wire format directly, and only as
far as the two things it returns; it needs no generated ``xplane_pb2``.

XSpace{planes=1} XPlane{name=2 lines=3 event_metadata=4 stat_metadata=5}
XLine{name=2 timestamp_ns=3 events=4} XEvent{metadata_id=1 offset_ps=2
duration_ps=3} XEventMetadata{id=1 name=2 stats=5} XStat{metadata_id=1
str_value=5 ref_value=7} XStatMetadata{id=1 name=2}; a map entry is {key=1
value=2}.
"""

from __future__ import annotations

import re
from typing import Any, Dict, Iterator, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SCOPE_STAT = "tf_op"
Event = Tuple[str, float, float]  # name, start_s, end_s


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    result = shift = 0
    while True:
        byte = buf[i]
        i += 1
        result |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return result, i
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, Any]]:
    """(field number, value) of one message: an int for a varint, the bytes of
    a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield key >> 3, value


def _map_entry(buf: bytes) -> Tuple[int, bytes]:
    key, value = 0, b""
    for field, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def _plane(buf: bytes) -> Dict[str, Any]:
    plane: Dict[str, Any] = {"name": "", "lines": [], "event_metadata": [], "stat_names": {}}
    for field, v in _fields(buf):
        if field == 2:
            plane["name"] = v.decode()
        elif field == 3:
            plane["lines"].append(v)
        elif field == 4:
            plane["event_metadata"].append(v)
        elif field == 5:
            key, meta = _map_entry(v)
            plane["stat_names"][key] = next((x.decode() for f, x in _fields(meta) if f == 2), "")
    return plane


def _event_metadata(plane: Dict[str, Any]) -> Dict[int, Tuple[str, Dict[str, Any]]]:
    """id -> (name, {stat name: string or reference value})."""
    names = plane["stat_names"]
    out: Dict[int, Tuple[str, Dict[str, Any]]] = {}
    for entry in plane["event_metadata"]:
        key, meta = _map_entry(entry)
        name, stats = "", {}
        for field, v in _fields(meta):
            if field == 2:
                name = v.decode("utf-8", "replace")
            elif field == 5:
                stat = dict(_fields(v))
                value = stat.get(5, stat.get(7))
                stats[names.get(stat.get(1), "")] = value.decode("utf-8", "replace") if isinstance(value, bytes) else value
        out[key] = (name, stats)
    return out


def read(path: str, span_prefix: str) -> Dict[str, Any]:
    """``scopes``: for each device plane's operations, event name -> its
    ``tf_op`` (only where there is one). ``spans``: the host planes' events
    whose name starts with ``span_prefix``, sorted by start, on the same clock
    as the device events of ``xplane.load`` (line timestamp + event offset)."""
    with open(path, "rb") as fh:
        space = fh.read()
    scopes: Dict[str, str] = {}
    spans: List[Event] = []
    for field, buf in _fields(space):
        if field != 1:
            continue
        plane = _plane(buf)
        if DEVICE_PLANE.match(plane["name"]):
            for name, stats in _event_metadata(plane).values():
                scope = stats.get(SCOPE_STAT)
                if isinstance(scope, int):  # a reference to a stat metadata's name
                    scope = plane["stat_names"].get(scope, "")
                if scope:
                    scopes[name] = scope
        elif plane["name"].startswith("/host:"):
            wanted = {k: name for k, (name, _s) in _event_metadata(plane).items() if name.startswith(span_prefix)}
            if not wanted:
                continue
            for line in plane["lines"]:
                t0_ns, events = 0, []
                for f, v in _fields(line):
                    if f == 3:
                        t0_ns = v
                    elif f == 4:
                        events.append(v)
                for ev in events:
                    e = dict(_fields(ev))
                    if e.get(1) in wanted:
                        start = (t0_ns + e.get(2, 0) * 1e-3) * 1e-9
                        spans.append((wanted[e[1]], start, start + e.get(3, 0) * 1e-12))
    return {"scopes": scopes, "spans": sorted(spans, key=lambda s: s[1])}
