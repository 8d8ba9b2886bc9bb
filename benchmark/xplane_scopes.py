"""The ``jax.named_scope`` of each device operation, from the profile.

``jax.profiler.ProfileData`` gives an event's own stats (its offset and
duration) but not those of its metadata, and the scope lies there: an
operation's ``XEventMetadata`` carries the framework's name for it
(``jit(tpu_model_forward)/.../dsa_score/dot_general``) as a stat. So
this reads the ``.xplane.pb`` itself: a protocol-buffer wire reader for
the five message types that hold it (XSpace, XPlane, XEventMetadata,
XStat, XStatMetadata; tsl/profiler/protobuf/xplane.proto), nothing
else decoded, no dependency.

    scopes(path) -> {operation's name as trace_reduce keys it: scope}

``seconds_under(reduced, scope_of, part)`` then adds up the device time
of the operations whose scope has ``part`` as one of its steps.
"""

from __future__ import annotations

import functools
import os

# the stat of an operation's metadata that holds its scoped name (in
# the v5e's profile under jax 0.9.0)
SCOPE_STAT = "tf_op"


def _varint(buf, i):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message; a length-
    delimited value is a memoryview, a fixed one its raw bytes."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        field, wire = tag >> 3, tag & 7
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
            raise ValueError(f"wire type {wire} in an xplane file")
        yield field, wire, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _map_entry(buf):
    key, value = 0, b""
    for field, _, v in _fields(buf):
        if field == 1:
            key = v
        elif field == 2:
            value = v
    return key, value


def _stat(buf):
    """(metadata id, value) of one XStat: a string, a reference to a
    stat's name, or a number."""
    ident, value = 0, None
    for field, wire, v in _fields(buf):
        if field == 1:
            ident = v
        elif field in (5, 6):
            value = _text(v)
        elif field == 7:
            value = ("ref", v)
        elif field in (3, 4) and wire == 0:
            value = v
    return ident, value


def plane_metadata(buf) -> dict:
    """One XPlane: its name and, for every event metadata, the name
    and the stats it carries, by the stats' own names."""
    name, events, stat_names = "", [], {}
    for field, _, v in _fields(buf):
        if field == 2:
            name = _text(v)
        elif field == 4:
            events.append(_map_entry(v)[1])
        elif field == 5:
            key, meta = _map_entry(v)
            for f, _, x in _fields(meta):
                if f == 2:
                    stat_names[key] = _text(x)
    out = {}
    for meta in events:
        ev_name, display, stats = "", "", {}
        for f, _, x in _fields(meta):
            if f == 2:
                ev_name = _text(x)
            elif f == 4:
                display = _text(x)
            elif f == 5:
                ident, value = _stat(x)
                if isinstance(value, tuple):
                    value = stat_names.get(value[1], "")
                stats[stat_names.get(ident, str(ident))] = value
        out[ev_name] = {"display_name": display, "stats": stats}
    return {"name": name, "events": out}


@functools.lru_cache(maxsize=2)
def device_metadata(path: str) -> dict:
    """{operation name: {"display_name", "stats"}} over the device
    planes of one profile."""
    from trace_reduce import find_trace
    if os.path.isdir(path):
        path = find_trace(path)
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for field, _, v in _fields(buf):
        if field == 1:
            plane = plane_metadata(v)
            if plane["name"].startswith("/device:"):
                out.update(plane["events"])
    return out


def scopes(path: str) -> dict:
    """{operation name: its scoped name}, for the operations that
    carry one; empty where the profile holds none."""
    out = {}
    for name, meta in device_metadata(path).items():
        value = meta["stats"].get(SCOPE_STAT)
        if isinstance(value, str) and "/" in value:
            out[name] = value
    return out


def for_run(ctx: dict):
    """The scopes of the traced run's profile, which ``run.py`` keeps at
    ``<root>/.bench_trace/<workload>`` until the readers have run (or
    those a test put into the context); None where there are none."""
    if "scopes" in ctx:
        return ctx["scopes"] or None
    cell = ctx.get("cell") or {}
    trace_dir = os.path.join(cell.get("root", ""), ".bench_trace",
                             cell.get("name", ""))
    if not ctx.get("trace") or not os.path.isdir(trace_dir):
        return None
    try:
        return scopes(trace_dir) or None
    except (FileNotFoundError, ValueError, IndexError):
        return None


def seconds_under(reduced: dict, scope_of: dict, part: str) -> tuple:
    """Device time and count of the window's operations whose scope has
    ``part`` among its steps."""
    seconds, count = 0.0, 0
    for name, v in reduced["ops"].items():
        if part in scope_of.get(name, "").split("/"):
            seconds += v["seconds"]
            count += v["count"]
    return seconds, count
