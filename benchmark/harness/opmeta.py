"""What a profiler trace (``.xplane.pb``) says of each operation besides
its times: the scope it was traced under.

On a TPU v5 lite the ``XLA Ops`` events carry the HLO line as their name and
three stats of their own (offset, duration, a multiplier); the
``op_name`` that ``jax.named_scope`` writes is a stat of the event's
*metadata*, ``tf_op`` (``jit(_pool_decode)/lm_head/dot_general:``; my chip
run, PR 26), beside ``program_id``, the number in the executable's name on
the ``XLA Modules`` line.  ``jax.profiler.ProfileData`` does not show
metadata stats, so the file is read here by its wire format: the few fields
of ``XSpace``/``XPlane``/``XEventMetadata``/``XStat`` that are needed, with
the lines (all of the file's bulk) skipped by their length.
"""
from __future__ import annotations

import functools
import os

from . import xplane

# field numbers of tsl/profiler/protobuf/xplane.proto
SPACE_PLANES = 1
PLANE_NAME, PLANE_EVENT_METADATA, PLANE_STAT_METADATA = 2, 4, 5
MAP_KEY, MAP_VALUE = 1, 2
META_NAME, META_STATS = 2, 5
STAT_METADATA_ID, STAT_UINT64, STAT_INT64, STAT_STR, STAT_REF = 1, 3, 4, 5, 7
U64 = (1 << 64) - 1


def _varint(buf, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, i
        shift += 7


def fields(buf):
    """``(field number, value)`` of one message: an int for a varint, the
    bytes for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError("wire type %d" % wire)
        yield key >> 3, value


def _map_entry(buf):
    entry = dict(fields(buf))
    return entry.get(MAP_KEY, 0), entry.get(MAP_VALUE, b"")


def op_scopes(path: str) -> dict:
    """``{(program id, operation's short name): scope}`` over the device
    planes of the trace at ``path``: the scope is the operation's
    ``op_name`` less its last part, the primitive (``jit(_step)/
    transpose(jvp(loss))/lm_head``).  A fusion has the ``op_name`` of its
    root.  Operations that carry none are left out.  A cell's scope
    metrics all read one file: it is read once."""
    return _op_scopes(path, os.path.getmtime(path))


@functools.lru_cache(maxsize=2)
def _op_scopes(path: str, _mtime: float) -> dict:
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for number, plane in fields(space):
        if number != SPACE_PLANES:
            continue
        name, events, stat_names = "", [], {}
        for number, value in fields(plane):
            if number == PLANE_NAME:
                name = bytes(value).decode()
            elif number == PLANE_EVENT_METADATA:
                events.append(_map_entry(value)[1])
            elif number == PLANE_STAT_METADATA:
                key, meta = _map_entry(value)
                stat_names[key] = bytes(dict(fields(meta)).get(
                    META_NAME, b"")).decode()
        if not xplane.DEVICE_PLANE.match(name):
            continue
        for meta in events:
            op, program, scope = "", None, None
            for number, value in fields(meta):
                if number == META_NAME:
                    op = xplane.short_name(bytes(value).decode())
                elif number == META_STATS:
                    stat = dict(fields(value))
                    kind = stat_names.get(stat.get(STAT_METADATA_ID))
                    if kind == "program_id":
                        program = stat.get(STAT_UINT64,
                                           stat.get(STAT_INT64, 0)) & U64
                    elif kind == "tf_op":
                        text = stat_names.get(stat[STAT_REF], "") \
                            if STAT_REF in stat \
                            else bytes(stat.get(STAT_STR, b"")).decode()
                        scope = text.rsplit(":", 1)[0].rpartition("/")[0]
            if scope:
                out[(program, op)] = scope
    return out
