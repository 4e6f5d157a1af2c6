"""What ``jax.profiler.ProfileData`` hides: the stats on a device plane's
``XEventMetadata``.

A TPU trace under jax 0.9.0 carries, for every executed HLO instruction, the
stats ``tf_op`` (the instruction's ``op_name``: the ``jax.named_scope`` path
that produced it, ``jit(fixture_step)/matmuls/dot_general:``),
``hlo_category``, ``flops``, ``bytes_accessed`` and ``source`` (read by hand
from ``fixtures/v5e_fixture_step.xplane.pb``); the Python binding exposes an
event's name and times only.  This module reads ``tf_op`` from the raw
``XSpace`` bytes with a wire-format reader of its own — no protobuf schema,
no new dependency.

The fields it walks (tsl/profiler/protobuf/xplane.proto):

    XSpace.planes = 1
    XPlane.name = 2, .event_metadata = 4 (map), .stat_metadata = 5 (map)
    map entry: key = 1, value = 2
    XEventMetadata.name = 2, .stats = 5
    XStatMetadata.name = 2
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7
"""
from __future__ import annotations

import typing

from .reduce import DEVICE_PLANE, short_name

STAT = "tf_op"


def _varint(buf: bytes, i: int) -> typing.Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if not byte & 0x80:
            return value, i
        shift += 7


def fields(buf: bytes) -> typing.Iterator[typing.Tuple[int, typing.Any]]:
    """``(field number, value)`` of one message: an int for a varint, bytes
    for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} at byte {i}: not an XSpace")
        yield number, value


def _map_entry(buf: bytes) -> typing.Tuple[int, bytes]:
    entry = dict(fields(buf))
    return entry.get(1, 0), entry.get(2, b"")


def _plane_tf_ops(plane: bytes) -> typing.Tuple[str, typing.Dict[str, str]]:
    name, events, stat_names = "", [], {}
    for number, value in fields(plane):
        if number == 2:
            name = value.decode()
        elif number == 4:
            events.append(_map_entry(value)[1])
        elif number == 5:
            key, meta = _map_entry(value)
            stat_names[key] = dict(fields(meta)).get(2, b"").decode()
    out: typing.Dict[str, str] = {}
    if not DEVICE_PLANE.match(name):
        return name, out
    for meta in events:
        event_name, tf_op = "", None
        for number, value in fields(meta):
            if number == 2:
                event_name = value.decode()
            elif number == 5:
                stat = dict(fields(value))
                if stat_names.get(stat.get(1)) != STAT:
                    continue
                if 5 in stat:
                    tf_op = stat[5].decode()
                elif 7 in stat:       # a reference into the stat names
                    tf_op = stat_names.get(stat[7])
        if tf_op:
            out[short_name(event_name)] = tf_op
    return name, out


def tf_ops(path: str) -> typing.Optional[typing.Dict[str, typing.Dict[str, str]]]:
    """``{device plane: {instruction short name: tf_op}}`` of an
    ``.xplane.pb``; an instruction without the stat is left out.  ``None``
    where no device plane carries the stat at all (a CPU trace, another
    profiler version)."""
    with open(path, "rb") as f:
        space = f.read()
    planes = {}
    for number, value in fields(space):
        if number == 1:
            name, ops = _plane_tf_ops(value)
            if ops:
                planes[name] = ops
    return planes or None
