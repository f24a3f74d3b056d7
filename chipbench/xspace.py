"""The JAX name stack of each device operation, read from a profiler trace.

Each device operation's event metadata in an ``.xplane.pb`` file carries a
``tf_op`` stat, ``<name stack>:<type>``: its JAX name stack is such as
``jit(_scan_block)/while/body/closed_call/commit_loop/while/body/
closed_call/install/scatter``, where ``jax.named_scope`` puts the scope
names.  ``jax.profiler.ProfileData`` does not expose that stat, so this
module reads the file's protocol-buffer wire format itself, with nothing
but the standard library; it reads only the planes' names, event metadata
and stat metadata, and skips their lines of events.

The messages read, from ``tsl/profiler/protobuf/xplane.proto``::

    XSpace         planes = 1
    XPlane         name = 2, event_metadata = 4 (map), stat_metadata = 5 (map)
    XEventMetadata name = 2, stats = 5
    XStatMetadata  name = 2
    XStat          metadata_id = 1, str_value = 5, ref_value = 7
"""
from __future__ import annotations

from typing import Dict, Iterator, Tuple

DEVICE_PREFIX = "/device:TPU:"
TF_OP = "tf_op"


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of each field of one message: an int for
    varints, ``bytes`` for length-delimited and fixed-width fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield key >> 3, v


def _map_values(entries) -> Iterator[bytes]:
    """Values of a protobuf map's entries (key = 1, value = 2)."""
    for entry in entries:
        for f, v in fields(entry):
            if f == 2:
                yield v


def _name(msg: bytes) -> str:
    for f, v in fields(msg):
        if f == 2:
            return v.decode("utf-8", "replace")
    return ""


def _plane_tf_ops(plane: bytes) -> Dict[str, str]:
    events, stat_meta = [], []
    for f, v in fields(plane):
        if f == 4:
            events.append(v)
        elif f == 5:
            stat_meta.append(v)
    stat_name = {}
    for m in _map_values(stat_meta):
        sid = 0
        for f, v in fields(m):
            if f == 1:
                sid = v
        stat_name[sid] = _name(m)
    out = {}
    for ev in _map_values(events):
        name, tf_op = "", None
        for f, v in fields(ev):
            if f == 2:
                name = v.decode("utf-8", "replace")
            elif f == 5:
                stat = dict(fields(v))
                if stat_name.get(stat.get(1)) != TF_OP:
                    continue
                if 5 in stat:
                    tf_op = stat[5].decode("utf-8", "replace")
                elif 7 in stat:
                    tf_op = stat_name.get(stat[7])
        if tf_op is not None:
            out[name] = tf_op.rpartition(":")[0] if ":" in tf_op else tf_op
    return out


def tf_ops(xspace: bytes) -> Dict[str, str]:
    """``{operation name: JAX name stack}`` from the ``tf_op`` stats of
    the TPU device planes of one serialized ``XSpace``; an operation's
    name is the one ``ProfileData`` gives its events (its HLO text)."""
    out: Dict[str, str] = {}
    for f, plane in fields(xspace):
        if f == 1 and _name(plane).startswith(DEVICE_PREFIX):
            out.update(_plane_tf_ops(plane))
    return out
