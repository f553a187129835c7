"""Each device op's JAX op name, read from the profiler's ``.xplane.pb``,
and the device time of the ops under one of the program's named scopes.

JAX's ``ProfileData`` gives an op event's name (the HLO instruction's
text) and its own stats, but not the stats of its event metadata, where
the ``tf_op`` stat holds the op's name path: the jit names and the
``jax.named_scope`` segments around it, e.g.
``jit(run_ops)/msl.scatter/scatter:``.  No protobuf class of the trace is
installed, so this reads the few fields it needs from the wire format:

    XSpace          1 planes (XPlane)
    XPlane          2 name, 4 event_metadata (map<int64, XEventMetadata>),
                    5 stat_metadata (map<int64, XStatMetadata>)
    XEventMetadata  2 name, 5 stats (XStat)
    XStatMetadata   1 id, 2 name
    XStat           1 metadata_id, 5 str_value

A fusion carries the op name of its root op, so an op is attributed to a
scope by its root.  Ops that XLA makes with no source op (the copy that
keeps an undonated parameter intact, some layout copies) have no ``tf_op``
and fall under no scope.
"""

from __future__ import annotations

import re

from bench.trace import clip, merge, union_ns

TF_OP = "tf_op"


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of a message: an int for a varint or a fixed
    field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _map_value(entry):
    return next((v for f, v in _fields(entry) if f == 2), b"")


def _plane_tf_ops(plane) -> tuple[str, dict]:
    name, events, stat_names = "", [], {}
    for f, v in _fields(plane):
        if f == 2:
            name = bytes(v).decode()
        elif f == 4:
            events.append(_map_value(v))
        elif f == 5:
            meta = dict(_fields(_map_value(v)))
            stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
    ops = {}
    for ev in events:
        ev_name, tf_op = "", None
        for f, v in _fields(ev):
            if f == 2:
                ev_name = bytes(v).decode()
            elif f == 5:
                stat = dict(_fields(v))
                if stat_names.get(stat.get(1)) == TF_OP and 5 in stat:
                    tf_op = bytes(stat[5]).decode()
        if tf_op is not None:
            ops[ev_name] = tf_op
    return name, ops


def tf_ops(path: str) -> dict:
    """{device: {op event name: tf_op}} for the ``/device:TPU:<n>`` planes
    of a trace; event names are those ``trace.reduce_file`` keeps."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out = {}
    for f, plane in _fields(space):
        if f != 1:
            continue
        name, ops = _plane_tf_ops(plane)
        m = re.fullmatch(r"/device:TPU:(\d+)", name)
        if m:
            out[int(m.group(1))] = ops
    return out


def scope_seconds(summary, ops_tf: dict, scope: str, program: str) -> float:
    """Device seconds per chip of the ops, inside the runs of the programs
    whose module name matches ``program``, whose ``tf_op`` path has the
    segment ``/<scope>/``; overlaps on one chip count once, as in
    ``Summary.seconds``."""
    rx = re.compile(program)
    seg = f"/{scope}/"
    if not summary.ops:
        return 0.0
    tot = 0
    for dev, evs in summary.ops.items():
        runs = merge([(s, e) for n, s, e in summary.modules.get(dev, [])
                      if rx.search(n)])
        names = ops_tf.get(dev, {})
        mine = [(s, e) for n, s, e in evs if seg in names.get(n, "")]
        tot += union_ns(clip(mine, runs))
    return tot * 1e-9 / len(summary.ops)
