"""Read a profiler trace (`*.xplane.pb`, the XSpace protobuf) without
TensorFlow: the message types are declared here from the public schema
(tsl/profiler/protobuf/xplane.proto) and parsed by `google.protobuf`.

`jax.profiler.ProfileData` drops the per-op metadata stats (the HLO op's
`tf_op` name stack, where `jax.named_scope` scopes appear), which the
per-scope attribution needs, so the trace is read here instead.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

from google.protobuf import descriptor_pb2, descriptor_pool, message_factory

_F = descriptor_pb2.FieldDescriptorProto


def _field(msg, name, number, ftype, label=_F.LABEL_OPTIONAL, type_name=None,
           oneof=None):
    f = msg.field.add(name=name, number=number, type=ftype, label=label)
    if type_name:
        f.type_name = type_name
    if oneof is not None:
        f.oneof_index = oneof


@functools.lru_cache(maxsize=1)
def _space_class():
    fd = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="benchxp", syntax="proto3")
    rep = _F.LABEL_REPEATED

    st = fd.message_type.add(name="XStat")
    st.oneof_decl.add(name="value")
    _field(st, "metadata_id", 1, _F.TYPE_INT64)
    _field(st, "double_value", 2, _F.TYPE_DOUBLE, oneof=0)
    _field(st, "uint64_value", 3, _F.TYPE_UINT64, oneof=0)
    _field(st, "int64_value", 4, _F.TYPE_INT64, oneof=0)
    _field(st, "str_value", 5, _F.TYPE_STRING, oneof=0)
    _field(st, "bytes_value", 6, _F.TYPE_BYTES, oneof=0)
    _field(st, "ref_value", 7, _F.TYPE_UINT64, oneof=0)

    ev = fd.message_type.add(name="XEvent")
    ev.oneof_decl.add(name="data")
    _field(ev, "metadata_id", 1, _F.TYPE_INT64)
    _field(ev, "offset_ps", 2, _F.TYPE_INT64, oneof=0)
    _field(ev, "num_occurrences", 5, _F.TYPE_INT64, oneof=0)
    _field(ev, "duration_ps", 3, _F.TYPE_INT64)
    _field(ev, "stats", 4, _F.TYPE_MESSAGE, rep, ".benchxp.XStat")

    ln = fd.message_type.add(name="XLine")
    _field(ln, "id", 1, _F.TYPE_INT64)
    _field(ln, "display_id", 10, _F.TYPE_INT64)
    _field(ln, "name", 2, _F.TYPE_STRING)
    _field(ln, "display_name", 11, _F.TYPE_STRING)
    _field(ln, "timestamp_ns", 3, _F.TYPE_INT64)
    _field(ln, "duration_ps", 9, _F.TYPE_INT64)
    _field(ln, "events", 4, _F.TYPE_MESSAGE, rep, ".benchxp.XEvent")

    em = fd.message_type.add(name="XEventMetadata")
    _field(em, "id", 1, _F.TYPE_INT64)
    _field(em, "name", 2, _F.TYPE_STRING)
    _field(em, "display_name", 4, _F.TYPE_STRING)
    _field(em, "metadata", 3, _F.TYPE_BYTES)
    _field(em, "stats", 5, _F.TYPE_MESSAGE, rep, ".benchxp.XStat")
    _field(em, "child_id", 6, _F.TYPE_INT64, rep)

    sm = fd.message_type.add(name="XStatMetadata")
    _field(sm, "id", 1, _F.TYPE_INT64)
    _field(sm, "name", 2, _F.TYPE_STRING)
    _field(sm, "description", 3, _F.TYPE_STRING)

    pl = fd.message_type.add(name="XPlane")
    for nm, num, typ in (("EventMetadataEntry", 4, ".benchxp.XEventMetadata"),
                         ("StatMetadataEntry", 5, ".benchxp.XStatMetadata")):
        ent = pl.nested_type.add(name=nm)
        ent.options.map_entry = True
        _field(ent, "key", 1, _F.TYPE_INT64)
        _field(ent, "value", 2, _F.TYPE_MESSAGE, type_name=typ)
    _field(pl, "id", 1, _F.TYPE_INT64)
    _field(pl, "name", 2, _F.TYPE_STRING)
    _field(pl, "lines", 3, _F.TYPE_MESSAGE, rep, ".benchxp.XLine")
    _field(pl, "event_metadata", 4, _F.TYPE_MESSAGE, rep,
           ".benchxp.XPlane.EventMetadataEntry")
    _field(pl, "stat_metadata", 5, _F.TYPE_MESSAGE, rep,
           ".benchxp.XPlane.StatMetadataEntry")
    _field(pl, "stats", 6, _F.TYPE_MESSAGE, rep, ".benchxp.XStat")

    sp = fd.message_type.add(name="XSpace")
    _field(sp, "planes", 1, _F.TYPE_MESSAGE, rep, ".benchxp.XPlane")
    _field(sp, "errors", 2, _F.TYPE_STRING, rep)
    _field(sp, "warnings", 3, _F.TYPE_STRING, rep)
    _field(sp, "hostnames", 4, _F.TYPE_STRING, rep)

    pool = descriptor_pool.DescriptorPool()
    pool.Add(fd)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("benchxp.XSpace"))


class Event(NamedTuple):
    start_ps: int       # absolute: line timestamp plus offset
    dur_ps: int
    name: str           # event metadata name (the HLO instruction for ops)
    scope: str          # the op's `tf_op` name stack, "" when absent


class Line(NamedTuple):
    name: str
    events: list


class Plane(NamedTuple):
    name: str
    lines: list


def _stat_value(st):
    kind = st.WhichOneof("value")
    return None if kind is None else getattr(st, kind)


def read(path: str) -> list[Plane]:
    """Planes of the trace at ``path``, each line's events carrying absolute
    start times in picoseconds and, for device ops, their name stack."""
    space = _space_class()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    planes = []
    for pl in space.planes:
        stat_names = {k: v.name for k, v in pl.stat_metadata.items()}
        meta = {}
        for k, m in pl.event_metadata.items():
            scope = ""
            for st in m.stats:
                if stat_names.get(st.metadata_id) in ("tf_op", "long_name"):
                    v = _stat_value(st)
                    if isinstance(v, str) and "/" in v:
                        scope = v
                        break
                    if isinstance(v, int) and v in stat_names:
                        scope = stat_names[v]
                        break
            meta[k] = (m.name, scope)
        lines = []
        for ln in pl.lines:
            base = ln.timestamp_ns * 1000
            evs = []
            for ev in ln.events:
                nm, scope = meta.get(ev.metadata_id, ("", ""))
                evs.append(Event(base + ev.offset_ps, ev.duration_ps, nm,
                                 scope))
            lines.append(Line(ln.name, evs))
        planes.append(Plane(pl.name, lines))
    return planes
